"""Generation tests: cached decode == uncached forward; sampling ops."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlefleetx_tpu.models.gpt import model as gpt
from paddlefleetx_tpu.models.gpt.config import GPTConfig
from paddlefleetx_tpu.models.gpt.generation import (
    GenerationConfig,
    forward_cached,
    generate,
    init_cache,
    init_paged_pools,
    paged_forward_step,
    paged_prefill,
    serving_params,
)
from paddlefleetx_tpu.ops.sampling import sample_top_p, top_k_filter, top_p_filter

TINY = GPTConfig(
    vocab_size=97,
    hidden_size=64,
    num_layers=2,
    num_attention_heads=8,
    max_position_embeddings=64,
    hidden_dropout_prob=0.0,
    attention_probs_dropout_prob=0.0,
    dtype="float32",
)


def test_cached_prefill_matches_forward():
    params = gpt.init(TINY, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, TINY.vocab_size)
    ref = gpt.forward(params, tokens, TINY, train=False)
    cache = init_cache(TINY, 2, 32)
    got, _ = forward_cached(params, tokens, cache, jnp.int32(0), TINY)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_incremental_decode_matches_full_forward():
    """Token-by-token cached decode must equal the full uncached forward."""
    params = gpt.init(TINY, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (1, 12), 0, TINY.vocab_size)

    ref = gpt.forward(params, tokens, TINY, train=False)

    cache = init_cache(TINY, 1, 16)
    logits_steps = []
    for t in range(12):
        lg, cache = forward_cached(params, tokens[:, t : t + 1], cache, jnp.int32(t), TINY)
        logits_steps.append(lg[:, 0])
    got = jnp.stack(logits_steps, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=3e-4, atol=3e-4)


def test_greedy_generation_deterministic():
    params = gpt.init(TINY, jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (2, 8), 0, TINY.vocab_size)
    gen = GenerationConfig(max_dec_len=10, decode_strategy="greedy_search", eos_token_id=-1)
    out1 = generate(params, prompt, TINY, gen)
    out2 = generate(params, prompt, TINY, gen)
    assert out1.shape == (2, 10)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


@pytest.mark.slow  # ~9s (unjitted per-step full-forward python rollout);
# tier-1 budget funding for the shard_map-port tests.  Replacement
# coverage: cached-vs-uncached logits parity stays tier-1 at every decode
# step via test_incremental_decode_matches_full_forward, and greedy
# token-level parity stays tier-1 via test_bucketed_greedy_matches_unpadded
# + test_tp_generation_parity; still in make test-all.
def test_greedy_matches_uncached_argmax_rollout():
    params = gpt.init(TINY, jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (1, 6), 0, TINY.vocab_size)
    gen = GenerationConfig(max_dec_len=6, decode_strategy="greedy_search", eos_token_id=-1)
    out = np.asarray(generate(params, prompt, TINY, gen))[0]

    # slow rollout with full forward each step
    seq = np.asarray(prompt)[0].tolist()
    for _ in range(6):
        logits = gpt.forward(params, jnp.asarray([seq]), TINY, train=False)
        seq.append(int(jnp.argmax(logits[0, -1])))
    np.testing.assert_array_equal(out, np.asarray(seq[6:]))


def test_eos_stops_and_pads():
    params = gpt.init(TINY, jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (1, 4), 0, TINY.vocab_size)
    # force eos = the greedy-argmax first token -> everything after is pad
    gen0 = GenerationConfig(max_dec_len=5, decode_strategy="greedy_search", eos_token_id=-1)
    first = int(np.asarray(generate(params, prompt, TINY, gen0))[0, 0])
    gen = GenerationConfig(
        max_dec_len=5, decode_strategy="greedy_search", eos_token_id=first, pad_token_id=0,
        min_dec_len=0,
    )
    out = np.asarray(generate(params, prompt, TINY, gen))[0]
    assert out[0] == first
    assert np.all(out[1:] == 0)


def test_top_k_filter():
    logits = jnp.asarray([[1.0, 5.0, 3.0, 2.0]])
    f = top_k_filter(logits, 2)
    assert float(f[0, 1]) == 5.0 and float(f[0, 2]) == 3.0
    assert float(f[0, 0]) < -1e9 and float(f[0, 3]) < -1e9


def test_top_p_filter_keeps_nucleus():
    probs = jnp.asarray([[0.5, 0.3, 0.15, 0.05]])
    logits = jnp.log(probs)
    f = top_p_filter(logits, 0.7)
    # 0.5 alone < 0.7, 0.5+0.3 crosses -> keep first two
    assert np.isfinite(np.asarray(f)[0, :2]).all()
    assert np.asarray(f)[0, 2] < -1e9 and np.asarray(f)[0, 3] < -1e9


def test_sample_top_p_distribution():
    probs = jnp.tile(jnp.asarray([[0.6, 0.25, 0.1, 0.05]]), (2000, 1))
    ids = sample_top_p(jax.random.key(0), probs, jnp.full((2000,), 0.7))
    vals, counts = np.unique(np.asarray(ids), return_counts=True)
    assert set(vals.tolist()) <= {0, 1}  # nucleus = {0.6, 0.25}
    frac0 = counts[vals.tolist().index(0)] / 2000
    assert abs(frac0 - 0.6 / 0.85) < 0.05


# ---------------------------------------------------------------------------
# Beam search + processors + TP serving
# ---------------------------------------------------------------------------


def test_beam_search_shapes_and_determinism():
    params = gpt.init(TINY, jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (2, 6), 0, TINY.vocab_size)
    gen = GenerationConfig(
        max_dec_len=8, decode_strategy="beam_search", num_beams=4, eos_token_id=96
    )
    out1 = generate(params, prompt, TINY, gen)
    out2 = generate(params, prompt, TINY, gen)
    assert out1.shape == (2, 8)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_beam1_matches_greedy_prefix():
    """num_beams=1 beam search follows the same argmax path as greedy while
    EOS is suppressed (min_dec_len)."""
    params = gpt.init(TINY, jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(2), (2, 6), 0, TINY.vocab_size)
    n = 8
    g_greedy = GenerationConfig(
        max_dec_len=n, min_dec_len=n, decode_strategy="greedy_search",
        eos_token_id=96,
    )
    g_beam = GenerationConfig(
        max_dec_len=n, min_dec_len=n, decode_strategy="beam_search",
        num_beams=1, eos_token_id=96,
    )
    a = np.asarray(generate(params, prompt, TINY, g_greedy))
    b = np.asarray(generate(params, prompt, TINY, g_beam))
    np.testing.assert_array_equal(a[:, : n - 1], b[:, : n - 1])


def test_beam_score_improves_on_greedy():
    """Beam search's chosen sequence log-prob >= the greedy path's.

    NB: beam search does not guarantee this in general (the greedy prefix
    can be evicted from the top-K mid-decode); the fixed seed/model here is
    known to keep the property — if a numeric change flips it, check the
    eviction explanation before suspecting the beam code."""
    params = gpt.init(TINY, jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(3), (1, 6), 0, TINY.vocab_size)
    n = 6

    def score(seq):
        """Sum log p of continuation `seq` after `prompt` (teacher forced)."""
        full = jnp.concatenate([prompt, seq[None]], axis=1)
        logits = gpt.forward(params, full, TINY, train=False)
        lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
        cont = lp[0, prompt.shape[1] - 1 :]
        return float(
            sum(cont[t, int(seq[t])] for t in range(n))
        )

    g_greedy = GenerationConfig(
        max_dec_len=n, min_dec_len=n, decode_strategy="greedy_search", eos_token_id=96
    )
    g_beam = GenerationConfig(
        max_dec_len=n, min_dec_len=n, decode_strategy="beam_search",
        num_beams=4, eos_token_id=96,
    )
    s_greedy = score(np.asarray(generate(params, prompt, TINY, g_greedy))[0])
    s_beam = score(np.asarray(generate(params, prompt, TINY, g_beam))[0])
    assert s_beam >= s_greedy - 1e-4


def test_forced_bos_eos_tokens():
    params = gpt.init(TINY, jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(4), (2, 4), 0, TINY.vocab_size)
    gen = GenerationConfig(
        max_dec_len=6, decode_strategy="greedy_search", eos_token_id=-1,
        forced_bos_token_id=11, forced_eos_token_id=13,
    )
    out = np.asarray(generate(params, prompt, TINY, gen))
    np.testing.assert_array_equal(out[:, 0], 11)
    np.testing.assert_array_equal(out[:, -1], 13)


def test_hamming_diversity_penalizes_decided_tokens():
    """Tokens chosen by earlier groups this step must be penalized out of
    the argmax for the current group (HammingDiversityLogitsProcessor)."""
    from paddlefleetx_tpu.models.gpt.generation import apply_hamming_diversity

    vocab = 16
    logits = jnp.zeros((2, vocab)).at[:, 5].set(1.0).at[:, 7].set(0.9)
    # groups 0..1 (beams 0,1) already chose token 5 this step; beam 2+ TBD
    current = jnp.array([5, 5, -1, -1], jnp.int32)
    out = apply_hamming_diversity(logits, current, group_start=2, penalty=10.0)
    # token 5 penalized twice -> argmax moves to 7
    assert int(jnp.argmax(out[0])) == 7
    # penalty counts only DECIDED beams (indices < group_start)
    np.testing.assert_allclose(float(logits[0, 5]) - float(out[0, 5]), 20.0)
    # undecided sentinel (-1) contributes nothing
    np.testing.assert_allclose(np.asarray(out[:, :vocab - 1][:, 6:]),
                               np.asarray(logits[:, 6:vocab - 1]), atol=1e-6)


def test_diverse_beam_search_runs_e2e():
    params = gpt.init(TINY, jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(5), (1, 4), 0, TINY.vocab_size)
    n = 4
    gen = GenerationConfig(
        max_dec_len=n, min_dec_len=n, decode_strategy="beam_search",
        num_beams=4, num_beam_groups=4, diversity_penalty=1.5, eos_token_id=96,
    )
    out = np.asarray(generate(params, prompt, TINY, gen))
    assert out.shape == (1, n)


TINY_TP = GPTConfig(
    vocab_size=96,  # divisible by mp=2 (the vocab axis is model-sharded)
    hidden_size=64,
    num_layers=2,
    num_attention_heads=8,
    max_position_embeddings=64,
    hidden_dropout_prob=0.0,
    attention_probs_dropout_prob=0.0,
    dtype="float32",
)


def test_tp_generation_parity(devices8):
    """generate() on a dp4 x mp2 mesh (heads-sharded KV cache) must equal
    the single-device greedy rollout (VERDICT r1 item 5)."""
    from paddlefleetx_tpu.parallel.mesh import MeshConfig, build_mesh
    from paddlefleetx_tpu.parallel.sharding import make_rules, tree_logical_to_sharding

    params = gpt.init(TINY_TP, jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(6), (2, 8), 0, TINY_TP.vocab_size)
    gen = GenerationConfig(max_dec_len=8, decode_strategy="greedy_search", eos_token_id=-1)
    ref = np.asarray(generate(params, prompt, TINY_TP, gen))

    mesh = build_mesh(MeshConfig(dp_degree=4, mp_degree=2), devices8)
    rules = make_rules(mesh=mesh)
    ctx = gpt.ShardingCtx(mesh, rules)
    shardings = tree_logical_to_sharding(gpt.gpt_logical_axes(TINY_TP), mesh, rules)
    p_sh = jax.device_put(params, shardings)
    with mesh:
        got = np.asarray(
            jax.jit(lambda p, x: generate(p, x, TINY_TP, gen, ctx=ctx))(p_sh, prompt)
        )
    np.testing.assert_array_equal(got, ref)


def test_tp_beam_search_parity(devices8):
    """Beam search on a TP mesh equals single-device beam search.

    Was xfailed since PR 1 as a "jax-0.4.37 TP numerics divergence" —
    root-caused in the shard_map-port PR: GSPMD left the beam scan's
    bookkeeping carry marked partial-over-`model` (every emitted token id
    came back exactly mp_degree x the true value); generation.beam_search
    now pins the carry sharding each step (`_pin_beam`)."""
    from paddlefleetx_tpu.parallel.mesh import MeshConfig, build_mesh
    from paddlefleetx_tpu.parallel.sharding import make_rules, tree_logical_to_sharding

    params = gpt.init(TINY_TP, jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(7), (1, 6), 0, TINY_TP.vocab_size)
    gen = GenerationConfig(
        max_dec_len=6, decode_strategy="beam_search", num_beams=4, eos_token_id=96
    )
    ref = np.asarray(generate(params, prompt, TINY_TP, gen))
    mesh = build_mesh(MeshConfig(dp_degree=4, mp_degree=2), devices8)
    rules = make_rules(mesh=mesh)
    ctx = gpt.ShardingCtx(mesh, rules)
    shardings = tree_logical_to_sharding(gpt.gpt_logical_axes(TINY_TP), mesh, rules)
    p_sh = jax.device_put(params, shardings)
    with mesh:
        got = np.asarray(
            jax.jit(lambda p, x: generate(p, x, TINY_TP, gen, ctx=ctx))(p_sh, prompt)
        )
    np.testing.assert_array_equal(got, ref)


# ---------------------------------------------------------------------------
# Bucketed serving: left-padded prompts (VERDICT r1 weak #4)
# ---------------------------------------------------------------------------


def test_bucketed_greedy_matches_unpadded():
    """Left-padded bucketed prompts must generate exactly what each prompt
    generates unpadded (mask + position-id correctness)."""
    from paddlefleetx_tpu.models.gpt.generation import pad_prompts

    params = gpt.init(TINY, jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, TINY.vocab_size, n).tolist() for n in (5, 9, 12)
    ]
    gen = GenerationConfig(
        max_dec_len=8, decode_strategy="greedy_search", eos_token_id=-1,
        pad_token_id=0,
    )
    # reference: each prompt alone, unpadded
    refs = [
        np.asarray(generate(params, jnp.asarray([p]), TINY, gen))[0]
        for p in prompts
    ]
    padded, lens = pad_prompts(prompts, pad_token_id=0, multiple=16)
    assert padded.shape[1] == 16  # one bucket
    out = np.asarray(
        generate(params, padded, TINY, gen, prompt_lens=lens)
    )
    for i, r in enumerate(refs):
        np.testing.assert_array_equal(out[i], r)


def test_bucketed_beam_matches_unpadded():
    from paddlefleetx_tpu.models.gpt.generation import pad_prompts

    params = gpt.init(TINY, jax.random.key(0))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, TINY.vocab_size, n).tolist() for n in (4, 7)]
    gen = GenerationConfig(
        max_dec_len=6, decode_strategy="beam_search", num_beams=4,
        eos_token_id=96, pad_token_id=0,
    )
    refs = [
        np.asarray(generate(params, jnp.asarray([p]), TINY, gen))[0]
        for p in prompts
    ]
    padded, lens = pad_prompts(prompts, pad_token_id=0, multiple=8)
    out = np.asarray(generate(params, padded, TINY, gen, prompt_lens=lens))
    for i, r in enumerate(refs):
        np.testing.assert_array_equal(out[i], r)


def test_pad_prompts_bucket_width():
    from paddlefleetx_tpu.models.gpt.generation import pad_prompts

    padded, lens = pad_prompts([[1, 2, 3], [4] * 70], pad_token_id=0, multiple=64)
    assert padded.shape == (2, 128)
    assert lens.tolist() == [3, 70]
    assert padded[0, :125].sum() == 0  # left padding
    assert padded[0, 125:].tolist() == [1, 2, 3]


# ---------------------------------------------------------------------------
# serving_params: the tree a server holds is in the dtype the step computes
# in (cast once, not inside every decode step), to the same bits
# ---------------------------------------------------------------------------

TINY_BF16 = dataclasses.replace(TINY, dtype="bfloat16")


@pytest.fixture(scope="module")
def trees():
    """(float32 tree, cast tree) of the tiny bf16 model.  Every leaf is
    perturbed: fresh biases are zeros and fresh scales ones, which round
    to themselves whatever a cast does."""
    p32 = gpt.init(TINY_BF16, jax.random.key(0))
    leaves, treedef = jax.tree.flatten(p32)
    keys = jax.random.split(jax.random.key(7), len(leaves))
    p32 = treedef.unflatten([
        x + 0.02 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)
    ])
    return p32, serving_params(p32, TINY_BF16)


@pytest.mark.parametrize("group,want", [
    ("embeddings", "bfloat16"), ("layers/attn", "bfloat16"),
    ("layers/mlp", "bfloat16"), ("layers/ln_1", "float32"),
    ("layers/ln_2", "float32"), ("final_ln", "float32"),
])
def test_serving_params_casts_what_the_forwards_cast_and_nothing_else(
    trees, group, want
):
    p32, cast = trees
    for part in group.split("/"):
        p32, cast = p32[part], cast[part]
    assert set(cast) == set(p32) and cast
    for name, leaf in cast.items():
        assert str(leaf.dtype) == want, (group, name)
        # the served bits are the float32 leaf's, rounded once
        np.testing.assert_array_equal(
            np.asarray(leaf.astype(jnp.float32)),
            np.asarray(p32[name].astype(leaf.dtype).astype(jnp.float32)),
        )


def test_serving_params_returns_a_float32_configurations_tree_itself():
    params = gpt.init(TINY, jax.random.key(0))
    assert serving_params(params, TINY) is params


def _f32(tree):
    return [np.asarray(x.astype(jnp.float32)) for x in jax.tree.leaves(tree)]


def _paged_inputs(kv_dtype):
    nb, bs = 9, 8
    pools = init_paged_pools(TINY_BF16, nb, bs, kv_dtype=kv_dtype)
    # two rows with some context already in their blocks, so attention
    # reads pool contents and not only the step's own token
    filled = jax.tree.map(
        lambda x: (jax.random.normal(jax.random.key(5), x.shape) * 0.5).astype(x.dtype)
        if x.dtype != jnp.int8
        else jax.random.randint(jax.random.key(6), x.shape, -90, 90, jnp.int8),
        pools,
    )
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    return filled, tables


def _run(case, kv_dtype, params):
    cfg = TINY_BF16
    if case == "forward_cached":
        tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
        cache = init_cache(cfg, 2, 32, kv_dtype=kv_dtype)
        logits, cache = jax.jit(
            lambda p: forward_cached(p, tokens, cache, jnp.int32(0), cfg))(params)
        step, cache = jax.jit(
            lambda p, c: forward_cached(p, tokens[:, :1], c, jnp.int32(16), cfg)
        )(params, cache)
        return logits, step, cache
    pools, tables = _paged_inputs(kv_dtype)
    if case == "paged_prefill":
        prompt = jax.random.randint(jax.random.key(2), (1, 16), 0, cfg.vocab_size)
        return jax.jit(lambda p: paged_prefill(
            p, prompt, jnp.int32(13), pools, tables[0, :2], cfg))(params)
    t = {"paged_step": 1, "paged_verify_chunk": 4}[case]
    tokens = jax.random.randint(jax.random.key(3), (2, t), 0, cfg.vocab_size)
    positions = jnp.asarray([11, 19], jnp.int32)
    return jax.jit(lambda p: paged_forward_step(
        p, tokens, pools, tables, positions, jnp.ones((2,), bool), cfg))(params)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize(
    "case", ["forward_cached", "paged_step", "paged_verify_chunk", "paged_prefill"])
def test_cast_tree_serves_bit_equal_logits_and_pools(trees, case, kv_dtype):
    """Each matmul consumed round_bf16(w) from the float32 tree and
    consumes the same bits from the cast one: not close, equal."""
    p32, cast = trees
    want, got = _f32(_run(case, kv_dtype, p32)), _f32(_run(case, kv_dtype, cast))
    assert len(want) == len(got) >= 3
    for w, g in zip(want, got):
        assert np.isfinite(w).all()
        np.testing.assert_array_equal(g, w)


# ---------------------------------------------------------------------------
# The arena rides the layer loop as the CARRY and is written in place: the
# kernel is handed the whole stack and a layer.  The reference below is the
# semantics that replaced: one layer's pool at a time, out of the stack and
# back into it.
# ---------------------------------------------------------------------------


def _per_layer_reference(params, tokens, pools, tables, positions, active, cfg,
                         n_valid=None):
    """`paged_forward_step` as a plain loop over the layers, each a call of
    its own on ``pools.k[l]`` / ``pools.v[l]`` alone (a stack of one)."""
    from paddlefleetx_tpu.models.gpt.generation import (
        _in_dtype,
        _paged_layer_step,
        layer_norm,
        live_slots,
    )

    t = tokens.shape[1]
    emb = _in_dtype("embeddings", params["embeddings"], jnp.dtype(cfg.dtype))
    word, pe = emb["word"], emb["position"]
    pos_t = positions[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    x = word[tokens] + pe[jnp.clip(
        jnp.where(active[:, None], pos_t, 0), 0, cfg.max_position_embeddings - 1)]
    bs = pools.k.shape[3]
    blk = jnp.take_along_axis(
        tables, jnp.clip(pos_t // bs, 0, tables.shape[1] - 1), axis=1)
    blk = jnp.where(active[:, None], blk, 0)
    if n_valid is not None:
        blk = jnp.where(jnp.arange(t)[None, :] < n_valid[:, None], blk, 0)
    off = pos_t % bs
    live = live_slots(active)
    layer_step = jax.jit(_paged_layer_step, static_argnums=(9,))
    out = []
    for l in range(cfg.num_layers):
        p_l = jax.tree.map(lambda a: a[l], params["layers"])
        one = jax.tree.map(lambda a: a[l][None], pools)
        x, one = layer_step(
            p_l, x, one, jnp.int32(0), blk, off, tables, positions, live, cfg)
        out.append(one)
    x = layer_norm(x, params["final_ln"]["scale"], params["final_ln"]["bias"])
    logits = jnp.einsum("bsh,vh->bsv", x, word).astype(jnp.float32)
    return logits, jax.tree.map(lambda *a: jnp.concatenate(a), *out)


# name -> (t, positions, active, n_valid); two rows over _paged_inputs'
# tables unless the case is the one-row prefill chunk
_ARENA_CASES = {
    "step_with_an_inactive_row": (1, [11, 19], [True, False], None),
    "verify_chunk": (3, [11, 19], [True, True], None),
    "one_row_chunk_with_pad_slots": (6, [10], [True], [4]),
}


def _arena_step(fn, case, kv_dtype, params):
    t, positions, active, n_valid = _ARENA_CASES[case]
    cfg = TINY_BF16
    pools, tables = _paged_inputs(kv_dtype)
    rows = len(positions)
    tokens = jax.random.randint(jax.random.key(3), (rows, t), 0, cfg.vocab_size)
    n_valid = None if n_valid is None else jnp.asarray(n_valid, jnp.int32)
    logits, out = fn(
        params, tokens, pools, tables[:rows], jnp.asarray(positions, jnp.int32),
        jnp.asarray(active), cfg, n_valid=n_valid)
    return pools, logits, out


_step = jax.jit(paged_forward_step, static_argnums=(6,))


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("case", list(_ARENA_CASES))
def test_carried_arena_is_bit_equal_to_the_per_layer_loop(trees, case, kv_dtype):
    _, params = trees
    _, logits, pools = _arena_step(_step, case, kv_dtype, params)
    _, want_logits, want = _arena_step(
        _per_layer_reference, case, kv_dtype, params)
    assert np.isfinite(np.asarray(want_logits)).all()
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want_logits))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), pools) == jax.tree.map(
        lambda a: (a.shape, a.dtype), want)
    got, ref = _f32(pools), _f32(want)
    assert len(got) == len(ref) == (2 if kv_dtype == "bf16" else 4)
    for g, w in zip(got, ref):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_each_layer_writes_its_own_blocks_of_the_arena(trees, kv_dtype):
    """Row 0 at position 11 writes slot 3 of its block 2 in EVERY layer: with
    the layer dropped from the address, both layers' writes would land in
    layer 0's block.  Each layer's block changes, to its own values, and
    nothing but the rows' slots (and the null block's) moves anywhere."""
    _, params = trees
    before, _, after = _arena_step(
        _step, "step_with_an_inactive_row", kv_dtype, params)
    for b, a in zip(_f32(before), _f32(after)):
        changed = (a != b).reshape(a.shape[:4] + (-1,)).any(-1)  # [L, nb, n, bs]
        for l in range(TINY_BF16.num_layers):
            # the active row's slot (block 2, offset 3), every head
            assert changed[l, 2, :, 3].all()
            # the inactive row's write went to this layer's null block
            where = np.argwhere(changed[l])
            assert set(where[:, 0]) <= {0, 2}
            assert (where[where[:, 0] == 2][:, 2] == 3).all()
        assert (a[0, 2, :, 3] != a[1, 2, :, 3]).any()
