"""Flash-decode attention + decode-loop + fused-sampler tests.

Covers the ISSUE decode-overhaul acceptance criteria:
  - blocked kernel parity vs the dense attend-over-everything path
    (prefill, single-token decode at odd pos, t>1 chunked prefill,
    left-padded buckets) on BOTH the lax and pallas spellings;
  - the decode step never touches cache blocks beyond ceil((pos+t)/block)
    (NaN-poison proof + blocks_visited formula);
  - top-k-prefilter nucleus sampler exactness vs the full-sort
    sample_top_p under fixed keys, incl. the nucleus-overflow fallback,
    and a jaxpr assertion that the fast branch has no full-vocab sort;
  - the early-exit while_loop pads after EOS; the donated cache;
  - a caller's decode block that is no multiple of 8 fails loudly.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlefleetx_tpu.models.gpt import model as gpt
from paddlefleetx_tpu.models.gpt.config import GPTConfig
from paddlefleetx_tpu.models.gpt.generation import (
    GenerationConfig,
    generate,
    init_cache,
    pad_prompts,
)
from paddlefleetx_tpu.ops.decode_attention import (
    blocks_visited,
    decode_attention,
    decode_block,
    dense_cache_attention,
)
from paddlefleetx_tpu.ops.sampling import (
    sample_logits,
    sample_top_p,
    sample_top_p_topk,
)

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


def _rand_case(rng, b, t, n, d, L):
    q = jnp.asarray(rng.normal(size=(b, t, n, d)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(b, n, L, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(b, n, L, d)), jnp.float32)
    return q, kc, vc


# ---------------------------------------------------------------------------
# Kernel parity vs the dense path
# ---------------------------------------------------------------------------


# pallas-interpret variants follow the repo convention for kernel tests
# (test_flash_attention.py): slow suite — interpret-mode compiles dominate
# the tier-1 wall clock; the lax spelling shares all mask/online-softmax
# logic and stays in the fast subset
PALLAS = pytest.param("pallas", marks=pytest.mark.slow)


@pytest.mark.parametrize("impl", ["lax", PALLAS])
@pytest.mark.parametrize(
    "pos,t",
    [(0, 16), (13, 1), (7, 5), (39, 1)],  # prefill, odd-pos decode, chunked
)
def test_blocked_matches_dense(impl, pos, t):
    rng = np.random.default_rng(0)
    q, kc, vc = _rand_case(rng, 2, t, 4, 16, 40)
    ref = dense_cache_attention(q, kc, vc, jnp.int32(pos))
    got = decode_attention(q, kc, vc, jnp.int32(pos), impl=impl, block=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl,max_len", [("lax", 20), pytest.param(
    "pallas", 24, marks=pytest.mark.slow)])
def test_unaligned_cache_length_parity(impl, max_len):
    """A cache the block does not divide: decode_block rounds the clamp
    down to 16 and the clamped-start last block covers the tail — parity
    must hold.  The lax spelling takes any length (20); the pallas one
    takes what init_cache allocates (kv_cache_len: 24)."""
    rng = np.random.default_rng(5)
    q, kc, vc = _rand_case(rng, 2, 1, 4, 16, max_len)
    pos = jnp.int32(max_len - 1)
    ref = dense_cache_attention(q, kc, vc, pos)
    got = decode_attention(q, kc, vc, pos, impl=impl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_pallas_spelling_refuses_a_misaligned_cache():
    """The kernel's block loads carry an alignment hint Mosaic trusts; a
    cache length that would make it false is an error in the pallas
    spelling (explicit, or "auto" on a TPU) — never a silent lax reroute.
    init_cache allocates kv_cache_len slots, which always passes."""
    from paddlefleetx_tpu.ops.decode_attention import kv_cache_len

    rng = np.random.default_rng(6)
    q, kc, vc = _rand_case(rng, 1, 1, 2, 8, 20)
    with pytest.raises(ValueError, match="multiples of 8"):
        decode_attention(q, kc, vc, jnp.int32(3), impl="pallas")
    assert kv_cache_len(20) == 24 and kv_cache_len(24) == 24
    assert kv_cache_len(20, quantized=True) == 128
    assert init_cache(TINY, 2, 20).k.shape[3] == 24
    assert init_cache(TINY, 2, 20, kv_dtype="int8").k_scale.shape[3] == 128


@pytest.mark.parametrize("impl", ["lax", PALLAS])
def test_blocked_matches_dense_left_padded(impl):
    """kv_valid_from masks pre-prompt slots identically to the dense bias;
    compare only query rows at/after each row's first real token (fully
    masked pad rows are 0 on the blocked path, garbage-uniform on dense —
    neither is ever consumed downstream)."""
    rng = np.random.default_rng(1)
    pos, t = 0, 12
    q, kc, vc = _rand_case(rng, 2, t, 4, 16, 24)
    vf = jnp.asarray([5, 0], jnp.int32)
    ref = np.asarray(dense_cache_attention(q, kc, vc, jnp.int32(pos), kv_valid_from=vf))
    got = np.asarray(
        decode_attention(q, kc, vc, jnp.int32(pos), kv_valid_from=vf, impl=impl, block=8)
    )
    gp = pos + np.arange(t)
    for bi in range(2):
        rows = gp >= int(vf[bi])
        np.testing.assert_allclose(got[bi][rows], ref[bi][rows], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["lax", PALLAS])
def test_decode_never_visits_blocks_beyond_pos(impl):
    """NaN-poison everything past ceil((pos+t)/block)*block: a kernel that
    touches those slots propagates NaN through 0*NaN; the blocked path must
    stay finite (it never loads them), the dense path must NOT (it loads
    the whole buffer — the poison proves the probe works)."""
    rng = np.random.default_rng(2)
    pos, t, block = 13, 1, 16
    q, kc, vc = _rand_case(rng, 2, t, 4, 16, 48)
    lim = -(-(pos + t) // block) * block
    kc = kc.at[:, :, lim:, :].set(jnp.nan)
    vc = vc.at[:, :, lim:, :].set(jnp.nan)
    out = decode_attention(q, kc, vc, jnp.int32(pos), impl=impl, block=block)
    assert np.isfinite(np.asarray(out)).all()
    dense = dense_cache_attention(q, kc, vc, jnp.int32(pos))
    assert not np.isfinite(np.asarray(dense)).all()


def test_blocks_visited_formula():
    assert int(blocks_visited(1, 16, 64)) == 1
    assert int(blocks_visited(16, 16, 64)) == 1
    assert int(blocks_visited(17, 16, 64)) == 2
    assert int(blocks_visited(64, 16, 64)) == 4
    # clamped to the cache's total block count
    assert int(blocks_visited(64, 48, 64)) == 2
    # traced limit (the decode loop's pos + t) works too
    ns = jax.jit(lambda lim: blocks_visited(lim, 16, 64))(jnp.int32(33))
    assert int(ns) == 3


def test_decode_block_loud():
    assert decode_block(1024) == 256
    # clamping to a short cache must keep the multiple-of-8 tiling
    # invariant (round down), not hand Mosaic an unaligned block
    assert decode_block(100) == 96
    assert decode_block(20) == 16
    assert decode_block(1024, block=256) == 256
    assert decode_block(20, block=256) == 16
    # only a degenerate sub-8 cache yields a sub-8 block (lax-only path)
    assert decode_block(5) == 5
    assert decode_block(1024, block=128) == 128
    assert decode_block(1024, block=64) == 64
    for bad in (100, -8):  # not a multiple of 8; not positive
        with pytest.raises(ValueError, match="multiple of 8"):
            decode_block(1024, block=bad)
    with pytest.raises(ValueError, match="impl"):
        decode_attention(
            jnp.zeros((1, 1, 1, 8)), jnp.zeros((1, 1, 8, 8)),
            jnp.zeros((1, 1, 8, 8)), jnp.int32(0), impl="cuda",
        )


# ---------------------------------------------------------------------------
# End-to-end generation: the early-exit loop, the donated cache
# ---------------------------------------------------------------------------


def test_while_loop_early_exit_pads_after_eos():
    """Force EOS on the first step: the while loop must stop and the
    remaining slots must be pad-filled."""
    params = gpt.init(TINY, jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(3), (2, 4), 0, TINY.vocab_size)
    gen0 = GenerationConfig(max_dec_len=6, decode_strategy="greedy_search", eos_token_id=-1)
    firsts = np.asarray(generate(params, prompt, TINY, gen0))[:, 0]
    # eos = row 0's first greedy token: row 0 finishes at step 0
    gen = GenerationConfig(
        max_dec_len=6, decode_strategy="greedy_search",
        eos_token_id=int(firsts[0]), pad_token_id=0, min_dec_len=0,
    )
    out = np.asarray(generate(params, prompt, TINY, gen))
    assert out[0, 0] == int(firsts[0])
    assert np.all(out[0, 1:] == 0)


def test_generate_with_donated_cache_matches_internal():
    """generate(cache=..., return_cache=True) (the serving donation path)
    must equal the internally-allocated path; the donated buffer is
    consumed (aliased to the returned final cache), and RECYCLING the
    returned cache into a second request — stale tail slots and all —
    still produces identical tokens (the blocked kernel never visits
    blocks beyond pos+t, so stale data is unreachable)."""
    params = gpt.init(TINY, jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(4), (2, 8), 0, TINY.vocab_size)
    gen = GenerationConfig(max_dec_len=6, decode_strategy="greedy_search", eos_token_id=-1)
    ref = np.asarray(generate(params, prompt, TINY, gen))
    cache = init_cache(TINY, 2, 8 + 6)
    fn = jax.jit(
        lambda p, x, c: generate(p, x, TINY, gen, cache=c, return_cache=True),
        donate_argnums=(2,),
    )
    got, cache_out = fn(params, prompt, cache)
    np.testing.assert_array_equal(np.asarray(got), ref)
    assert cache.k.is_deleted(), "donated cache must be consumed"
    # recycle the returned (non-zero, stale-tailed) cache
    got2, _ = fn(params, prompt, cache_out)
    np.testing.assert_array_equal(np.asarray(got2), ref)
    with pytest.raises(ValueError, match="cache shape"):
        generate(params, prompt, TINY, gen, cache=init_cache(TINY, 2, 4))
    with pytest.raises(ValueError, match="beam_search"):
        generate(
            params, prompt, TINY,
            GenerationConfig(max_dec_len=6, decode_strategy="beam_search"),
            cache=init_cache(TINY, 2, 8 + 6),
        )


@pytest.mark.slow  # three per-prompt reference retraces; the same
# kv_valid_from fold is locked fast by test_blocked_matches_dense_left_padded
# and tests/test_generation.py::test_bucketed_greedy_matches_unpadded
def test_bucketed_generation_still_matches_unpadded():
    """Left-padded buckets through the BLOCKED kernel + while loop match
    per-prompt unpadded generation (the kv_valid_from fold is exercised
    end-to-end, not just at the op level)."""
    params = gpt.init(TINY, jax.random.key(0))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, TINY.vocab_size, n).tolist() for n in (3, 11)]
    gen = GenerationConfig(
        max_dec_len=6, decode_strategy="greedy_search", eos_token_id=-1, pad_token_id=0
    )
    refs = [
        np.asarray(generate(params, jnp.asarray([p]), TINY, gen))[0] for p in prompts
    ]
    padded, lens = pad_prompts(prompts, pad_token_id=0, multiple=16)
    out = np.asarray(generate(params, padded, TINY, gen, prompt_lens=lens))
    for i, r in enumerate(refs):
        np.testing.assert_array_equal(out[i], r)


# ---------------------------------------------------------------------------
# Fused nucleus sampling
# ---------------------------------------------------------------------------


def test_topk_prefilter_exact_vs_full_sort():
    """When every row's nucleus fits in the prefilter, the fast path must
    reproduce sample_top_p draw-for-draw (same key, same uniform, same
    prefix sums)."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(scale=3.0, size=(64, 1000)), jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_ps = jnp.full((64,), 0.9)
    for seed in range(3):
        key = jax.random.key(seed)
        ref = np.asarray(sample_top_p(key, probs, top_ps))
        got = np.asarray(sample_top_p_topk(key, probs, top_ps, k=64))
        np.testing.assert_array_equal(got, ref)


def test_topk_prefilter_overflow_falls_back():
    """A near-flat distribution overflows a small prefilter (cum_k < p):
    the guarded fallback must route to the full sort and still match."""
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(scale=0.01, size=(8, 512)), jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_ps = jnp.full((8,), 0.99)
    k = 16
    # sanity: the top-16 of a ~uniform 512-way dist covers ~3%, not 99%
    assert float(jnp.cumsum(jax.lax.top_k(probs, k)[0], -1)[:, -1].max()) < 0.99
    for seed in range(3):
        key = jax.random.key(seed)
        ref = np.asarray(sample_top_p(key, probs, top_ps))
        got = np.asarray(sample_top_p_topk(key, probs, top_ps, k=k))
        np.testing.assert_array_equal(got, ref)


def _sort_eqns(jaxpr, min_operand_len):
    """Recursively collect sort/argsort eqns whose operand trailing dim is
    >= min_operand_len (i.e. full-vocab sorts; lax.top_k is its own
    primitive and does not count)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort" and any(
            v.aval.shape and v.aval.shape[-1] >= min_operand_len
            for v in eqn.invars
        ):
            found.append(eqn)
        for sub in eqn.params.values():
            vals = sub if isinstance(sub, (list, tuple)) else [sub]
            for s in vals:
                if hasattr(s, "jaxpr"):
                    inner = s.jaxpr if hasattr(s.jaxpr, "eqns") else s
                    found += _sort_eqns(
                        inner if hasattr(inner, "eqns") else inner.jaxpr,
                        min_operand_len,
                    )
    return found


def test_fast_path_has_no_full_vocab_sort():
    """Acceptance: sample_logits(top_p<1) no longer argsorts the whole
    vocab on the fast path.  The cond's fast branch must contain no sort
    over a vocab-sized operand; the slow (fallback) branch keeps one."""
    vocab = 50257
    key = jax.random.key(0)
    logits = jnp.zeros((2, vocab))
    jaxpr = jax.make_jaxpr(
        lambda k, lg: sample_logits(k, lg, top_p=0.9)
    )(key, logits)
    conds = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "cond"]
    assert conds, "expected the prefilter lax.cond in the sampling jaxpr"
    branches = conds[-1].params["branches"]
    per_branch = [
        len(_sort_eqns(br.jaxpr, vocab)) for br in branches
    ]
    # one branch (the fallback) sorts the vocab, the other must not
    assert sorted(per_branch) == [0, 1], per_branch
    # and the pipeline OUTSIDE the guarded cond introduces no full sort
    # (top-level eqns only — the recursive walk would re-find the
    # fallback branch's sort inside the cond)
    top_level = [
        e for e in jaxpr.jaxpr.eqns
        if e.primitive.name == "sort" and any(
            v.aval.shape and v.aval.shape[-1] >= vocab for v in e.invars
        )
    ]
    assert not top_level


def test_topp_prefilter_off_draws_the_same_tokens():
    key = jax.random.key(0)
    logits = jnp.asarray(np.random.default_rng(2).normal(size=(4, 128)), jnp.float32)
    base = np.asarray(sample_logits(key, logits, top_p=0.9))
    full = np.asarray(sample_logits(key, logits, top_p=0.9, top_p_prefilter_k=0))  # the full sort alone
    np.testing.assert_array_equal(base, full)


# ---------------------------------------------------------------------------
# The paged kernels visit the live rows only (pfx_decode_paged,
# pfx_decode_window: the grid's first axis is the step's live list)
# ---------------------------------------------------------------------------

_LIVE_ROWS = 6
_LIVE_MASKS = {
    "all_live": [1, 1, 1, 1, 1, 1], "none_live": [0, 0, 0, 0, 0, 0],
    "first_dead": [0, 1, 1, 1, 1, 1], "last_dead": [1, 1, 1, 1, 1, 0],
    "alternate_dead": [1, 0, 1, 0, 1, 0],
}
_LIVE_BS, _LIVE_M, _LIVE_KV, _LIVE_D = 16, 12, 2, 8  # 12 pages: two groups of 8, the last ragged
_LIVE_WINDOW = 40


@functools.lru_cache(maxsize=None)
def _live_call(impl, windowed, masked):
    """One compiled call a (spelling, kind of layer, with or without a
    list): the mask is an argument, so its cases share the program."""
    from paddlefleetx_tpu.ops.decode_attention import live_slots, paged_decode_attention

    def call(q, k_pool, v_pool, tables, positions, active):
        starts = jnp.maximum(positions - (_LIVE_WINDOW - 1), 0) if windowed else None
        return paged_decode_attention(
            q, k_pool, v_pool, tables, positions, impl=impl, starts=starts,
            live=live_slots(active) if masked else None)

    return jax.jit(call)


@pytest.mark.parametrize("mask", list(_LIVE_MASKS))
@pytest.mark.parametrize("group", [1, 8], ids=["mha", "gqa8"])
@pytest.mark.parametrize("kind,t", [("full", 1), ("full", 3), ("window", 1)],
                         ids=["full-t1", "full-verify3", "window-t1"])
@pytest.mark.parametrize("impl", ["lax", "pallas"])
def test_the_paged_kernel_visits_the_live_rows_only(impl, kind, t, group, mask):
    """Both spellings, full and window layers, the decode step and a verify
    chunk, with and without shared KV heads: the rows the live list names
    equal the all-live call's rows to the bit; a row it leaves out is
    exactly 0 and is NEVER READ: its table points at pages of NaN and its
    position is stale, far past the table."""
    rng = np.random.default_rng(_LIVE_ROWS * t + group)
    b, bs, M, kv, d = _LIVE_ROWS, _LIVE_BS, _LIVE_M, _LIVE_KV, _LIVE_D
    nb = b * M + 2
    poison = nb - 1  # a page of NaN that no live row's table names
    k_pool, v_pool = (jnp.asarray(rng.normal(size=(nb, kv, bs, d)), jnp.float32)
                      .at[poison].set(jnp.nan) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(b, t, kv * group, d)), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, nb - 1))[: b * M].reshape(b, M), jnp.int32)
    positions = jnp.asarray([0, 17, 127, 128, 150, M * bs - t], jnp.int32)
    active = jnp.asarray(_LIVE_MASKS[mask], bool)

    windowed = kind == "window"
    every = _live_call(impl, windowed, False)(
        q, k_pool, v_pool, tables, positions, jnp.ones((b,), bool))
    assert bool(jnp.isfinite(every).all())
    got = _live_call(impl, windowed, True)(
        q, k_pool, v_pool, jnp.where(active[:, None], tables, poison),
        jnp.where(active, positions, M * bs + 1000), active)
    assert got.shape == q.shape and bool(jnp.isfinite(got).all())
    np.testing.assert_array_equal(np.asarray(got[active]), np.asarray(every[active]))
    assert bool((got[~active] == 0).all())


def test_one_function_makes_the_live_list_for_every_kernel():
    """`live_slots` lives beside the paged kernels and the state kernel's
    module hands out the same function: the step makes ONE list."""
    from paddlefleetx_tpu.models.gpt import generation
    from paddlefleetx_tpu.ops import decode_attention, ssm

    assert ssm.live_slots is decode_attention.live_slots is generation.live_slots
    live, count = decode_attention.live_slots(jnp.asarray([0, 1, 1, 0, 1], bool))
    assert live.dtype == count.dtype == jnp.int32 and count.shape == (1,)
    assert live[:3].tolist() == [1, 2, 4] and int(count[0]) == 3
    # the mask comes back from the list whatever fills its tail (slot 0, live or not)
    for mask in ([1, 0, 0, 1], [0, 0, 1, 1], [0, 0, 0, 0], [1, 1, 1, 1]):
        active = jnp.asarray(mask, bool)
        assert decode_attention._live_mask(decode_attention.live_slots(active), 4).tolist() \
            == active.tolist()
