"""Speculative decoding + int8 KV-cache quantization
(`ops/speculative.py`, the spec spellings in `models/gpt/generation.py`,
engine wiring in `core/continuous_batching.py`).

The acceptance criteria, in-process and deterministic:

  - GREEDY speculative output is TOKEN-IDENTICAL (f32 exact assert) to
    the non-speculative path on BOTH decode paths — the contiguous
    while-loop and the paged/continuous engine — including mid-decode
    admission/eviction and full-rejection iterations;
  - SAMPLED speculation preserves the target distribution (statistical
    test on a tiny vocab — the Leviathan residual rule);
  - int8 KV decode matches the unquantized kernels within quantization
    tolerance, and arena payload bytes HALVE vs bf16 (block bytes x
    pfx_kv_blocks_used is the evidence `pfx_kv_bytes` reports);
  - accepted-length variation is runtime data: repeating spec traffic
    keys ZERO extra compiles (the bounded-retrace contract).

Heavy suites are slow-marked and ride `make test-spec`; tier-1 keeps the
lean acceptance core (870s budget — see the Makefile tiering notes).
"""

import numpy as np
import pytest

# same tiny shapes as test_continuous_batching so the persistent compile
# cache is shared across files
TINY = {
    "Global": {"global_batch_size": 8, "seed": 3},
    "Engine": {"mix_precision": {"enable": False},
               "save_load": {"save_steps": 0}},
    "Model": {
        "module": "GPTModule",
        "vocab_size": 96,
        "hidden_size": 32,
        "num_layers": 2,
        "num_attention_heads": 4,
        "max_position_embeddings": 128,
        "dtype": "float32",
    },
    "Distributed": {},
    "Optimizer": {"name": "FusedAdamW",
                  "lr": {"name": "Constant", "learning_rate": 1e-3}},
    "Generation": {"max_dec_len": 8, "decode_strategy": "greedy_search",
                   "pad_to_multiple": 16, "eos_token_id": 95,
                   "pad_token_id": 0},
}

PROMPTS = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 10], [11, 12, 13, 14]]


# ---------------------------------------------------------------------------
# pure units: drafters, config parsing, multi-position sampling
# ---------------------------------------------------------------------------


def test_ngram_propose_host_lookup_and_fallback():
    from paddlefleetx_tpu.ops.speculative import ngram_propose_host

    # needle [2, 3]: last earlier occurrence ends at index 2 -> continue 4, 1, 2
    assert ngram_propose_host([1, 2, 3, 4, 1, 2, 3], 3, n=2) == [4, 1, 2]
    # continuation shorter than k: the last proposed token repeats
    # (needle [7, 8] ends at index 1 -> continuation [7, 8], padded)
    assert ngram_propose_host([7, 8, 7, 8], 3, n=2) == [7, 8, 8]
    # no match: repeat the last token
    assert ngram_propose_host([5, 6, 7], 3, n=2) == [7, 7, 7]
    with pytest.raises(ValueError, match="k >= 1"):
        ngram_propose_host([1], 0)


def test_ngram_propose_in_graph_matches_host_semantics():
    import jax.numpy as jnp

    from paddlefleetx_tpu.ops.speculative import ngram_propose

    ctx = jnp.asarray([[1, 2, 3, 4, 1, 2, 0, 0, 0, 0],
                       [9, 9, 9, 9, 9, 9, 0, 0, 0, 0]])
    known = jnp.int32(6)
    # row 0: needle (2, 3) ends an occurrence at p=2 -> draft 4, 1, 2;
    # row 1: needle (9, 9) matches everywhere, LAST valid end p=4 ->
    # draft ctx[5] = 9 then clamps to the fallback (pending) past known
    draft = ngram_propose(ctx, known, jnp.asarray([3, 9]), 3, n=2)
    assert draft.tolist()[0] == [4, 1, 2]
    assert draft.tolist()[1] == [9, 9, 9]
    # no match anywhere: fallback repeats pending
    fb = ngram_propose(ctx, known, jnp.asarray([42, 42]), 3, n=2)
    assert fb.tolist() == [[42, 42, 42], [42, 42, 42]]


def test_spec_config_parse_and_loud_errors():
    from paddlefleetx_tpu.ops.speculative import SpecConfig, spec_config_from

    assert spec_config_from({}) is None
    assert spec_config_from(None) is None
    sc = spec_config_from({"draft_k": 3, "ngram": 2})
    assert sc == SpecConfig(draft_k=3, ngram=2)
    with pytest.raises(ValueError, match="drafter"):
        spec_config_from({"draft_k": 2, "drafter": "medusa"})
    with pytest.raises(ValueError, match="draft_k"):
        SpecConfig(draft_k=0)


def test_sample_logits_multi_position_and_single_position_pin():
    """The satellite refactor: [b, k, vocab] verify logits sample with
    per-position subkeys; the original [b, vocab] contract is pinned
    (deterministic draw for a fixed key, one-hot logits force their
    token through every filter combination)."""
    import jax
    import jax.numpy as jnp

    from paddlefleetx_tpu.ops.sampling import sample_logits

    key = jax.random.key(7)
    # old single-position behavior: degenerate one-hot always samples it
    one_hot = jnp.full((4, 32), -1e9).at[jnp.arange(4), [3, 9, 21, 30]].set(0.0)
    for kw in ({}, {"top_k": 4}, {"top_p": 0.9}, {"temperature": 0.5}):
        got = sample_logits(key, one_hot, **kw)
        assert got.shape == (4,)
        assert got.tolist() == [3, 9, 21, 30], kw
    # and the draw for a fixed key is deterministic
    soft = jax.random.normal(key, (4, 32))
    a = sample_logits(key, soft, top_p=0.9)
    b = sample_logits(key, soft, top_p=0.9)
    assert a.tolist() == b.tolist()

    # multi-position: [b, k, v] -> [b, k]; each position draws its OWN
    # forced token (per-position subkeys, independent positions)
    forced = jnp.stack([
        jnp.full((4, 32), -1e9).at[jnp.arange(4), [1, 2, 3, 4]].set(0.0),
        jnp.full((4, 32), -1e9).at[jnp.arange(4), [5, 6, 7, 8]].set(0.0),
    ], axis=1)  # [4, 2, 32]
    got = sample_logits(key, forced, top_p=0.9)
    assert got.shape == (4, 2)
    assert got[:, 0].tolist() == [1, 2, 3, 4]
    assert got[:, 1].tolist() == [5, 6, 7, 8]


# ---------------------------------------------------------------------------
# contiguous-path greedy parity (raw generate(), no server)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    import jax

    from paddlefleetx_tpu.models.gpt import model as gpt
    from paddlefleetx_tpu.models.gpt.config import GPTConfig

    cfg = GPTConfig(
        vocab_size=96, hidden_size=32, num_layers=2, num_attention_heads=4,
        max_position_embeddings=128, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, dtype="float32",
    )
    return cfg, gpt.init(cfg, jax.random.key(0))


def test_contiguous_greedy_spec_token_identical(tiny_model):
    """THE contiguous acceptance parity (f32 exact): random prompts (low
    acceptance — rejection/correction exercised) and a repetitive prompt
    (high acceptance — multi-token commits exercised), plus the
    committed-vs-proposed accounting."""
    import jax
    import jax.numpy as jnp

    from paddlefleetx_tpu.models.gpt.generation import GenerationConfig, generate
    from paddlefleetx_tpu.ops.speculative import SpecConfig

    cfg, params = tiny_model
    gen = GenerationConfig(
        decode_strategy="greedy_search", max_dec_len=20, eos_token_id=95
    )
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(1, 96, size=(3, 8)), jnp.int32)
    rep = jnp.asarray(np.tile([11, 23, 7, 41], (3, 2)), jnp.int32)
    for ids in (prompts, rep):
        base = generate(params, ids, cfg, gen, key=jax.random.key(1))
        toks, (prop, acc) = generate(
            params, ids, cfg, gen, key=jax.random.key(1),
            spec=SpecConfig(draft_k=4), return_spec_stats=True,
        )
        np.testing.assert_array_equal(np.asarray(base), np.asarray(toks))
        assert int(prop) > 0 and 0 <= int(acc) <= int(prop)


def test_contiguous_spec_full_rejection_still_token_identical(tiny_model, monkeypatch):
    """Every draft wrong on every iteration (the drafter is forced to a
    token the target never argmaxes): the loop degrades to one committed
    token per verify — output must STILL be token-identical, with zero
    accepted drafts."""
    import jax
    import jax.numpy as jnp

    from paddlefleetx_tpu.models.gpt import generation
    from paddlefleetx_tpu.models.gpt.generation import GenerationConfig, generate
    from paddlefleetx_tpu.ops.speculative import SpecConfig

    cfg, params = tiny_model
    gen = GenerationConfig(
        decode_strategy="greedy_search", max_dec_len=10, eos_token_id=95
    )
    ids = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    base = generate(params, ids, cfg, gen, key=jax.random.key(1))
    # verified below: 77 never appears in the baseline output, so a
    # constant-77 draft is rejected at every slot
    assert 77 not in np.asarray(base)
    monkeypatch.setattr(
        generation, "ngram_propose",
        lambda ctx, known, pending, k, n=2: jnp.full(
            (ctx.shape[0], k), 77, jnp.int32
        ),
    )
    toks, (prop, acc) = generate(
        params, ids, cfg, gen, key=jax.random.key(1),
        spec=SpecConfig(draft_k=3), return_spec_stats=True,
    )
    np.testing.assert_array_equal(np.asarray(base), np.asarray(toks))
    assert int(acc) == 0 and int(prop) == 3 * 10 * 2  # k * steps * rows


def test_contiguous_spec_eos_and_left_padding_parity(tiny_model):
    """EOS mid-decode (early-exit + pad fill) and left-padded serving
    buckets both stay token-identical under speculation."""
    import jax
    import jax.numpy as jnp

    from paddlefleetx_tpu.models.gpt.generation import (
        GenerationConfig,
        generate,
        pad_prompts,
    )
    from paddlefleetx_tpu.ops.speculative import SpecConfig

    cfg, params = tiny_model
    spec = SpecConfig(draft_k=4)
    # forced EOS fires mid-window: exercises eos_hit truncation + pads
    gen = GenerationConfig(
        decode_strategy="greedy_search", max_dec_len=12, eos_token_id=95,
        forced_eos_token_id=95,
    )
    ids = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    base = generate(params, ids, cfg, gen, key=jax.random.key(1))
    toks = generate(params, ids, cfg, gen, key=jax.random.key(1), spec=spec)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(toks))

    gen2 = GenerationConfig(
        decode_strategy="greedy_search", max_dec_len=8, eos_token_id=95
    )
    padded, lens = pad_prompts(PROMPTS[:3], 0, multiple=16)
    base2 = generate(params, padded, cfg, gen2, key=jax.random.key(1),
                     prompt_lens=lens)
    toks2 = generate(params, padded, cfg, gen2, key=jax.random.key(1),
                     prompt_lens=lens, spec=spec)
    np.testing.assert_array_equal(np.asarray(base2), np.asarray(toks2))


@pytest.mark.slow  # two extra compiles; make test-spec / test-all
def test_contiguous_spec_repetition_penalty_parity(tiny_model):
    """repetition_penalty != 1 routes the verify through the sequential
    counts-aware processor chain — still token-identical."""
    import jax
    import jax.numpy as jnp

    from paddlefleetx_tpu.models.gpt.generation import GenerationConfig, generate
    from paddlefleetx_tpu.ops.speculative import SpecConfig

    cfg, params = tiny_model
    gen = GenerationConfig(
        decode_strategy="greedy_search", max_dec_len=14, eos_token_id=95,
        repetition_penalty=1.3, min_dec_len=3,
    )
    ids = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    base = generate(params, ids, cfg, gen, key=jax.random.key(1))
    toks = generate(params, ids, cfg, gen, key=jax.random.key(1),
                    spec=SpecConfig(draft_k=3))
    np.testing.assert_array_equal(np.asarray(base), np.asarray(toks))


# ---------------------------------------------------------------------------
# paged / continuous engine parity
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def server():
    import jax

    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.core.serving import GenerationServer
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import AttrDict, process_configs

    cfg = AttrDict.from_nested(TINY)
    cfg = process_configs(cfg, num_devices=jax.device_count())
    mesh = init_dist_env(cfg)
    module = build_module(cfg)
    return GenerationServer(cfg, mesh, module)


@pytest.fixture(scope="module")
def sequential(server):
    return [server.generate_ids([p], max_dec_len=6)[0] for p in PROMPTS]


def _engine(server, **kw):
    from paddlefleetx_tpu.core.continuous_batching import PagedDecodeEngine

    kw.setdefault("max_batch", 4)
    return PagedDecodeEngine(server, **kw)


def _drain(engine, max_steps=64):
    for _ in range(max_steps):
        engine.step()
        if not engine.active.any():
            return
    raise AssertionError("engine never drained")


def test_paged_spec_parity_with_admission_eviction_and_retrace_bound(
    server, sequential
):
    """THE paged acceptance parity (f32 exact): speculative rows admitted
    mid-decode of the running batch AND a mid-decode eviction decode
    token-identically to the sequential coalesce path; per-row accepted
    lengths vary per iteration yet repeating the traffic adds ZERO
    compiles (accepted length is runtime data, never a compile key)."""
    from paddlefleetx_tpu.ops.speculative import SpecConfig

    eng = _engine(server, spec=SpecConfig(draft_k=3))
    s0 = eng.admit(PROMPTS[0], 6)
    s1 = eng.admit(PROMPTS[1], 6)
    eng.step()
    s2 = eng.admit(PROMPTS[2], 6)   # mid-decode admission
    eng.release(s1)                 # mid-decode eviction
    s3 = eng.admit(PROMPTS[3], 6)
    _drain(eng)
    assert eng.slots[s0].tokens == sequential[0]
    assert eng.slots[s2].tokens == sequential[2]
    assert eng.slots[s3].tokens == sequential[3]
    for s in (s0, s2, s3):
        eng.release(s)
    assert eng.cache.stats()["kv_blocks_used"] == 0
    assert eng.stats["spec_proposed"] > 0

    # retrace bound: the same traffic mix again — and the evicted prompt
    # alone — keys zero fresh compiles even though accepted lengths and
    # batch composition differ per iteration
    traces = eng.stats["traces"]
    slots = [eng.admit(p, 6) for p in PROMPTS]
    _drain(eng)
    assert [eng.slots[s].tokens for s in slots] == sequential
    assert eng.stats["traces"] == traces, eng.stats


def test_paged_spec_full_rejection_iterations(server, sequential, monkeypatch):
    """Forced all-wrong drafts: every iteration commits exactly one
    token per row (ncommit degenerates to the baseline), output stays
    token-identical and the acceptance counter reads zero."""
    from paddlefleetx_tpu.core import continuous_batching as cb
    from paddlefleetx_tpu.ops.speculative import SpecConfig

    flat = [t for row in sequential for t in row]
    assert 77 not in flat  # the forced draft token never argmaxes
    monkeypatch.setattr(
        cb, "ngram_propose_host", lambda seq, k, n=2: [77] * k
    )
    eng = _engine(server, spec=SpecConfig(draft_k=3))
    slots = [eng.admit(p, 6) for p in PROMPTS[:2]]
    _drain(eng)
    assert [eng.slots[s].tokens for s in slots] == sequential[:2]
    assert eng.stats["spec_accepted"] == 0
    assert eng.stats["spec_proposed"] > 0


def test_paged_spec_scheduler_end_to_end(server, sequential):
    """The threaded ContinuousScheduler over a speculative engine
    resolves futures with the sequential-path tokens and exports the
    acceptance metrics through its collector."""
    from paddlefleetx_tpu.core.continuous_batching import ContinuousScheduler
    from paddlefleetx_tpu.ops.speculative import SpecConfig
    from paddlefleetx_tpu.utils.telemetry import get_registry

    eng = _engine(server, spec=SpecConfig(draft_k=3))
    sched = ContinuousScheduler(eng, max_depth=8)
    sched.start()
    futs = [sched.submit([p], 6, deadline_s=120) for p in PROMPTS]
    got = [f.result(timeout=300)[0] for f in futs]
    assert got == sequential
    snap = {
        name: vals for name, _, vals in (
            (n, l, v) for n, l, v in sched.collect()
        )
    }
    assert "pfx_spec_accept_rate" in snap
    assert snap["pfx_kv_bytes"] >= 0
    reg = get_registry()
    assert reg.counter("pfx_spec_proposed_total").get() > 0
    assert sched.shutdown(timeout=30)


# ---------------------------------------------------------------------------
# int8 KV-cache quantization
# ---------------------------------------------------------------------------


def test_int8_attention_matches_native_within_tolerance():
    """Both spellings of both kernels: quantize a random cache/arena and
    compare against the unquantized math — per-(slot, head) amax/127
    symmetric quantization bounds the attention-output error far below
    the parity tolerance."""
    import jax.numpy as jnp

    from paddlefleetx_tpu.ops.decode_attention import (
        decode_attention,
        paged_decode_attention,
        quantize_kv,
    )

    rng = np.random.default_rng(0)
    # L: what init_cache allocates for an int8 cache (kv_cache_len rounds to
    # the 128 lanes the kernel slices its scale rows along)
    b, n, d, L = 2, 2, 8, 128
    q = jnp.asarray(rng.normal(size=(b, 3, n, d)).astype(np.float32))
    kc = jnp.asarray(rng.normal(size=(b, n, L, d)).astype(np.float32))
    vc = jnp.asarray(rng.normal(size=(b, n, L, d)).astype(np.float32))
    base = np.asarray(decode_attention(q, kc, vc, jnp.int32(12), impl="lax"))
    kq, ks = quantize_kv(kc)
    vq, vs = quantize_kv(vc)
    assert kq.dtype == jnp.int8 and ks.shape == (b, n, L)
    for impl in ("lax", "pallas"):
        got = np.asarray(decode_attention(
            q, kq, vq, jnp.int32(12), impl=impl, k_scale=ks, v_scale=vs
        ))
        np.testing.assert_allclose(got, base, atol=0.05)

    bs, nb, M = 8, 10, 3
    kp = jnp.asarray(rng.normal(size=(nb, n, bs, d)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(nb, n, bs, d)).astype(np.float32))
    tables = jnp.asarray([[1, 2, 3], [4, 5, 0]], jnp.int32)
    positions = jnp.asarray([10, 5], jnp.int32)
    pbase = np.asarray(paged_decode_attention(
        q, kp, vp, tables, positions, impl="lax"
    ))
    kpq, kps = quantize_kv(kp)
    vpq, vps = quantize_kv(vp)
    for impl in ("lax", "pallas"):
        got = np.asarray(paged_decode_attention(
            q, kpq, vpq, tables, positions, impl=impl,
            k_scale=kps, v_scale=vps,
        ))
        np.testing.assert_allclose(got, pbase, atol=0.05)
    # scales travel in pairs — loud otherwise
    with pytest.raises(ValueError, match="both"):
        decode_attention(q, kq, vq, jnp.int32(12), k_scale=ks)


def test_int8_arena_bytes_halve_and_e2e_parity(server, sequential):
    """The acceptance evidence: per-block K+V payload bytes under int8
    are exactly HALF the bf16 arena's (pfx_kv_bytes = blocks_used x
    block bytes), and an int8 engine still serves the parity prompts
    within tolerance (token-identical on this tiny f32 model)."""
    import jax.numpy as jnp

    from paddlefleetx_tpu.models.gpt.generation import init_paged_pools

    eng8 = _engine(server, kv_dtype="int8")
    assert eng8.pools.k.dtype == jnp.int8
    assert eng8.pools.k_scale is not None
    # bf16 reference arena of the same geometry: int8 payload is half
    bf16 = init_paged_pools(
        eng8.mcfg, eng8.cache.allocator.num_blocks, eng8.block,
        dtype=jnp.bfloat16, kv_dtype="bf16",
    )
    layers, _, heads, bs, d = bf16.k.shape
    bf16_block_bytes = 2 * layers * heads * bs * d * bf16.k.dtype.itemsize
    assert eng8.kv_block_bytes() * 2 == bf16_block_bytes

    slots = [eng8.admit(p, 6) for p in PROMPTS]
    used = eng8.cache.stats()["kv_blocks_used"]
    assert used > 0
    _drain(eng8)
    got = [eng8.slots[s].tokens for s in slots]
    # tolerance contract: identical lengths always; this tiny f32 model
    # is argmax-stable under the ~1/127 quantization error, so assert
    # token identity outright (a real bf16 model counts divergences in
    # the bench row instead)
    assert got == sequential


@pytest.mark.slow  # extra engine compiles; make test-spec / test-all
def test_int8_plus_speculation_compose(server, sequential):
    from paddlefleetx_tpu.ops.speculative import SpecConfig

    eng = _engine(server, spec=SpecConfig(draft_k=3), kv_dtype="int8")
    slots = [eng.admit(p, 6) for p in PROMPTS]
    _drain(eng)
    assert [eng.slots[s].tokens for s in slots] == sequential
    assert eng.stats["spec_proposed"] > 0


# ---------------------------------------------------------------------------
# sampled mode: distribution preservation (tiny vocab, statistical)
# ---------------------------------------------------------------------------


@pytest.mark.slow  # statistical batch is its own compile; make test-spec
def test_sampled_spec_preserves_distribution_tiny_vocab():
    """Leviathan residual rule end-to-end: 1024 identical rows decode 4
    tokens with and without speculation; the per-position empirical
    token distributions must agree within sampling noise (calibrated by
    a baseline-vs-baseline control at a different key).  Runs the
    filtered (temperature + top-p) pipeline so the residual math is
    exercised where it is subtle."""
    import jax
    import jax.numpy as jnp

    from paddlefleetx_tpu.models.gpt import model as gpt
    from paddlefleetx_tpu.models.gpt.config import GPTConfig
    from paddlefleetx_tpu.models.gpt.generation import GenerationConfig, generate
    from paddlefleetx_tpu.ops.speculative import SpecConfig

    cfg = GPTConfig(
        vocab_size=16, hidden_size=16, num_layers=1, num_attention_heads=2,
        max_position_embeddings=32, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, dtype="float32",
    )
    params = gpt.init(cfg, jax.random.key(0))
    gen = GenerationConfig(
        decode_strategy="sampling", max_dec_len=4, temperature=0.9,
        top_p=0.8, eos_token_id=15, pad_token_id=0,
    )
    B = 1024
    ids = jnp.tile(jnp.asarray([[3, 7, 2, 9]], jnp.int32), (B, 1))

    def marginals(tokens):
        t = np.asarray(tokens)
        return np.stack([
            np.bincount(t[:, j], minlength=16) / t.shape[0]
            for j in range(t.shape[1])
        ])

    base = marginals(generate(params, ids, cfg, gen, key=jax.random.key(1)))
    ctrl = marginals(generate(params, ids, cfg, gen, key=jax.random.key(2)))
    spec = marginals(generate(
        params, ids, cfg, gen, key=jax.random.key(3),
        spec=SpecConfig(draft_k=2),
    ))

    # total-variation distance per position: spec-vs-base must sit in
    # the same noise band as base-vs-base (2x margin + epsilon)
    tv_ctrl = 0.5 * np.abs(base - ctrl).sum(axis=1)
    tv_spec = 0.5 * np.abs(base - spec).sum(axis=1)
    assert (tv_spec <= 2.0 * tv_ctrl + 0.06).all(), (tv_spec, tv_ctrl)


# ---------------------------------------------------------------------------
# serving-layer wiring
# ---------------------------------------------------------------------------


@pytest.mark.slow  # fresh server boot + compiles; make test-spec / test-all
def test_serving_config_routes_speculation_and_counts():
    """Generation.speculative.draft_k in the config routes generate_ids
    through the spec loop: output token-identical to a plain server,
    acceptance counters live on stats/registry, and repeat traffic keys
    no extra traces."""
    import copy

    import jax

    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.core.serving import GenerationServer
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import AttrDict, process_configs

    tiny = copy.deepcopy(TINY)
    tiny["Generation"]["speculative"] = {"draft_k": 3}
    cfg = process_configs(AttrDict.from_nested(tiny),
                          num_devices=jax.device_count())
    mesh = init_dist_env(cfg)
    srv = GenerationServer(cfg, mesh, build_module(cfg))
    assert srv.spec is not None and srv.spec.draft_k == 3

    plain = copy.deepcopy(TINY)
    cfg2 = process_configs(AttrDict.from_nested(plain),
                           num_devices=jax.device_count())
    mesh2 = init_dist_env(cfg2)
    ref_srv = GenerationServer(cfg2, mesh2, build_module(cfg2))

    for p in PROMPTS[:2]:
        assert (srv.generate_ids([p], max_dec_len=6)
                == ref_srv.generate_ids([p], max_dec_len=6))
    assert srv.stats["spec_proposed"] > 0
    assert srv.stats["spec_accepted"] >= 0
    traces = srv.stats["traces"]
    srv.generate_ids([PROMPTS[0]], max_dec_len=6)
    assert srv.stats["traces"] == traces


@pytest.mark.slow
@pytest.mark.fault  # subprocess drill conventions; make test-spec
def test_spec_serve_drill_cli_roundtrip(tmp_path):
    """Through the real CLI: tools/serve.py --scheduler continuous
    --draft-k 3 --kv-dtype int8 serves token-stable greedy output, the
    acceptance counters reach /metrics, and SIGTERM drain still exits
    0 — the speculative engine honors every serving contract."""
    import signal

    from test_paged_drills import (
        _finish,
        _healthz,
        _metrics,
        _post,
        _start_server,
    )

    proc, port = _start_server(
        tmp_path, extra_args=("--draft-k", "3", "--kv-dtype", "int8"),
    )
    try:
        body = {"prompt_ids": [1, 2, 3], "max_tokens": 8, "deadline_s": 45}
        code1, r1 = _post(port, body, timeout=90)
        assert code1 == 200, (code1, r1)
        code2, r2 = _post(port, body, timeout=90)
        assert code2 == 200, (code2, r2)
        assert r1["completion_ids"] == r2["completion_ids"]
        m = _metrics(port)
        assert m.get("pfx_spec_proposed_total", 0) > 0, m
        assert m.get("pfx_spec_accepted_total", -1) >= 0, m
        assert "pfx_spec_accept_rate" in m, m
        assert m.get("pfx_kv_bytes", -1) >= 0, m
        assert m["pfx_kv_blocks_used"] == 0, m  # all rows retired
        h = _healthz(port)
        assert h["state"] == "ok", h
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=60) == 0
    finally:
        log = _finish(proc)
    assert "Traceback" not in log, log[-3000:]
