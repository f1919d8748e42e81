"""A ``layer_pattern`` block (its Nemotron-H spelling, docs/nemotron_h.md) on
the serving path, held to the benchmark's plain reference
(pfx_bench/reference/nemotron_h.py) on the CPU at tiny widths with seeded
weights: the chunked prefill against the sequential recurrence under right
padding; prefill and decode through ``PagedDecodeEngine`` (the recurrent state
a slot, the pages of the attention layers, a REUSED slot, a 2-token prompt)
against the reference's full forward pass; the Pallas kernels (interpret mode)
against ``jnp``; the share test that ties one chip's experts to the whole
layer; what is refused, by name; counters and gauges; the benchmark's new
arithmetic, data and reader.

Everything runs in float32, where system and reference differ by
accumulation order only: the tolerances are a few float32 roundings of
values of order 1 (2e-5), and each says so where it is used.  A recurrent
state or a conv column kept in bfloat16 would miss them by two orders."""

import functools
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlefleetx_tpu.models.gpt import generation as G
from paddlefleetx_tpu.models.gpt import model as gpt
from paddlefleetx_tpu.models.gpt import moe
from paddlefleetx_tpu.models.gpt import ssm as mixer
from paddlefleetx_tpu.models.gpt.config import GPTConfig
from paddlefleetx_tpu.ops import decode_attention as DA
from paddlefleetx_tpu.ops import ssm as ssm_ops
from paddlefleetx_tpu.ops.grouped_matmul import grouped_matmul

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "pfx_bench")  # noqa: E10 — a directory, not a metric
F32_ROUNDINGS = 2e-5  # logits of order 1, float32 both sides, another summation order


def _load(name, *parts):
    spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("nemotron_h_reference", "reference", "nemotron_h.py")

# every kind of layer, a dense one too; 8 experts top-2 of which ids 2..5 are
# held; 4 query heads on 2 KV heads; 4 state-space heads in 2 B/C groups, a
# chunk of 8 so that a prompt spans several and ends inside one
TOY = dict(
    vocab_size=96, hidden_size=32, num_layers=8, num_attention_heads=4, num_kv_heads=2,
    attn_head_dim=8, ffn_hidden_size=48, max_position_embeddings=64, norm="rmsnorm",
    norm_eps=1e-5, position="none", use_bias=False, mlp_act="relu2", tie_embeddings=False,
    hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0, layer_pattern="MEM*EME-",
    ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_groups=2, ssm_conv=4, ssm_chunk=8,
    num_experts=8, moe_gate="sigmoid", moe_top_k=2, moe_ffn_hidden_size=24, moe_experts_held=4,
    moe_expert_offset=2, moe_shared_experts=2, moe_route_scale=2.5,
    rescale_prenorm_residual=True, initializer_range=0.2, dtype="float32", attn_impl="xla",
)
BLOCK = 8


def _with_bias(params, seed=5):
    rng = np.random.default_rng(seed)
    for blk in params["blocks"]:
        if "router_kernel" in blk["mlp"]:  # a bias that moves the choice for some tokens
            n = blk["mlp"]["router_kernel"].shape[1]
            blk["mlp"]["e_score_correction_bias"] = jnp.asarray(rng.normal(size=(n,)) * 0.1,
                                                                jnp.float32)
    return params


@pytest.fixture(scope="module")
def toy():
    cfg = GPTConfig(**TOY)
    return cfg, _with_bias(G.init_serving_params(cfg, jax.random.PRNGKey(0)))


def test_served_tree_pools_and_row_state(toy):
    cfg, params = toy
    assert set(params) == {"embeddings", "blocks", "final_ln", "head"} and len(params["blocks"]) == 8
    kinds = {k: params["blocks"][TOY["layer_pattern"].index(k)] for k in "M*E-"}
    assert all("mlp" in b for b in params["blocks"])  # every layer, empty for a mixer layer
    assert set(kinds["M"]) == {"ln_1", "ssm", "mlp"} and kinds["M"]["mlp"] == {}
    assert set(kinds["M"]["ssm"]) == {"in_kernel", "conv_kernel", "conv_bias", "dt_bias", "A_log",
                                      "D", "norm", "out_kernel"}
    assert kinds["M"]["ssm"]["in_kernel"].shape == (32, 32 + (32 + 2 * 2 * 16) + 4)  # z | xBC | dt
    assert set(kinds["*"]["attn"]) == {"q_kernel", "k_kernel", "v_kernel", "out_kernel"}
    assert kinds["*"]["attn"]["k_kernel"].shape == (32, 2, 8)
    assert set(kinds["E"]["mlp"]) == {"router_kernel", "experts", "shared", "e_score_correction_bias"}
    assert set(kinds["E"]["mlp"]["experts"]) == {"w1", "w2"}  # non-gated: two matrices
    assert kinds["E"]["mlp"]["experts"]["w1"].shape == (4, 32, 24)  # held, not 8
    assert kinds["E"]["mlp"]["shared"]["w1"].shape == (32, 48) and set(kinds["-"]["mlp"]) == {"w1", "w2"}
    a = np.asarray(-jnp.exp(kinds["M"]["ssm"]["A_log"]))
    dt = np.asarray(jax.nn.softplus(kinds["M"]["ssm"]["dt_bias"]))
    assert (-16 <= a).all() and (a <= -1).all() and (1e-4 <= dt).all() and (dt <= 0.1001).all()
    assert cfg.kv_layers == 1 and cfg.ssm_layers == 3 and cfg.cached_token == ((2, 8), (2, 8))
    assert cfg.row_state == (("ssm", (4, 8, 16), "float32"), ("conv", (3, 96), "float32"))
    assert GPTConfig(**dict(TOY, dtype="bfloat16")).row_state[1][2] == "bfloat16"
    pools = G.init_paged_pools(cfg, 5, BLOCK, slots=3)
    assert pools.k.shape == pools.v.shape == (1, 5, 2, BLOCK, 8)  # pages for the * layer only
    assert pools.ssm.shape == (3, 3, 1, 16, 32) and pools.ssm.dtype == jnp.float32
    assert pools.conv.shape == (3, 3, 3 * 96) and pools.fields() == ("k", "v", "ssm", "conv")
    assert G.PagedPools.of(pools.fields(), tuple(x for x in pools if x is not None)) == pools
    with pytest.raises(ValueError, match="batch slots"):
        G.init_paged_pools(cfg, 5, BLOCK)
    with pytest.raises(NotImplementedError, match="served"):
        gpt.forward(params, jnp.ones((1, 8), jnp.int32), cfg)


# -- (a) the chunked prefill against the sequential recurrence ------------------


def _sequential_state(p, u, n, cfg):
    """State and conv columns after token n - 1, by the recurrence in numpy."""
    z, xbc, dt = mixer.in_projection(p, u[0], cfg)
    xbc, dt = np.asarray(xbc, np.float64), np.asarray(dt, np.float64)
    taps, kernel = cfg.ssm_conv, np.asarray(p["conv_kernel"], np.float64)
    padded = np.concatenate([np.zeros((taps - 1, xbc.shape[1])), xbc])
    state = np.zeros((cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state))
    a = -np.exp(np.asarray(p["A_log"], np.float64))
    for t in range(n):
        conv = sum(padded[t + j] * kernel[j] for j in range(taps)) + np.asarray(p["conv_bias"])
        conv = conv / (1 + np.exp(-conv))
        x = conv[:cfg.ssm_inner].reshape(cfg.ssm_heads, cfg.ssm_head_dim)
        b = np.repeat(conv[cfg.ssm_inner:cfg.ssm_inner + 32].reshape(2, 16), 2, axis=0)
        state = (np.exp(dt[t] * a)[:, None, None] * state
                 + (dt[t][:, None] * x)[:, :, None] * b[:, None, :])
    return state, padded[n:n + taps - 1].reshape(-1)


@pytest.mark.parametrize("bucket,n", [(16, 13), (16, 16), (8, 2), (8, 1), (24, 17), (32, 9), (6, 5)])
def test_chunked_prefill_equals_the_sequential_recurrence_under_right_padding(toy, bucket, n):
    """(a) a prompt of n tokens right-padded to its bucket (whole chunks of 8,
    or one short chunk): the mixer's result at the real tokens, the state after
    the LAST REAL token and the last 3 real conv columns (zeros before token 0
    for a prompt shorter than 3) equal the token-by-token recurrence."""
    cfg, params = toy
    p = params["blocks"][0]["ssm"]
    rng = np.random.default_rng(bucket * 100 + n)
    u = jnp.asarray(rng.normal(size=(1, bucket, 32)), jnp.float32)
    junk = u.at[0, n:].set(1e3)  # what the padding holds must not matter
    out, state, columns = mixer.mixer_prefill(p, junk, n, cfg)
    want = ref.mamba_mixer(u[:, :n], jax.tree.map(lambda a: a.astype(jnp.float32), p), TOY)
    assert float(jnp.max(jnp.abs(out[0, :n] - want[0]))) < F32_ROUNDINGS
    s_want, c_want = _sequential_state(p, u, n, cfg)
    assert float(np.max(np.abs(np.asarray(ssm_ops.unpack_state(state, 4, 8)) - s_want))) < F32_ROUNDINGS
    assert float(np.max(np.abs(np.asarray(columns) - c_want))) < 1e-6
    if n < cfg.ssm_conv - 1:
        assert bool((columns[:(cfg.ssm_conv - 1 - n) * 96] == 0).all())


# -- (b) through the server, the engine and the scheduler -----------------------

SERVE = {
    "Global": {"global_batch_size": 8, "seed": 7},
    "Engine": {"mix_precision": {"enable": False}, "save_load": {"save_steps": 0}},
    "Model": dict(TOY, module="GPTModule"),
    "Distributed": {},
    "Optimizer": {"name": "FusedAdamW", "lr": {"name": "Constant", "learning_rate": 1e-3}},
    "Generation": {"max_dec_len": 12, "min_dec_len": 12, "decode_strategy": "greedy_search",
                   "pad_to_multiple": 8, "eos_token_id": 0, "pad_token_id": 0},
}


@pytest.fixture(scope="module")
def server():
    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.core.serving import GenerationServer
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import AttrDict, process_configs

    cfg = process_configs(AttrDict.from_nested(SERVE), num_devices=1)
    srv = GenerationServer(cfg, init_dist_env(cfg, devices=jax.devices()[:1]), build_module(cfg))
    _with_bias(srv.params)
    return srv


def _engine(server, **kw):
    from paddlefleetx_tpu.core.continuous_batching import PagedDecodeEngine

    kw.setdefault("max_batch", 2)
    kw.setdefault("block", BLOCK)
    return PagedDecodeEngine(server, **kw)


def test_prefill_and_decode_through_the_engine_equal_the_full_forward(server):
    """(b) two slots; rows admitted at different steps, a 2-token prompt, and a
    row admitted into the slot a finished row left (its 3 layers' state
    overwritten): after the admission and after every step the row's pending
    logits equal the reference's at that position of prompt + served tokens."""
    eng = _engine(server)
    rng = np.random.default_rng(11)
    seen = {}  # row name -> (prompt, [logits ...])

    def admit(name, n, max_new):
        prompt = rng.integers(1, 96, size=n).tolist()
        slot = eng.admit(prompt, max_new)
        seen[name] = (prompt, [np.asarray(eng._logits[slot])], slot)
        return slot

    def step():
        rows = {s: r for s, r in enumerate(eng.slots) if r is not None and eng.active[s]}
        finished = eng.step()
        for name, (_, logits, slot) in seen.items():
            if slot in rows and name in live:
                logits.append(np.asarray(eng._logits[slot]))
        for s in finished:
            done[s] = list(eng.slots[s].tokens)
            eng.release(s)
        return finished

    live, done, tokens = set(), {}, {}
    a = admit("a", 13, 4); live.add("a")
    step(); step()
    b = admit("b", 2, 9); live.add("b")  # the 2-token prompt, two steps later
    assert a != b
    while a not in done:
        step()
    tokens["a"] = done.pop(a); live.discard("a")
    c = admit("c", 21, 5); live.add("c")
    assert c == a  # the slot that row a left, its state still there
    while live - set(tokens):
        for s in step():
            name = next(k for k, v in seen.items() if v[2] == s and k in live and k not in tokens)
            tokens[name] = done.pop(s)
            live.discard(name)
    for name, (prompt, logits, _) in seen.items():
        out = tokens[name]
        assert len(out) == {"a": 4, "b": 9, "c": 5}[name] and len(logits) == len(out) + 1
        full = jnp.asarray([prompt + out])
        want = np.asarray(ref.logits(server.params, full, TOY))[0, len(prompt) - 1:]
        got = np.stack(logits)[:len(want)]
        assert float(np.max(np.abs(got - want[:len(got)]))) < F32_ROUNDINGS, name
        rows = want[:len(out)].copy()
        rows[:, 0] = -np.inf  # min_dec_len: the end token cannot be chosen
        assert rows.argmax(-1).tolist() == out, name  # the reference's greedy tokens
    assert bool(jnp.isfinite(eng.pools.ssm).all()) and bool(jnp.isfinite(eng.pools.conv).all())


def test_served_tokens_do_not_depend_on_what_the_dead_slots_hold(server):
    """The state update visits the live slots only, so what a dead slot holds
    can neither move a live row nor be moved: the same request served with
    the other slots EMPTY (zeros, never written) and with the other slots
    holding finished rows' stale states gives the same tokens and, step by
    step, the same state to the bit; the stale slots come through every step
    they are skipped in bit for bit; and a row admitted into a slot that was
    skipped for many steps starts from its prefill's state, not the stale one."""
    rng = np.random.default_rng(23)
    p, q = (rng.integers(1, 96, size=n).tolist() for n in (17, 6))
    junk = [rng.integers(1, 96, size=n).tolist() for n in (9, 14, 3)]

    def row_state(eng, slot):
        return np.asarray(eng.pools.ssm[:, slot]), np.asarray(eng.pools.conv[:, slot])

    def serve(eng, wanted, joins=()):
        """Step until the ``wanted`` slots finish; ``joins``: (after step, prompt,
        max_new) admissions on the way.  -> {slot: tokens}, {slot: [state a step]}."""
        tokens, states, joins, n = {}, {s: [row_state(eng, s)] for s in wanted}, list(joins), 0
        while set(wanted) - set(tokens):
            for at, prompt, max_new in [j for j in joins if j[0] == n]:
                wanted.append(eng.admit(prompt, max_new))
                states[wanted[-1]] = [row_state(eng, wanted[-1])]
            live = [s for s in wanted if s not in tokens]
            finished = eng.step()
            n += 1
            for s in live:
                states[s].append(row_state(eng, s))
            for s in finished:
                tokens[s] = list(eng.slots[s].tokens)
                eng.release(s)
        return tokens, states

    alone = _engine(server, max_batch=3)
    slot = alone.admit(p, 12)
    assert slot == 0
    want_p, want_states = serve(alone, [slot])
    assert not np.asarray(alone.pools.ssm[:, 1:]).any() and not np.asarray(alone.pools.conv[:, 1:]).any()
    slot = alone.admit(q, 5)
    want_q, want_q_states = serve(alone, [slot])

    eng = _engine(server, max_batch=3)
    serve(eng, [eng.admit(j, 4 + i) for i, j in enumerate(junk)])  # three rows come and go
    assert all(r is None for r in eng.slots)
    stale = [row_state(eng, s) for s in range(3)]
    assert all(np.abs(st).max() > 1e-3 and np.abs(cv).max() > 1e-3 for st, cv in stale)
    slot = eng.admit(p, 12)
    assert slot == 0
    got, states = serve(eng, [slot], joins=[(7, q, 5)])  # q joins slot 1, skipped in each of these 7 steps
    assert got[0] == want_p[0] and got[1] == want_q[0] and len(got[0]) == 12 and len(got[1]) == 5
    for mine, want in ((states[0], want_states[0]), (states[1], want_q_states[0])):
        assert len(mine) == len(want) > 5  # after the prefill and after every step
        for (st, cv), (wst, wcv) in zip(mine, want):
            assert (st == wst).all() and (cv == wcv).all()
    assert all((a == b).all() for a, b in zip(row_state(eng, 2), stale[2]))  # never live since: as it was
    lg = np.asarray(ref.logits(server.params, jnp.asarray([q + got[1]]), TOY))[0, len(q) - 1:-1].copy()
    lg[:, 0] = -np.inf
    assert lg.argmax(-1).tolist() == got[1]  # the reference's greedy tokens from q's own prefill


def test_the_scheduler_serves_and_counts(server):
    """(f) requests through ContinuousScheduler: every served token is the
    reference's greedy choice; the new counters and gauges are on its page."""
    from paddlefleetx_tpu.core.continuous_batching import ContinuousScheduler

    eng = _engine(server, max_batch=3)
    assert eng.kv_bytes_per_token() == 1 * 2 * (2 * 8) * 4  # the * layer alone: K and V of 2 heads
    assert eng.state_bytes_per_row() == 3 * (4 * 8 * 16 * 4 + 3 * 96 * 4)
    assert eng.cache.allocator.num_blocks == 3 * eng.max_row_blocks + 1  # rows x context, no state in it
    sched = ContinuousScheduler(eng, max_depth=16, name="nemotron-test")
    sched.start()
    try:
        rng = np.random.default_rng(6)
        prompts = [rng.integers(1, 96, size=n).tolist() for n in (20, 2, 33, 17, 7)]
        futures = [sched.submit([p], 12) for p in prompts]
        for p, f in zip(prompts, futures):
            out = f.result(timeout=300)[0]
            lg = np.asarray(ref.logits(server.params, jnp.asarray([p + out]), TOY))[0]
            rows = lg[len(p) - 1:len(p) - 1 + len(out)].copy()
            rows[:, 0] = -np.inf
            assert len(out) == 12 and out == rows.argmax(-1).tolist()
        page = dict((n, v) for n, _, v in sched.collect())
        steps, rows = page["pfx_sched_decode_steps_total"], page["pfx_sched_decode_row_steps_total"]
        assert page["pfx_state_bytes_per_row"] == 3 * (2048 + 1152) and page["pfx_kv_bytes_per_token"] == 128
        assert page["pfx_ssm_row_steps_total"] == 3 * rows and rows == 5 * 12  # live (row, step) x M layers
        assert page["pfx_ssm_slot_steps_total"] == 3 * 3 * steps >= page["pfx_ssm_row_steps_total"]
        assert page["pfx_ssm_prefill_tokens_total"] == 3 * sum(map(len, prompts))
        assert page["pfx_moe_serve_pairs_total"] == (sum(map(len, prompts)) + rows) * 2 * 3
        assert 0 < page["pfx_moe_serve_held_pairs_total"] < page["pfx_moe_serve_pairs_total"]
        # every admission went through the kernel: 3 E layers x 2 relu2 matrices a prefill
        assert page["pfx_moe_serve_grouped_calls_total"] == 6 * len(prompts) == (
            eng.mcfg.sorted_pair_products * int(sched.stats["prefill_admits"]))
    finally:
        assert sched.shutdown(timeout=30)


# -- (c) the kernels in interpret mode ---------------------------------------------


def _recurrence(held, x, dt, a, b, c, d):
    """One step of every slot by the equations: held [slots, heads, P, N],
    b / c [slots, groups, N] -> (the new state, y)."""
    b_h, c_h = (jnp.repeat(v, held.shape[1] // v.shape[1], axis=1) for v in (b, c))
    s = held * jnp.exp(dt * a)[:, :, None, None] + (x * dt[:, :, None])[..., None] * b_h[:, :, None]
    return s, jnp.einsum("bhpn,bhn->bhp", s, c_h, precision="highest") + d[None, :, None] * x


@pytest.mark.parametrize("layers,slots,heads,hd,n,groups,dtype", [
    (3, 4, 8, 64, 16, 2, jnp.float32),   # 4 lane groups of 128, 2 heads each, 4 heads a B/C group
    (2, 3, 4, 8, 16, 1, jnp.float32),    # a toy: one lane group of 32
    (2, 5, 64, 64, 8, 8, jnp.float32),   # the published heads and groups: 32 lane groups, 2 blocks of 16
    (2, 3, 8, 64, 16, 2, jnp.bfloat16),  # a bfloat16 state (the configuration states float32)
])
def test_the_ssm_decode_kernel_equals_jnp(layers, slots, heads, hd, n, groups, dtype):
    """(c) pfx_ssm_decode: the layer's live slots rewritten in place, the other
    layers untouched, a slot that is not active keeping its state; pfx_ssm_write
    overwrites one slot of every layer."""
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    state = jax.random.normal(ks[0], (layers, slots, heads, hd, n), jnp.float32)
    packed = ssm_ops.pack_state(state).astype(dtype)
    assert packed.shape == (layers, slots) + ssm_ops.packed_shape(heads, hd, n)
    assert bool((ssm_ops.unpack_state(ssm_ops.pack_state(state), heads, hd) == state).all())
    x = jax.random.normal(ks[1], (slots, heads, hd))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (slots, heads)))
    active = jnp.ones((slots,), bool).at[1].set(False)
    a = -jnp.exp(jax.random.normal(ks[3], (heads,)))
    b, c = (jax.random.normal(k, (slots, groups, n)) for k in ks[4:6])
    d = jax.random.normal(ks[6], (heads,))
    layer = layers - 1
    held = ssm_ops.unpack_state(packed[layer].astype(jnp.float32), heads, hd)
    want_s, want_y = _recurrence(held, x, dt, a, b, c, d)
    tol = F32_ROUNDINGS if dtype == jnp.float32 else 0.05
    for impl in ("lax", "pallas"):
        y, new = ssm_ops.ssm_decode_update(packed, x, dt, a, b, c, d, active=active, layer=layer,
                                           impl=impl)
        assert float(jnp.max(jnp.abs(y - want_y)[active])) < F32_ROUNDINGS * 8, impl  # sums of up to 64 terms
        got = ssm_ops.unpack_state(new[layer].astype(jnp.float32), heads, hd)
        assert float(jnp.max(jnp.abs(got - want_s)[active])) < tol, impl
        assert bool((new[0] == packed[0]).all()) and bool((got[1] == held[1]).all()), impl
        assert bool((y[1] == 0).all()), impl
    fresh = jax.random.normal(ks[5], (layers,) + packed.shape[2:], jnp.float32)
    for impl in ("lax", "pallas"):
        out = ssm_ops.write_slot_states(packed, fresh, 2, impl=impl)
        assert bool((out[:, 2] == fresh.astype(dtype)).all()) and bool((out[:, 0] == packed[:, 0]).all())


SENTINEL = -3.5  # what a slot the kernel must not visit holds: finite, and no state's value

LIVE_MASKS = {  # over 6 slots
    "none": [0, 0, 0, 0, 0, 0], "first": [1, 0, 0, 0, 0, 0], "last": [0, 0, 0, 0, 0, 1],
    "alternating": [0, 1, 0, 1, 0, 1], "a-third": [0, 0, 1, 0, 1, 0], "all": [1, 1, 1, 1, 1, 1],
}


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("mask", list(LIVE_MASKS))
@pytest.mark.parametrize("heads,hd,n,groups", [
    pytest.param(4, 8, 16, 1, id="toy"),  # one lane group of 32
    pytest.param(64, 64, 128, 8, id="published"),  # one layer's widths: 32 lane groups, 2 blocks of 16
])
def test_the_ssm_decode_kernel_visits_the_live_slots_only(heads, hd, n, groups, mask, layer):
    """pfx_ssm_decode's contract, both spellings under jit: a live slot's state
    and y are the recurrence's (and the all-live case the whole batch's, as
    before the kernel followed the live rows); EVERY dead slot's state and
    every other layer's pages come back bit for bit (they hold a sentinel that
    any arithmetic would move); a dead row's y is exactly 0, never what an
    unvisited output block holds; no slot live at all changes nothing."""
    slots, layers = 6, 3
    active = jnp.asarray(LIVE_MASKS[mask], bool)
    ks = jax.random.split(jax.random.PRNGKey(slots * layer + sum(LIVE_MASKS[mask])), 7)
    visited = jnp.zeros((layers, slots), bool).at[layer].set(active)[:, :, None, None, None]
    state = jnp.where(visited, jax.random.normal(ks[0], (layers, slots, heads, hd, n)), SENTINEL)
    packed = ssm_ops.pack_state(state)
    x = jax.random.normal(ks[1], (slots, heads, hd))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (slots, heads)))
    a = -jnp.exp(jax.random.normal(ks[3], (heads,)))
    b, c = (jax.random.normal(k, (slots, groups, n)) for k in ks[4:6])
    d = jax.random.normal(ks[6], (heads,))
    want_s, want_y = _recurrence(state[layer], x, dt, a, b, c, d)
    got = {}
    for impl in ("lax", "pallas"):
        step = jax.jit(lambda st, act, impl=impl: ssm_ops.ssm_decode_update(
            st, x, dt, a, b, c, d, active=act, layer=layer, live=ssm_ops.live_slots(act), impl=impl))
        y, new = got[impl] = step(packed, active)
        assert new.shape == packed.shape and new.dtype == jnp.float32 and y.shape == x.shape
        s = ssm_ops.unpack_state(new, heads, hd)
        assert bool((jnp.where(visited, SENTINEL, s) == SENTINEL).all()), impl  # bit for bit
        assert bool((y[~active] == 0).all()) and bool(jnp.isfinite(y).all()), impl
        assert float(jnp.max(jnp.abs(jnp.where(visited[layer], s[layer] - want_s, 0)))) < F32_ROUNDINGS, impl
        assert float(jnp.max(jnp.abs(jnp.where(active[:, None, None], y - want_y, 0)))) \
            < F32_ROUNDINGS * 8, impl  # sums of up to 128 terms
    # the two spellings differ by the order of y's sum over the state alone
    assert float(jnp.max(jnp.abs(got["lax"][1] - got["pallas"][1]))) < 1e-6


def test_the_live_list_is_the_mask_compacted():
    live, count = ssm_ops.live_slots(jnp.asarray([0, 1, 1, 0, 1], bool))
    assert live.dtype == count.dtype == jnp.int32 and count.shape == (1,)
    assert live[:3].tolist() == [1, 2, 4] and int(count[0]) == 3
    live, count = ssm_ops.live_slots(jnp.zeros((4,), bool))
    assert int(count[0]) == 0 and bool((live < 4).all()) and bool((live >= 0).all())
    with pytest.raises(ValueError, match="one bool a slot"):
        ssm_ops.ssm_decode_update(
            jnp.zeros((1, 2, 1, 16, 32)), jnp.zeros((2, 4, 8)), jnp.zeros((2, 4)), -jnp.ones((4,)),
            jnp.zeros((2, 2, 16)), jnp.zeros((2, 2, 16)), jnp.ones((4,)), active=jnp.ones((3,), bool),
            layer=0)


def test_the_ssm_kernel_refuses_heads_that_straddle_a_group():
    with pytest.raises(ValueError, match="lax"):
        ssm_ops.ssm_decode_update(
            jnp.zeros((1, 2, 1, 16, 128)), jnp.zeros((2, 4, 32)), jnp.zeros((2, 4)), -jnp.ones((4,)),
            jnp.zeros((2, 2, 16)), jnp.zeros((2, 2, 16)), jnp.ones((4,)), active=jnp.ones((2,), bool),
            layer=0, impl="pallas")


@pytest.mark.parametrize("n,kv,t,bs,width", [
    (8, 2, 1, 16, 6), (32, 2, 1, 128, 5), (8, 2, 3, 16, 6), (6, 3, 2, 8, 9), (4, 4, 1, 16, 6)])
def test_the_paged_decode_kernel_with_shared_kv_heads_equals_dense_attention(n, kv, t, bs, width):
    """(c) pfx_decode_paged over pools of fewer KV heads than query heads
    (the last case: as many, the program the GPT-2 block has): rows at a
    page's first and last slot, a verify chunk of t queries, both spellings."""
    rng = np.random.default_rng(1)
    layers, b, d = 3, 5, 16
    nb = b * width + 1
    k, v = (jnp.asarray(rng.normal(size=(layers, nb, kv, bs, d)), jnp.float32) for _ in "kv")
    q = jnp.asarray(rng.normal(size=(b, t, n, d)), jnp.float32)
    tables = jnp.asarray(1 + rng.permutation(nb - 1)[:b * width].reshape(b, width), jnp.int32)
    positions = jnp.asarray([0, bs - 1, bs, 2 * bs + 3, width * bs - t], jnp.int32)
    assert DA.paged_pages_per_step(bs, width, n // kv) >= DA.paged_pages_per_step(bs, width)
    for layer in (0, 2):
        dense = []
        for i in range(b):
            kk, vv = (jnp.repeat(jnp.concatenate([pool[layer, tb] for tb in tables[i]], axis=1),
                                 n // kv, axis=0) for pool in (k, v))  # [n, width * bs, d]
            rows = []
            for qi in range(t):
                upto = int(positions[i]) + qi + 1
                s = jnp.einsum("nd,nkd->nk", q[i, qi], kk[:, :upto]) / np.sqrt(d)
                rows.append(jnp.einsum("nk,nkd->nd", jax.nn.softmax(s, -1), vv[:, :upto]))
            dense.append(jnp.stack(rows))
        for impl in ("lax", "pallas"):
            got = DA.paged_decode_attention(q, k, v, tables, positions, layer=layer, impl=impl)
            assert float(jnp.max(jnp.abs(got - jnp.stack(dense)))) < F32_ROUNDINGS, impl
    if kv != n:
        with pytest.raises(ValueError, match="int8"):
            DA.paged_decode_attention(q, k.astype(jnp.int8), v.astype(jnp.int8), tables, positions,
                                      layer=0, impl="pallas", k_scale=jnp.ones(k.shape[:-1]),
                                      v_scale=jnp.ones(k.shape[:-1]))


# -- (d) the share test ---------------------------------------------------------------


@pytest.mark.parametrize("n,every,kernel", [(40, True, False), (40, False, False), (150, False, False),
                                            (40, False, True), (150, False, True)])
def test_the_eight_shares_add_up_to_the_uncut_layer(n, every, kernel):
    """(d) 16 two-matrix experts over 8 shares of 2: the shares' routed parts
    plus the shared expert ONCE = the reference's layer with all 16 held, on
    the decode step's path (every held expert on every token) and the sorted
    one, through XLA's grouped product (training) and through the serving
    prefill's kernel, which gives what ``jax.lax.ragged_dot`` gives."""
    product = {"grouped_product": functools.partial(grouped_matmul, impl="pallas")} if kernel else {}
    sizes = dict(TOY, num_experts=16, moe_top_k=3, moe_experts_held=16, moe_expert_offset=0)
    whole = GPTConfig(**sizes)
    mlp = G.init_serving_params(whole, jax.random.PRNGKey(4))["blocks"][1]["mlp"]
    rng = np.random.default_rng(4)
    bias = jnp.asarray(rng.normal(size=(16,)) * 0.1, jnp.float32)
    mlp["e_score_correction_bias"] = bias
    m = jnp.asarray(rng.normal(size=(n, 32)), jnp.float32)
    want = ref.expert_layer(m, mlp, sizes)
    total = moe.feed_forward(m, mlp["shared"])
    for share in range(8):
        cfg = GPTConfig(**dict(sizes, moe_experts_held=2, moe_expert_offset=2 * share))
        part = dict(mlp, experts=jax.tree.map(lambda a: a[2 * share:2 * share + 2], mlp["experts"]))
        out, stats = moe.routed_experts(part, m, bias, cfg, every_held_expert=every, **product)
        assert int(stats["load"].sum()) == n * 3
        if kernel:
            ragged, _ = moe.routed_experts(part, m, bias, cfg)
            assert float(jnp.max(jnp.abs(out - ragged))) < F32_ROUNDINGS
        total = total + out
    assert float(jnp.max(jnp.abs(total - want))) < F32_ROUNDINGS
    idx, w = moe.sigmoid_route(m, mlp["router_kernel"], bias, whole)
    ridx, rw = ref.route(m, mlp["router_kernel"], bias, sizes)
    assert bool((jnp.sort(idx, -1) == jnp.sort(ridx, -1)).all())
    assert float(jnp.max(jnp.abs(w.sum(-1) - 2.5))) < 1e-5  # norm_topk_prob x scale


def test_a_prefill_sorts_its_pairs_and_a_decode_step_runs_every_held_expert(toy, monkeypatch):
    """What decides it is the one token a row of a decode step, at every size."""
    cfg, params = toy
    seen = []
    real = moe.routed_experts

    def spy(p, m, bias, cfg, valid=None, every_held_expert=False, grouped_product=None,
            load_ladder=False, gather_combine=False):
        # both are handed the forward-only kernel; only a prefill's sorted path runs it,
        # over one buffer (the ladder of sizes is the training step's), and brings the
        # pairs' results back by a gather (PR 42: no scatter to transpose in a forward)
        assert grouped_product is grouped_matmul and not load_ladder and gather_combine
        seen.append(every_held_expert)
        return real(p, m, bias, cfg, valid, every_held_expert, grouped_product,
                    gather_combine=gather_combine)

    monkeypatch.setattr(moe, "routed_experts", spy)
    pools = G.init_paged_pools(cfg, 4, BLOCK, slots=2)
    prompt = jnp.asarray(np.random.default_rng(2).integers(1, 96, size=(1, 16)))
    G.paged_prefill(params, prompt, jnp.int32(11), pools, jnp.asarray([1, 2]), cfg, slot=jnp.int32(1))
    assert seen == [False] * 3  # the three E layers of a 16-token prefill
    del seen[:]
    G._pattern_paged_forward_step(params, jnp.ones((2, 1), jnp.int32), pools, jnp.asarray([[1, 2], [3, 0]]),
                                  jnp.asarray([11, 0]), jnp.asarray([True, False]), cfg, None)
    assert seen == [True] * 3


# -- the state's part of the benchmark's check (runners/serve_deep_child.py) --------


@pytest.fixture(scope="module")
def deep_child():
    return _load("serve_deep_child", "runners", "serve_deep_child.py")


@pytest.mark.parametrize("ahead", [False, True])
def test_the_engine_s_state_is_the_reference_s_sequential_state(server, deep_child, ahead):
    """A row through the engine (prefill, 12 decode steps; synchronous, and as
    a scheduler that dispatches ahead leaves it: a step in flight): the first
    state-space layer's state of its slot is the reference's after prompt +
    every served token (a step feeds what it samples), to float32 roundings
    in every head."""
    eng = _engine(server)
    prompt = np.random.default_rng(3).integers(1, 96, size=19).tolist()
    if ahead:
        eng.dispatch_ahead = True
        earlier = eng.admit(prompt[:5], 3)
        while earlier not in eng.step():
            pass
        eng.release(earlier)
        assert eng.has_inflight
    tokens, got = deep_child.engine_state(server, eng, prompt)
    assert tokens[:19] == prompt and len(tokens) == 19 + 12 and got.shape == (4, 8, 16)
    assert all(r is None for r in eng.slots)  # the probe leaves the engine as it found it
    verdict = deep_child.state_verdict(ref, server.params, TOY, tokens, got, F32_ROUNDINGS)
    assert verdict["ok"] and verdict["state_error"] <= verdict["state_error_worst_head"] < F32_ROUNDINGS
    for other in (tokens + [5], tokens[:-1]):  # a token more or less read: another state
        assert not deep_child.state_verdict(ref, server.params, TOY, other, got, F32_ROUNDINGS)["ok"]


def test_a_state_kept_in_bfloat16_fails_the_state_s_check(server, deep_child, monkeypatch):
    """The control the benchmark's check exists for, at toy widths: the same
    engine with its rows' state in bfloat16 (a patch: no option spells it)
    misses the float32 reference's state by three orders more than the
    float32 state does, in a program that otherwise computes in float32."""
    floats = type(server.module.config).row_state.fget

    def halved(self):
        (name, shape, _), conv = floats(self)
        return ((name, shape, "bfloat16"), conv)

    monkeypatch.setattr(type(server.module.config), "row_state", property(halved))
    eng = _engine(server)
    assert eng.pools.ssm.dtype == jnp.bfloat16
    prompt = np.random.default_rng(3).integers(1, 96, size=19).tolist()
    tokens, got = deep_child.engine_state(server, eng, prompt)
    verdict = deep_child.state_verdict(ref, server.params, TOY, tokens, got, 100 * F32_ROUNDINGS)
    assert not verdict["ok"] and verdict["state_error_worst_head"] > 1e-3


def test_the_row_s_state_is_float32_whatever_the_model_computes_in():
    assert GPTConfig(**dict(TOY, dtype="bfloat16")).row_state[0][2] == "float32"
    assert GPTConfig(**dict(TOY, dtype="bfloat16")).row_state[1][2] == "bfloat16"  # the conv columns
    with pytest.raises(TypeError):
        GPTConfig(**dict(TOY, ssm_state_dtype="bfloat16"))  # no option spells another


def test_serve_arch_child_has_the_names_serve_deep_child_reads(deep_child):
    """serve_deep_child.py runs serve_arch_child.py's file and reads or sets
    these of its globals; the child stops with a message if one is missing,
    and this test says so before any chip time is spent."""
    import ast

    with open(deep_child.PARENT) as f:
        tree = ast.parse(f.read())
    names, todo = set(), list(tree.body)
    while todo:  # the module's own statements, a ``with``'s among them; no function's
        node = todo.pop()
        if isinstance(node, ast.FunctionDef):
            names.add(node.name)
        elif isinstance(node, ast.Assign):
            names |= {leaf.id for t in node.targets for leaf in ast.walk(t)
                      if isinstance(leaf, ast.Name)}
        elif isinstance(node, ast.With):
            todo += node.body
    assert set(deep_child.NAMES) <= names
    calls = [n for n in ast.walk(tree) if isinstance(n, ast.Call)
             and ast.unparse(n.func) == "serve.main"]
    assert len(calls) == 1  # the one call serve_deep_child wraps


# -- (e) what is accepted and what is refused ---------------------------------------


def test_the_pattern_is_servable_and_its_words_move_together():
    G.check_servable(GPTConfig(**TOY))
    for change, named in ((dict(layer_pattern="MEM*EME"), "each of the 8 layers"),
                          (dict(layer_pattern="MEM*EMEX"), "one of M"),
                          (dict(position="rope"), "position: none"),
                          (dict(mlp_act="swiglu"), "mlp_act: relu2"),
                          (dict(ssm_heads=0), "ssm_heads"), (dict(ssm_groups=3), "ssm_groups"),
                          (dict(qk_norm=True), "qk_norm"), (dict(kv_lora_rank=8), "kv_lora_rank"),
                          (dict(sliding_window=16), "sliding_window"),
                          (dict(moe_gate="gshard"), "moe_gate: sigmoid")):
        with pytest.raises(ValueError, match=named):
            GPTConfig(**dict(TOY, **change))
    with pytest.raises(ValueError, match="layer_pattern block"):
        GPTConfig(vocab_size=96, hidden_size=32, num_layers=2, num_attention_heads=4, norm="rmsnorm",
                  position="none", use_bias=False, mlp_act="swiglu", tie_embeddings=False,
                  hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)


@pytest.mark.parametrize("named,build", [
    pytest.param("prefill-chunk", lambda s: _engine(s, prefill_chunk=16)),
    pytest.param("prefix-cache-blocks", lambda s: _engine(s, prefix_cache_blocks=4)),
    pytest.param("int8", lambda s: _engine(s, kv_dtype="int8")),
    pytest.param("draft-k", lambda s: _engine(
        s, spec=__import__("paddlefleetx_tpu.ops.speculative", fromlist=["x"]).SpecConfig(draft_k=2))),
    pytest.param("preempt-resume", lambda s: _engine(s).preempt_row(0)),
    pytest.param(r"KV handoff \(--role prefill\)", lambda s: _engine(s).prefill_export([1, 2, 3], 4)),
    pytest.param(r"KV handoff \(--role decode\)", lambda s: _engine(s).adopt({}, {})),
    pytest.param("coalesce", lambda s: s.generate_ids([[1, 2, 3]], max_dec_len=4)),
    pytest.param("tensor parallelism", lambda s: G.paged_forward_step(
        s.params, jnp.ones((1,), jnp.int32), G.init_paged_pools(s.module.config, 3, BLOCK, slots=1),
        jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32), jnp.ones((1,), bool),
        s.module.config, object())),
    pytest.param("one token a row", lambda s: G.paged_forward_step(
        s.params, jnp.ones((1, 3), jnp.int32), G.init_paged_pools(s.module.config, 3, BLOCK, slots=1),
        jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32), jnp.ones((1,), bool),
        s.module.config)),
    pytest.param("pass slot", lambda s: G.paged_prefill(
        s.params, jnp.ones((1, 8), jnp.int32), jnp.int32(5),
        G.init_paged_pools(s.module.config, 3, BLOCK, slots=1), jnp.asarray([1]), s.module.config)),
])
def test_what_a_block_with_row_state_cannot_take_yet_is_refused_by_name(server, named, build):
    """(e) each raises a ValueError that names the option; the engine's say why:
    a recurrent state a slot is in no page, prefix block or handoff payload."""
    with pytest.raises(ValueError, match=named) as err:
        build(server)
    if "engine" in str(err.traceback[-1].path) or "continuous_batching" in str(err.traceback[-1].path):
        assert "row state" in str(err.value)


def test_the_model_gives_the_page_size(server):
    assert _engine(server, block=0).block != 128  # 4 query heads on 2 KV heads: the library's page
    wide = GPTConfig(**dict(TOY, num_attention_heads=16, attn_head_dim=2))
    assert wide.kv_block_default == 128 and GPTConfig(**TOY).kv_block_default == 0


@pytest.mark.parametrize("kw", [pytest.param(dict(TOY, dtype="bfloat16"), id="pattern-bf16"),
                                pytest.param(TOY, id="pattern-f32")])
def test_leaf_by_leaf_start_up_gives_init_then_serving_params_to_the_bit(kw):
    cfg = GPTConfig(**kw)
    made = G.init_serving_params(cfg, jax.random.PRNGKey(3))
    want = G.serving_params(gpt.init(cfg, jax.random.PRNGKey(3)), cfg)
    assert jax.tree.structure(made) == jax.tree.structure(want)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(made)[0], jax.tree.leaves(want)):
        assert a.dtype == b.dtype and bool((a == b).all()), jax.tree_util.keystr(path)
    dtype = jnp.dtype(cfg.dtype)
    m, e, a = (made["blocks"][TOY["layer_pattern"].index(k)] for k in "ME*")
    assert m["ssm"]["in_kernel"].dtype == m["ssm"]["conv_kernel"].dtype == dtype
    assert a["attn"]["q_kernel"].dtype == e["mlp"]["experts"]["w2"].dtype == dtype
    for leaf in (m["ssm"]["A_log"], m["ssm"]["dt_bias"], m["ssm"]["D"], m["ssm"]["conv_bias"],
                 m["ssm"]["norm"], m["ln_1"]["scale"], e["mlp"]["router_kernel"],
                 e["mlp"]["e_score_correction_bias"]):
        assert leaf.dtype == jnp.float32
    # the out-projection of every sub-block is drawn at range / sqrt(layers)
    for out in (m["ssm"]["out_kernel"], a["attn"]["out_kernel"], e["mlp"]["experts"]["w2"],
                e["mlp"]["shared"]["w2"]):
        assert abs(float(jnp.std(out.astype(jnp.float32))) / (0.2 / np.sqrt(8)) - 1) < 0.1
    assert abs(float(jnp.std(m["ssm"]["in_kernel"].astype(jnp.float32))) / 0.2 - 1) < 0.1


# -- (g) the benchmark's new data, arithmetic and readers ---------------------------


def test_the_configuration_file_states_the_cut_and_the_arithmetic_counts_the_tree():
    with open(os.path.join(BENCH, "configs", "nemotron-3-nano.json")) as f:
        conf = json.load(f)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # the top level holds the published numbers, uncut
        with open(catalog) as f:
            rows = [json.loads(line) for line in f]
        published = next(r for r in rows if r["name"].startswith("NVIDIA-Nemotron-3-Nano-30B"))
        for key, want in published["config"].items():
            assert conf[key] == want, key
    assert (conf["hidden_size"], conf["num_hidden_layers"], conf["mamba_num_heads"],
            conf["ssm_state_size"], conf["num_experts_per_tok"]) == (2688, 52, 64, 128, 6)
    model = conf["model"]
    for key, want in dict(hidden_size=2688, num_attention_heads=32, num_kv_heads=2, attn_head_dim=128,
                          ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssm_groups=8, ssm_conv=4,
                          ssm_chunk=128, num_experts=128, moe_top_k=6, moe_ffn_hidden_size=1856,
                          moe_shared_experts=2, moe_route_scale=2.5, norm_eps=1e-5).items():
        assert model[key] == want, key  # every width as published
    assert model["layer_pattern"] == conf["hybrid_override_pattern"] and model["num_layers"] == 52
    assert model["moe_experts_held"] * 8 == conf["n_routed_experts"] == 128
    assert model["vocab_size"] * 8 == conf["vocab_size"] == 131072
    assert conf["reduced"] == ["n_routed_experts", "vocabulary", "max_position_embeddings"]
    assert set(conf["reduced"]) == set(conf["reduced_keys"])
    GPTConfig(**model)  # the program takes the file's sizes as they are
    math_ = _load("nemotron_h_math", "math", "nemotron_h.py")
    assert abs(math_.param_count(model) / 1e9 - 5.2575) < 0.0005
    assert abs(math_.weight_bytes(model) / 1e9 - 10.531) < 0.001
    assert math_.state_bytes_per_row(model) == 23 * (64 * 64 * 128 * 4 + 3 * 6144 * 2) == 49_082_368
    assert math_.cached_token_bytes(model) == 6 * 2 * 2 * 128 * 2 == 6144
    toy = conf["rehearse_model"]
    tree = G.init_serving_params(GPTConfig(**toy, dtype="float32"), jax.random.PRNGKey(0))
    matrices = sum(a.size for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]
                   if a.ndim >= 2 and "conv_kernel" not in jax.tree_util.keystr(path))
    assert matrices == math_.param_count(toy)  # the arithmetic counts the program's tree
    # (g) a (row, step) and Mamba layer by hand: the state in and out, 4 vectors over the
    # 4,096 (head, head_dim) pairs, B and C of 8 groups of 128, all float32; 5 FLOPs an element
    work = math_.ssm_decode_work(model, 12345.0, 1.0)
    assert work["bytes"] == 23 * (2 * 64 * 64 * 128 * 4 + (4 * 4096 + 2 * 8 * 128) * 4)
    assert work["flops"] == 23 * 5 * 64 * 64 * 128
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    assert abs(math_.roofline_seconds(work, peaks) - work["bytes"] / 819e9) < 1e-12  # the HBM bounds it
    limits = conf["reference_limits"]
    assert 0 < limits["past_band_share_max"] < 1 and 0 < limits["argmax_agree_min"] < 1
    assert 0 < limits["state_error_worst_head_max"] < 0.1


def test_the_new_readers_read_nothing_from_a_parent_and_refuse_over_100():
    sys.path.insert(0, BENCH)
    try:
        roofline = _load("kernel_roofline", "readers", "kernel_roofline.py")
        share = _load("kernel_share", "readers", "kernel_share.py")
        import common
    finally:
        sys.path.remove(BENCH)
    with open(os.path.join(BENCH, "configs", "nemotron-3-nano.json")) as f:
        conf = json.load(f)
    kernel = "pfx_" + "ssm_decode"  # a kernel's name, not a metric's (lint E10)
    ctx = {"math": conf["math"], "model": conf["model"],
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           "profile_counters": [{"kv_tokens": 0, "row_steps": 0}, {"kv_tokens": 9_000_000, "row_steps": 5_000}],
           "kernel_self_s": {kernel: 1.0}, "trace": {"busy_s": 3.5}}
    args = dict(kernel=kernel, work="ssm_decode_work")
    assert abs(roofline.read(ctx, **args) - 100 * 5000 * 23 * 4_268_032 / 819e9) < 1e-6  # 59.9%
    assert abs(share.read(ctx, kernel=kernel) - 100 / 3.5) < 1e-9
    for lacking in ({"kernel_self_s": {}}, {"kernel_self_s": None}, {"trace": None}):  # a parent's run
        assert share.read(dict(ctx, **lacking), kernel=kernel) is None
    assert roofline.read(dict(ctx, kernel_self_s={}), **args) is None
    with pytest.raises(common.Fail, match="counted too high"):
        roofline.read(dict(ctx, kernel_self_s={kernel: 0.5}), **args)


@pytest.mark.parametrize("dtype", [jnp.float8_e4m3fn, jnp.float8_e5m2, jnp.bfloat16])
def test_the_reference_s_rounding_is_the_cast(dtype):
    """The ``fp8_reference`` control rounds in float32 arithmetic (the chip's
    compiler left most of a cast through float8 out): to the bit what the
    cast gives here, subnormals included (a matrix drawn at 0.02 / sqrt(52)
    lies in float8_e4m3's)."""
    rng = np.random.default_rng(0)
    for scale in (0.02, 0.02 / 52 ** 0.5, 1.0, 1e-4):
        a = jnp.asarray(rng.normal(size=(4000,)) * scale, jnp.bfloat16)
        got = jax.jit(lambda v: ref.round_through(v, dtype))(a)
        assert bool((got == a.astype(dtype).astype(jnp.float32)).all()), scale
