"""A ``layer_pattern`` block whose ``P`` layers run a Mamba-2 mixer AND rotated
grouped-query attention side by side on one normed input (its Falcon-H1
spelling, docs/falcon_h1.md) on the serving path, held to the benchmark's plain
reference (pfx_bench/reference/falcon_h1.py) on the CPU at the configuration
file's toy preset (``rehearse_model``) with seeded weights: prefill and decode
through ``PagedDecodeEngine`` (a recurrent state a slot AND pages in every ``P``
layer, a reused slot, dead slots) against the reference's full forward pass,
logits not tokens; the chunked prefill against the sequential recurrence under
right padding; the FOLDED tree against the reference's explicit constants, one
case a constant; ``P`` against ``M`` and ``*`` computed apart; the kernels in
interpret mode at state 256 on heads of 128 and at 5 query heads a KV head;
what is refused, by name; counters and gauges; the arithmetic.

Everything runs in float32, where system and reference differ by accumulation
order only: the tolerance is a few float32 roundings of values of order 1
(2e-5), and each use says what would miss it.  The reference reads the
UNFOLDED tree (``model.init``'s) and multiplies every constant where the
published forward does; the program reads the tree ``fold_mup`` made of it."""

import importlib.util
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlefleetx_tpu.models.gpt import generation as G
from paddlefleetx_tpu.models.gpt import model as gpt
from paddlefleetx_tpu.models.gpt import ssm as mixer
from paddlefleetx_tpu.models.gpt.config import MUP_NAMES, GPTConfig
from paddlefleetx_tpu.models.gpt.convert import fold_mup, mup_scale
from paddlefleetx_tpu.ops import decode_attention as DA
from paddlefleetx_tpu.ops import ssm as ssm_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "pfx_bench")  # noqa: E10 — a directory, not a metric
F32_ROUNDINGS = 2e-5  # logits of order 1, float32 both sides, another summation order


def _load(name, *parts):
    spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("falcon_h1_reference", "reference", "falcon_h1.py")
with open(os.path.join(BENCH, "configs", "falcon-h1-34b.json")) as _f:
    CONF = json.load(_f)
# the file's toy preset: "P-P-", 10 query heads on 2 KV heads (5 a KV head, as
# published), 8 state-space heads in 2 B/C groups with a state of twice the
# head's size, a chunk of 16, every constant away from 1; drawn at 0.2 (not
# 0.02) so that logits are of order 1 and the tolerance above means what it says
TOY = dict(CONF["rehearse_model"], initializer_range=0.2, dtype="float32")
BLOCK = 8
KEY = jax.random.PRNGKey(0)


def _trees(cfg, key=KEY):
    """(the checkpoint's tree, the served one): ``init`` and its fold."""
    raw = gpt.init(cfg, key)
    return raw, G.serving_params(fold_mup(raw, cfg), cfg)


@pytest.fixture(scope="module")
def toy():
    cfg = GPTConfig(**TOY)
    return (cfg,) + _trees(cfg)


def test_served_tree_pools_and_row_state(toy):
    """A ``P`` layer holds an ``ssm`` AND an ``attn`` group behind ONE norm; it
    counts in ``kv_layers`` and in ``ssm_layers`` (2 and 2 of 4 sub-blocks),
    so the pools hold pages AND a state a slot for the same layers."""
    cfg, _, params = toy
    assert set(params) == {"embeddings", "blocks", "final_ln", "head"} and len(params["blocks"]) == 4
    par, dense = params["blocks"][0], params["blocks"][1]
    assert set(par) == {"ln_1", "ssm", "attn", "mlp"} and par["mlp"] == {}
    assert set(par["ssm"]) == {"in_kernel", "conv_kernel", "conv_bias", "dt_bias", "A_log", "D",
                               "norm", "out_kernel"}
    assert par["ssm"]["in_kernel"].shape == (64, 128 + (128 + 2 * 2 * 32) + 8)  # z | x B C | dt
    assert par["attn"]["q_kernel"].shape == (64, 10, 16) and par["attn"]["k_kernel"].shape == (64, 2, 16)
    assert set(dense) == {"ln_1", "mlp"} and set(dense["mlp"]) == {"w1", "w3", "w2"}  # SwiGLU
    assert cfg.kv_layers == cfg.ssm_layers == 2 and cfg.cached_token == ((2, 16), (2, 16))
    assert cfg.row_state == (("ssm", (8, 16, 32), "float32"), ("conv", (3, 256), "float32"))
    assert cfg.layer_kind(0) == (0, True) and cfg.layer_kind(2) == (0, True)
    with pytest.raises(ValueError, match="no attention layer"):
        cfg.layer_kind(1)
    pools = G.init_paged_pools(cfg, 5, BLOCK, slots=3)
    assert pools.k.shape == pools.v.shape == (2, 5, 2, BLOCK, 16)
    assert pools.ssm.shape == (2, 3, 1, 32, 128) and pools.ssm.dtype == jnp.float32
    assert pools.conv.shape == (2, 3, 3 * 256) and pools.fields() == ("k", "v", "ssm", "conv")
    G.check_servable(cfg)


# -- the chunked prefill against the sequential recurrence ----------------------


@pytest.mark.parametrize("bucket,n", [(16, 13), (32, 32), (16, 2), (16, 1), (48, 33), (24, 20)])
def test_chunked_prefill_equals_the_sequential_recurrence_under_right_padding(toy, bucket, n):
    """A prompt of n tokens right-padded to its bucket (whole chunks of 16, or
    a short one): the FOLDED mixer's chunked result at the real tokens and its
    state after the LAST REAL token equal the reference's token-by-token
    recurrence on the unfolded weights with the constants spelled out."""
    cfg, raw, params = toy
    c = ref.constants(TOY)
    rng = np.random.default_rng(bucket * 100 + n)
    u = jnp.asarray(rng.normal(size=(1, bucket, 64)), jnp.float32)
    junk = u.at[0, n:].set(1e3)  # what the padding holds must not matter
    out, state, _ = mixer.mixer_prefill(params["blocks"][0]["ssm"], junk, n, cfg)
    want, last = ref.mamba_mixer(c["ssm_in_multiplier"] * u[:, :n], raw["blocks"][0]["ssm"], TOY, c)
    assert float(jnp.max(jnp.abs(out[0, :n] - c["ssm_out_multiplier"] * want[0]))) < F32_ROUNDINGS
    got = ssm_ops.unpack_state(state, 8, 16)
    assert float(jnp.max(jnp.abs(got - last[0]))) < F32_ROUNDINGS


# -- through the server, the engine and the scheduler ---------------------------

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
    """-> (the server, the checkpoint's tree its weights were folded from)."""
    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.core.serving import GenerationServer
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import AttrDict, process_configs

    cfg = process_configs(AttrDict.from_nested(SERVE), num_devices=1)
    srv = GenerationServer(cfg, init_dist_env(cfg, devices=jax.devices()[:1]), build_module(cfg))
    raw, srv.params = _trees(srv.module.config)
    return srv, raw


def _engine(server, **kw):
    from paddlefleetx_tpu.core.continuous_batching import PagedDecodeEngine

    kw.setdefault("max_batch", 2)
    kw.setdefault("block", BLOCK)
    return PagedDecodeEngine(server, **kw)


def _reference_rows(raw, prompt, out):
    """The reference's logits from the prompt's last position on, over the
    unfolded tree with explicit constants."""
    full = jnp.asarray([prompt + out])
    return np.asarray(ref.logits(raw, full, TOY, folded=False))[0, len(prompt) - 1:]


def test_prefill_and_decode_through_the_engine_equal_the_full_forward(server):
    """Two slots; rows admitted at different steps, a 2-token prompt, and a row
    admitted into the slot a finished row left (its state AND its pages taken
    over): after the admission and after every step the row's pending LOGITS
    equal the reference's at that position of prompt + served tokens.  A
    missing constant, keys left unrotated or cached unrotated, a state kept
    from the slot's last row or a state in bfloat16 each miss this tolerance
    by orders (the cases below read some of them)."""
    srv, raw = server
    eng = _engine(srv)
    rng = np.random.default_rng(11)
    seen, live, done, tokens = {}, set(), {}, {}

    def admit(name, n, max_new):
        prompt = rng.integers(1, 512, size=n).tolist()
        slot = eng.admit(prompt, max_new)
        seen[name] = (prompt, [np.asarray(eng._logits[slot])], slot)
        live.add(name)
        return slot

    def step():
        rows = {s for s, r in enumerate(eng.slots) if r is not None and eng.active[s]}
        finished = eng.step()
        for name, (_, logits, slot) in seen.items():
            if slot in rows and name in live:
                logits.append(np.asarray(eng._logits[slot]))
        for s in finished:
            name = next(k for k, v in seen.items() if v[2] == s and k in live)
            tokens[name] = list(eng.slots[s].tokens)
            live.discard(name)
            eng.release(s)

    a = admit("a", 13, 4)
    step(), step()
    b = admit("b", 2, 9)  # the 2-token prompt, two steps later
    assert a != b
    while "a" in live:
        step()
    assert admit("c", 21, 5) == a  # the slot row a left, its state and pages still there
    while live:
        step()
    for name, (prompt, logits, _) in seen.items():
        out = tokens[name]
        assert len(out) == {"a": 4, "b": 9, "c": 5}[name] and len(logits) == len(out) + 1
        want = _reference_rows(raw, prompt, out)
        got = np.stack(logits)[:len(want)]
        assert float(np.max(np.abs(got - want[:len(got)]))) < F32_ROUNDINGS, name
        rows = want[:len(out)].copy()
        rows[:, 0] = -np.inf  # min_dec_len: the end token cannot be chosen
        assert rows.argmax(-1).tolist() == out, name
    assert bool(jnp.isfinite(eng.pools.ssm).all()) and bool(jnp.isfinite(eng.pools.k).all())


def test_served_logits_do_not_depend_on_what_the_dead_slots_hold(server):
    """The same request served with the other slots EMPTY and with the other
    slots holding finished rows' stale states and pages gives the same logits
    to the bit, and a stale slot comes through the steps it is skipped in bit
    for bit, state and conv columns both."""
    srv, _ = server
    rng = np.random.default_rng(23)
    p = rng.integers(1, 512, size=17).tolist()
    junk = [rng.integers(1, 512, size=n).tolist() for n in (9, 14, 3)]

    def serve(eng, slot):
        rows = [np.asarray(eng._logits[slot])]
        while slot not in eng.step():
            rows.append(np.asarray(eng._logits[slot]))
        eng.release(slot)
        return np.stack(rows)

    alone = _engine(srv, max_batch=3)
    want = serve(alone, alone.admit(p, 12))
    eng = _engine(srv, max_batch=3)
    for i, j in enumerate(junk):
        eng.admit(j, 4 + i)
    while any(r is not None for r in eng.slots):
        for s in eng.step():
            eng.release(s)
    stale = (np.asarray(eng.pools.ssm[:, 2]), np.asarray(eng.pools.conv[:, 2]))
    assert np.abs(stale[0]).max() > 1e-3 and np.abs(stale[1]).max() > 1e-3
    slot = eng.admit(p, 12)
    assert slot == 0 and (serve(eng, slot) == want).all()
    assert (np.asarray(eng.pools.ssm[:, 2]) == stale[0]).all()
    assert (np.asarray(eng.pools.conv[:, 2]) == stale[1]).all()


def test_the_scheduler_serves_and_counts_a_parallel_layer_in_both_books(server):
    """Requests through ContinuousScheduler: every served token is the
    reference's greedy choice; the state's counters and gauges count the 2
    ``P`` layers, and so do the pages' (of 4 sub-blocks)."""
    from paddlefleetx_tpu.core.continuous_batching import ContinuousScheduler

    srv, raw = server
    eng = _engine(srv, max_batch=3)
    assert eng.kv_bytes_per_token() == 2 * 2 * (2 * 16) * 4  # 2 P layers: K and V of 2 heads x 16
    assert eng.state_bytes_per_row() == 2 * (8 * 16 * 32 * 4 + 3 * 256 * 4)
    assert eng.cache.allocator.num_blocks == 3 * eng.max_row_blocks + 1  # rows x context, no state in it
    sched = ContinuousScheduler(eng, max_depth=16, name="falcon-test")
    sched.start()
    try:
        rng = np.random.default_rng(6)
        prompts = [rng.integers(1, 512, size=n).tolist() for n in (20, 2, 33, 7)]
        futures = [sched.submit([p], 12) for p in prompts]
        for p, f in zip(prompts, futures):
            out = f.result(timeout=300)[0]
            rows = _reference_rows(raw, p, out)[:len(out)].copy()
            rows[:, 0] = -np.inf
            assert len(out) == 12 and out == rows.argmax(-1).tolist()
        page = dict((n, v) for n, _, v in sched.collect())
        steps, rows = page["pfx_sched_decode_steps_total"], page["pfx_sched_decode_row_steps_total"]
        assert page["pfx_state_bytes_per_row"] == 2 * (16384 + 3072) and page["pfx_kv_bytes_per_token"] == 512
        assert page["pfx_ssm_row_steps_total"] == 2 * rows and rows == 4 * 12
        assert page["pfx_ssm_slot_steps_total"] == 2 * 3 * steps >= page["pfx_ssm_row_steps_total"]
        assert page["pfx_ssm_prefill_tokens_total"] == 2 * sum(map(len, prompts))
        assert page["pfx_sched_decode_kv_tokens_total"] > 0
        assert "pfx_moe_serve_pairs_total" not in page  # no expert layer
    finally:
        assert sched.shutdown(timeout=30)


# -- the fold: one case a constant ---------------------------------------------------

CONSTANTS = [(name, i) for name, width in MUP_NAMES.items() for i in range(max(1, width))]


def _only(name, index, value=0.37):
    """``mup_multipliers`` with ONE constant away from 1."""
    width = MUP_NAMES[name]
    return {name: [value if i == index else 1.0 for i in range(width)] if width else value}


def _program_logits(cfg, params, tokens, n):
    """The serving programs over ``tokens``: a prefill of the first ``n`` and
    one-token decode steps over the rest -> logits at positions n - 1 .. end."""
    slots, width = 2, 4
    pools = G.init_paged_pools(cfg, 1 + slots * width, BLOCK, slots=slots)
    bucket = -(-n // BLOCK) * BLOCK
    prompt = np.zeros((1, bucket), np.int32)
    prompt[0, :n] = tokens[:n]
    tables = np.zeros((slots, width), np.int32)
    tables[1] = 1 + np.arange(width)
    pools, last, _ = G.paged_prefill(params, jnp.asarray(prompt), jnp.int32(n), pools,
                                     jnp.asarray(tables[1, :bucket // BLOCK]), cfg, slot=jnp.int32(1))
    rows, active = [np.asarray(last)], jnp.asarray([False, True])
    for t in range(n, len(tokens)):
        lg, pools = G.paged_forward_step(
            params, jnp.asarray([0, tokens[t]], jnp.int32), pools, jnp.asarray(tables),
            jnp.asarray([0, t], jnp.int32), active, cfg)
        rows.append(np.asarray(lg[1, 0]))
    return np.stack(rows)


@pytest.mark.parametrize("name,index", CONSTANTS, ids=[f"{n}-{i}" for n, i in CONSTANTS])
def test_the_folded_tree_equals_the_reference_s_explicit_constant(name, index):
    """ONE constant away from 1 (the others 1): the programs over the FOLDED
    tree (a prefill, then decode steps through pages and state) give the
    logits the reference gives over the UNFOLDED tree with that constant
    multiplied where the published forward multiplies it; and the reference
    without it is far away, so the case can see its constant."""
    sizes = dict(TOY, mup_multipliers=_only(name, index))
    cfg = GPTConfig(**sizes)
    raw = gpt.init(cfg, KEY)
    served = G.init_serving_params(cfg, KEY)  # folds leaf by leaf
    tokens = np.random.default_rng(5).integers(1, 512, size=17).tolist()
    got = _program_logits(cfg, served, tokens, 12)
    want = np.asarray(ref.logits(raw, jnp.asarray([tokens]), sizes, folded=False))[0, 11:]
    assert float(np.max(np.abs(got - want))) < F32_ROUNDINGS
    without = np.asarray(ref.logits(raw, jnp.asarray([tokens]), dict(TOY, mup_multipliers={}),
                                    folded=False))[0, 11:]
    assert float(np.max(np.abs(without - want))) > 100 * F32_ROUNDINGS
    # and the reference un-folds the served tree by its own table of places
    again = np.asarray(ref.logits(served, jnp.asarray([tokens]), sizes))[0, 11:]
    assert float(np.max(np.abs(again - want))) < F32_ROUNDINGS


def test_the_fold_is_the_one_place_the_constants_live(toy):
    """``fold_mup`` multiplies each scaled leaf once, in float32; a tree made
    leaf by leaf is that tree to the bit, in bfloat16 too; nothing else of the
    tree moves, and no program reads the constants."""
    cfg, raw, served = toy
    for kw in (TOY, dict(TOY, dtype="bfloat16")):
        c = GPTConfig(**kw)
        made = G.init_serving_params(c, jax.random.PRNGKey(3))
        want = G.serving_params(fold_mup(gpt.init(c, jax.random.PRNGKey(3)), c), c)
        assert jax.tree.structure(made) == jax.tree.structure(want)
        for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(made)[0], jax.tree.leaves(want)):
            assert a.dtype == b.dtype and bool((a == b).all()), jax.tree_util.keystr(path)
    moved = {jax.tree_util.keystr(p) for (p, a), b in zip(
        jax.tree_util.tree_flatten_with_path(raw)[0], jax.tree.leaves(served)) if not bool((a == b).all())}
    assert moved == {f"['blocks'][{l}]['{g}']['{n}']" for l, g, n in (
        [(l, "ssm", n) for l in (0, 2) for n in ("in_kernel", "out_kernel")]
        + [(l, "attn", f"{n}_kernel") for l in (0, 2) for n in ("q", "k", "v", "out")]
        + [(l, "mlp", n) for l in (1, 3) for n in ("w1", "w2")])} | {
            "['embeddings']['word']", "['head']['kernel']"}
    seg = mup_scale("ssm", "in_kernel", cfg)
    m = cfg.mup
    assert seg.shape == (392,) and np.allclose(seg[[0, 128, 256, 320, 384]], np.asarray(
        m["ssm_multipliers"]) * m["ssm_in_multiplier"])
    assert mup_scale("attn", "k_kernel", cfg) == m["attention_in_multiplier"] * m["key_multiplier"]
    assert mup_scale("mlp", "w3", cfg) == 1.0 and mup_scale("ln_1", "scale", cfg) == 1.0
    import inspect

    for module in (G, mixer, ssm_ops, DA):  # no program reads a constant: no ``cfg.mup`` anywhere
        assert not re.search(r"\.mup\b|mup_scale", inspect.getsource(module)), module.__name__


def test_a_huge_leaf_is_drawn_folded_and_cast_in_slabs_of_rows(monkeypatch):
    """The 261,120 x 5,120 tables are 5.3 GB each in float32: more than fits
    beside the tree.  ``slab_init`` draws such a leaf in slabs of rows (here
    the toy's 512 x 64 table in 4, by a patched limit), ``model.init`` and the
    server's leaf-by-leaf start-up draw the SAME slabs, and the server folds
    and casts each before the next exists: the trees agree to the bit."""
    from paddlefleetx_tpu.models import common

    whole = gpt.init(GPTConfig(**TOY), KEY)["embeddings"]["word"]
    monkeypatch.setattr(common, "SLAB_ELEMENTS", 512 * 64 // 4)
    for kw in (TOY, dict(TOY, dtype="bfloat16")):
        cfg = GPTConfig(**kw)
        spec = gpt.gpt_specs(cfg)["embeddings"]["word"]
        assert spec.init.slabs[1] == 4 and spec.shape == (512, 64)
        raw = gpt.init(cfg, KEY)
        made = G.init_serving_params(cfg, KEY)
        want = G.serving_params(fold_mup(raw, cfg), cfg)
        for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(made)[0], jax.tree.leaves(want)):
            assert a.dtype == b.dtype and bool((a == b).all()), jax.tree_util.keystr(path)
        assert not bool((raw["embeddings"]["word"] == whole).all())  # other draws than the whole leaf's
    assert common.slab_init(len, (512, 64)).slabs == (len, 4)
    assert common.slab_init(len, (100, 64)) is len  # one slab: the initializer itself


# -- P against M and * computed apart -------------------------------------------------


def test_a_parallel_layer_is_its_mixer_and_its_attention_on_the_same_input(toy):
    """One ``P`` layer over a prompt: what it adds to the stream is what an
    ``M`` layer and a ``*`` layer holding the same weights add, each computed
    apart from the SAME input (not one after the other), and its scope wraps
    the scopes of both."""
    cfg, _, params = toy
    one = {"blocks": (params["blocks"][0],)}
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 24, 64)), jnp.float32)
    positions = jnp.arange(24, dtype=jnp.int32)[None]

    def adds(kind):
        c = GPTConfig(**dict(TOY, num_layers=1, layer_pattern=kind))
        out, _, _ = G._pattern_stack(
            one, x, None, None, c,
            lambda p, y, pools, m: (mixer.mixer_prefill(p, y, 24, c)[0], pools),
            lambda q, k, v, pools, a: (G._pattern_prefill_attention(q, k, v, c), pools), positions)
        return out - x

    both, mixed, attended = adds("P"), adds("M"), adds("*")
    assert float(jnp.max(jnp.abs(attended))) > 1e-3 < float(jnp.max(jnp.abs(mixed)))
    assert float(jnp.max(jnp.abs(both - (mixed + attended)))) < 1e-6
    c = GPTConfig(**dict(TOY, num_layers=1, layer_pattern="P"))
    text = jax.jit(lambda p, t: G._pattern_stack(
        p, t, None, None, c,
        lambda p, y, pools, m: (mixer.mixer_prefill(p, y, 24, c)[0], pools),
        lambda q, k, v, pools, a: (G._pattern_prefill_attention(q, k, v, c), pools),
        positions)[0]).lower(one, x).as_text(debug_info=True)
    for scope in ("pfx.parallel/pfx.ssm.scan", "pfx.parallel/pfx.ssm.gate_norm",
                  "pfx.parallel/pfx.attn.gqa.prefill"):
        assert scope in text, scope


def test_the_rotation_at_given_positions_is_the_rotation(toy):
    """``rope_at`` at positions 0..s-1 is ``rope``; a decode step's one token a
    row at its own position is that row of it; theta 1e11 in float32."""
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 9, 3, 16)), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(9)[None], (2, 9))
    for theta in (1e4, 1e11):
        whole = gpt.rope(x, theta)
        assert float(jnp.max(jnp.abs(gpt.rope_at(x, positions, theta) - whole))) < 1e-6
        at = jnp.asarray([[7], [3]])
        one = gpt.rope_at(jnp.stack([x[0, 7:8], x[1, 3:4]]), at, theta)
        assert float(jnp.max(jnp.abs(one[0, 0] - whole[0, 7]))) < 1e-6
        assert float(jnp.max(jnp.abs(one[1, 0] - whole[1, 3]))) < 1e-6
    assert float(jnp.max(jnp.abs(ref._rope(x, 1e11) - gpt.rope(x, 1e11)))) < 1e-6


# -- the kernels in interpret mode at the published shapes ----------------------------


@pytest.mark.parametrize("layers,slots,heads,hd,n,groups", [(2, 3, 4, 128, 256, 2), (1, 2, 2, 128, 256, 1)])
def test_the_ssm_decode_kernel_at_state_256_on_heads_of_128_equals_jnp(layers, slots, heads, hd, n, groups):
    """``pfx_ssm_decode`` (interpret mode) at the published head and state
    size ([R, 256, 128] a slot, 8 lane groups a grid step by ``_STEP_BYTES``):
    y and the rewritten states equal the ``jnp`` spelling; a dead slot's state
    and the other layer's come through bit for bit; so does ``pfx_ssm_write``."""
    rng = np.random.default_rng(heads * 10 + groups)
    shape = (layers, slots) + ssm_ops.packed_shape(heads, hd, n)
    assert shape[2:] == (heads, 256, 128)
    states = jnp.asarray(rng.normal(size=shape), jnp.float32)
    x = jnp.asarray(rng.normal(size=(slots, heads, hd)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, size=(slots, heads)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, size=(heads,)), jnp.float32)
    b, c = (jnp.asarray(rng.normal(size=(slots, groups, n)), jnp.float32) for _ in range(2))
    d = jnp.ones((heads,), jnp.float32)
    active = jnp.asarray([True, False, True][:slots])
    layer = layers - 1
    got_y, got = ssm_ops.ssm_decode_update(states, x, dt, a, b, c, d, active=active, layer=layer,
                                           impl="pallas")
    want_y, want = ssm_ops.ssm_decode_update(states, x, dt, a, b, c, d, active=active, layer=layer,
                                             impl="lax")
    assert float(jnp.max(jnp.abs(got_y - want_y))) < 1e-4  # sums over 256 states of order 1
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    assert bool((got[layer, 1] == states[layer, 1]).all()) and bool((got[:layer] == states[:layer]).all())
    new = jnp.asarray(rng.normal(size=(layers,) + shape[2:]), jnp.float32)
    written = ssm_ops.write_slot_states(states, new, 1, impl="pallas")
    assert bool((written == states.at[:, 1].set(new)).all())


@pytest.mark.parametrize("n,kv,t,bs,width,d", [(20, 4, 1, 16, 6, 128), (10, 2, 1, 8, 5, 16), (5, 1, 3, 8, 4, 16)])
def test_the_paged_decode_kernel_with_five_query_heads_a_kv_head_equals_lax(n, kv, t, bs, width, d):
    """``pfx_decode_paged`` (interpret mode) with 5 query heads a KV head, the
    published 20/4 x 128 among them: rows of different lengths, one of them
    one token long, against the ``lax`` spelling."""
    rng = np.random.default_rng(n + kv)
    rows, blocks = 3, 1 + 3 * width
    q = jnp.asarray(rng.normal(size=(rows, t, n, d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, blocks, kv, bs, d)), jnp.float32) for _ in range(2))
    tables = jnp.asarray(1 + np.arange(rows * width).reshape(rows, width), jnp.int32)
    positions = jnp.asarray([bs * width - t, 0, bs + 3], jnp.int32)
    got = DA.paged_decode_attention(q, k, v, tables, positions, layer=1, impl="pallas")
    want = DA.paged_decode_attention(q, k, v, tables, positions, layer=1, impl="lax")
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
    assert DA.paged_pages_per_step(128, 6, 5) == 4 and DA.paged_pages_per_step(16, 48, 5) == 32


# -- the state's part of the benchmark's check (runners/serve_deep_child.py) ----------


@pytest.fixture(scope="module")
def deep_child():
    return _load("serve_deep_child", "runners", "serve_deep_child.py")


def test_the_engine_s_state_is_the_reference_s_sequential_state(server, deep_child):
    """A row through the engine (prefill, 12 decode steps): the FIRST ``P``
    layer's state of its slot is the reference's after prompt + every served
    token, computed from the served (folded) tree as the benchmark's child
    hands it over, to float32 roundings in every head."""
    srv, raw = server
    eng = _engine(srv)
    prompt = np.random.default_rng(3).integers(1, 512, size=19).tolist()
    tokens, got = deep_child.engine_state(srv, eng, prompt)
    assert tokens[:19] == prompt and len(tokens) == 19 + 12 and got.shape == (8, 16, 32)
    verdict = deep_child.state_verdict(ref, srv.params, TOY, tokens, got, F32_ROUNDINGS)
    assert verdict["ok"] and verdict["state_error_worst_head"] < F32_ROUNDINGS
    unfolded = np.asarray(ref.first_state(raw, jnp.asarray([tokens]), TOY, folded=False))[0]
    assert float(np.max(np.abs(unfolded - got))) < F32_ROUNDINGS
    assert not deep_child.state_verdict(ref, srv.params, TOY, tokens[:-1], got, F32_ROUNDINGS)["ok"]


def test_a_state_kept_in_bfloat16_fails_the_state_s_check(server, deep_child, monkeypatch):
    """The control at toy widths: the same engine with its rows' state in
    bfloat16 (a patch: no option spells it) misses the reference's state by
    orders more than the float32 state does."""
    srv, _ = server
    floats = type(srv.module.config).row_state.fget

    def halved(self):
        (name, shape, _), conv = floats(self)
        return ((name, shape, "bfloat16"), conv)

    monkeypatch.setattr(type(srv.module.config), "row_state", property(halved))
    eng = _engine(srv)
    assert eng.pools.ssm.dtype == jnp.bfloat16
    prompt = np.random.default_rng(3).integers(1, 512, size=19).tolist()
    tokens, got = deep_child.engine_state(srv, eng, prompt)
    verdict = deep_child.state_verdict(ref, srv.params, TOY, tokens, got, 100 * F32_ROUNDINGS)
    assert not verdict["ok"] and verdict["state_error_worst_head"] > 1e-3


# -- what is accepted and what is refused ----------------------------------------------


@pytest.mark.parametrize("change,named", [
    (dict(mlp_act="relu2"), "position: none, mlp_act: relu2"),  # rope + relu2
    (dict(position="none"), "position: none, mlp_act: relu2"),  # none + swiglu
    (dict(tie_embeddings=True), "tie_embeddings: False"),
    (dict(layer_pattern="P-P"), "each of the 4 layers"),
    (dict(layer_pattern="P-PX"), "P \\(both"),
    (dict(layer_pattern="P-PE", num_experts=8, moe_gate="sigmoid"), "relu2 experts"),
    (dict(ssm_heads=0), "M or P layer needs ssm_heads"),
    (dict(ssm_groups=3), "ssm_groups"),
    (dict(qk_norm=True), "qk_norm"), (dict(attn_gate=True), "attn_gate"),
    (dict(sliding_window=16), "sliding_window"), (dict(kv_lora_rank=8), "kv_lora_rank"),
    (dict(mup_multipliers={"query_multiplier": 2.0}), "unknown constant 'query_multiplier'"),
    (dict(mup_multipliers={"ssm_multipliers": [1.0, 2.0]}), "ssm_multipliers takes 5"),
    (dict(num_kv_heads=3), "num_kv_heads must divide"),
])
def test_what_the_configuration_refuses_is_refused_by_name(change, named):
    with pytest.raises(ValueError, match=named):
        GPTConfig(**dict(TOY, **change))


def test_the_constants_belong_to_a_pattern_block_and_the_old_refusals_stand():
    described = dict(vocab_size=96, hidden_size=32, num_layers=2, num_attention_heads=4, norm="rmsnorm",
                     position="rope", use_bias=False, mlp_act="swiglu", tie_embeddings=False,
                     hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    GPTConfig(**described)
    with pytest.raises(ValueError, match="mup_multipliers belong to a layer_pattern block"):
        GPTConfig(**described, mup_multipliers={"key_multiplier": 0.5})
    with pytest.raises(ValueError, match="layer_pattern block"):
        GPTConfig(**dict(described, position="none"))
    with pytest.raises(ValueError, match="layer_pattern block"):
        GPTConfig(**dict(described, mlp_act="relu2"))
    cfg = GPTConfig(**TOY)
    assert hash(cfg) == hash(GPTConfig(**TOY)) and cfg.mup["mlp_multipliers"] == (0.75, 0.5)
    assert GPTConfig(**dict(TOY, mup_multipliers={})).mup == {}
    # 4 or more query heads a KV head make the 128-token page: 20/4 as 32/2
    big = dict(TOY, num_attention_heads=20, num_kv_heads=4)
    assert GPTConfig(**big).kv_block_default == 128 and GPTConfig(**dict(big, num_kv_heads=10)).kv_block_default == 0


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
    pytest.param("one token a row", lambda s: G.paged_forward_step(
        s.params, jnp.ones((1, 3), jnp.int32), G.init_paged_pools(s.module.config, 3, BLOCK, slots=1),
        jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32), jnp.ones((1,), bool),
        s.module.config)),
    pytest.param("pass slot", lambda s: G.paged_prefill(
        s.params, jnp.ones((1, 8), jnp.int32), jnp.int32(5),
        G.init_paged_pools(s.module.config, 3, BLOCK, slots=1), jnp.asarray([1]), s.module.config)),
])
def test_what_a_block_with_row_state_cannot_take_yet_is_refused_by_name(server, named, build):
    """Every layer of this block keeps row state, so what pages alone carry is
    refused as for the Nemotron-H block, by the option's name."""
    with pytest.raises(ValueError, match=named):
        build(server[0])


# -- the benchmark's data, arithmetic and readers -----------------------------------


def test_the_configuration_file_states_the_cut_and_the_arithmetic_counts_the_tree():
    conf = CONF
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # every published number under its key, but the two reduced
        with open(catalog) as f:
            rows = [json.loads(line) for line in f]
        published = next(r for r in rows if r["name"] == "Falcon-H1-34B-Instruct")
        assert conf["source"] == published["source_url"]
        for key, want in published["config"].items():
            if key not in conf["reduced"]:
                assert conf[key] == want, key
        assert published["config"]["num_hidden_layers"] == 72 == conf["reduced_keys"]["num_hidden_layers"]["published"]
    assert conf["reduced"] == ["num_hidden_layers", "max_position_embeddings"] == list(conf["reduced_keys"])
    assert conf["num_hidden_layers"] == 6 and conf["max_position_embeddings"] == 768
    model = conf["model"]
    for key, want in dict(hidden_size=5120, num_attention_heads=20, num_kv_heads=4, attn_head_dim=128,
                          ffn_hidden_size=21504, vocab_size=261120, ssm_heads=32, ssm_head_dim=128,
                          ssm_state=256, ssm_groups=2, ssm_conv=4, ssm_chunk=128, norm_eps=1e-5,
                          rope_theta=1e11, num_layers=12, layer_pattern="P-" * 6).items():
        assert model[key] == want, key  # every width as published, the whole vocabulary
    for name in MUP_NAMES:  # the published constants, under their published names
        assert model["mup_multipliers"][name] == conf[name], name
    cfg = GPTConfig(**model)  # the program takes the file's sizes as they are
    assert cfg.kv_layers == cfg.ssm_layers == 6 and cfg.kv_block_default == 128
    for key in ("assumed", "deployment", "distorts", "reference_limits"):
        assert conf[key], key
    math_ = _load("falcon_h1_math", "math", "falcon_h1.py")
    parts = math_.layer_params(model)
    assert round(sum(parts.values()) / 1e6, 2) == 430.12 and round(parts["mlp"] / sum(parts.values()), 2) == 0.77
    assert round(math_.param_count(model, 72) / 1e9, 2) == 33.64
    assert round(math_.weight_bytes(model) / 1e9, 2) == 10.51
    assert math_.state_bytes_per_row(model) == 6 * (32 * 128 * 256 * 4 + 3 * 5120 * 2) == 25_350_144
    assert math_.cached_token_bytes(model) == 6 * 4 * 128 * 2 * 2 == 12_288
    row, token = cfg.row_state, cfg.cached_token
    assert 6 * int(np.prod(row[0][1])) * 4 + 6 * int(np.prod(row[1][1])) * 2 == 25_350_144
    assert 6 * sum(h * w for h, w in token) * 2 == 12_288
    step = math_.decode_step_bytes(model, 24, 24 * 400)
    assert round(step["weights"] / 1e9, 2) == 7.84 and step["states"] == 24 * 2 * 6 * 4_194_304
    assert 1.2e12 < math_.prefill_flops(model, 256) < 1.4e12
    toy = conf["rehearse_model"]
    tree = G.init_serving_params(GPTConfig(**toy, dtype="float32"), KEY)
    assert sum(a.size for a in jax.tree.leaves(tree)) == math_.param_count(toy)  # counts the program's tree
    work = math_.ssm_decode_work(model, 12345.0, 1.0)
    assert work["bytes"] == 6 * (2 * 32 * 128 * 256 * 4 + (4 * 4096 + 2 * 2 * 256) * 4)
    gqa = math_.gqa_decode_work(model, 1000.0, 10.0)
    assert gqa["bytes"] == 1000 * 12_288 + 6 * 10 * 2 * 20 * 128 * 2 and gqa["flops"] == 6 * 1000 * 20 * 128 * 4
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    assert math_.roofline_seconds(gqa, peaks) == gqa["bytes"] / 819e9  # the HBM bounds it
    limits = conf["reference_limits"]
    assert 0 < limits["past_band_share_max"] < 1 and 0 < limits["argmax_agree_min"] < 1
    assert 0 < limits["state_error_worst_head_max"] < 0.1


def test_the_attention_kernel_s_readers_read_nothing_from_a_parent_and_refuse_over_100():
    sys.path.insert(0, BENCH)
    try:
        roofline = _load("kernel_roofline", "readers", "kernel_roofline.py")
        share = _load("kernel_share", "readers", "kernel_share.py")
        import common
    finally:
        sys.path.remove(BENCH)
    kernel = "pfx_" + "decode_paged"  # a kernel's name, not a metric's (lint E10)
    for name in ("attn.decode_kernel_share", "kernels.gqa_decode_roofline"):
        with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
            assert json.load(f)["args"]["kernel"] == kernel
    ctx = {"math": CONF["math"], "model": CONF["model"],
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           "profile_counters": [{"kv_tokens": 0, "row_steps": 0}, {"kv_tokens": 9_000_000, "row_steps": 5_000}],
           "kernel_self_s": {kernel: 1.0}, "trace": {"busy_s": 3.5}}
    args = dict(kernel=kernel, work="gqa_decode_work")
    want = 100 * (9_000_000 * 12_288 + 6 * 5000 * 2 * 20 * 128 * 2) / 819e9
    assert abs(roofline.read(ctx, **args) - want) < 1e-6  # 13.5%
    assert abs(share.read(ctx, kernel=kernel) - 100 / 3.5) < 1e-9
    assert roofline.read(dict(ctx, kernel_self_s={}), **args) is None  # a parent's run
    assert share.read(dict(ctx, kernel_self_s={}), kernel=kernel) is None
    with pytest.raises(common.Fail, match="counted too high"):
        roofline.read(dict(ctx, kernel_self_s={kernel: 0.1}), **args)
