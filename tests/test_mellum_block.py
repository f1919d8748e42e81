"""A ``layer_pattern`` block whose attention layers are of TWO kinds (its
Mellum 2 spelling, docs/mellum2.md): ``W`` layers see a window and keep a RING
of pages a row, ``*`` layers see everything and keep pages that grow, each kind
rotates its own way (plain / YaRN), beside softmax-routed SwiGLU experts.  Held
to the benchmark's plain reference (pfx_bench/reference/mellum.py) on the CPU
at toy sizes with seeded weights: prefill and decode through
``PagedDecodeEngine`` and BOTH classes of pages, to 5 x the window and several
turns of the ring, against the reference's full forward pass, logits not
tokens; the same against the reference with the window off, YaRN off or the
weights not renormalised, which must FAIL; the window's edge, exactly; the four
shares of the experts against the uncut layer; the manager's two classes;
YaRN's table against a transcription of the published routine; what is
refused, by name; counters and gauges.

Everything runs in float32, where system and reference differ by accumulation
order only: the tolerance is a few float32 roundings of values of order 1.
The issue's toy (window 8, page 4) is not a page this system takes (a page is
a multiple of 8 tokens, the TPU's sublane tiling): window 8 on pages of 8 (a
ring of 2), 16 on 8 (3) and 12 on 8 (3: a window that is no whole number of
pages) stand for it."""

import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlefleetx_tpu.core.paged_cache import BlockPoolExhausted, PagedCacheManager
from paddlefleetx_tpu.models.gpt import generation as G
from paddlefleetx_tpu.models.gpt import model as gpt
from paddlefleetx_tpu.models.gpt import moe
from paddlefleetx_tpu.models.gpt.config import GPTConfig
from paddlefleetx_tpu.ops import decode_attention as DA

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "pfx_bench")  # noqa: E10 — a directory, not a metric
F32_ROUNDINGS = 5e-5  # logits of order 1 over 16 sub-blocks, float32 both sides


def _load(name, *parts):
    spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("mellum_reference", "reference", "mellum.py")
with open(os.path.join(BENCH, "configs", "mellum2-12b-a2.5b.json")) as _f:
    CONF = json.load(_f)
# the file's toy preset: "WEWEWE*E" x 2, 6 query heads on 2 KV heads, a window
# of 8, YaRN factor 16 over 16 original positions (so a context of 80 is past
# it, as 2,816 is not past 8,192: the toy makes the full layers' table matter
# more than the cell does, not less), 16 experts top-4 with 4 held; drawn at
# 0.125 so that q, k and the scores are of order 1, as at the published widths
with open(os.path.join(BENCH, "configs", "nemotron-3-nano.json")) as _f:
    CONF_NEMOTRON = json.load(_f)
TOY = dict(CONF["rehearse_model"], dtype="float32")
BLOCK = 8
KEY = jax.random.PRNGKey(0)


def _toy(**changes):
    return dict(TOY, **changes)


def _served(sizes, key=KEY):
    cfg = GPTConfig(**sizes)
    return cfg, G.serving_params(gpt.init(cfg, key), cfg)


def _server(sizes, max_dec_len=96):
    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.core.serving import GenerationServer
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import AttrDict, process_configs

    serve = {
        "Global": {"global_batch_size": 8, "seed": 7},
        "Engine": {"mix_precision": {"enable": False}, "save_load": {"save_steps": 0}},
        "Model": dict(sizes, module="GPTModule"),
        "Distributed": {},
        "Optimizer": {"name": "FusedAdamW", "lr": {"name": "Constant", "learning_rate": 1e-3}},
        "Generation": {"max_dec_len": max_dec_len, "min_dec_len": max_dec_len,
                       "decode_strategy": "greedy_search", "pad_to_multiple": 8,
                       "eos_token_id": 0, "pad_token_id": 0},
    }
    cfg = process_configs(AttrDict.from_nested(serve), num_devices=1)
    srv = GenerationServer(cfg, init_dist_env(cfg, devices=jax.devices()[:1]), build_module(cfg))
    srv.params = _served(sizes)[1]
    return srv


@pytest.fixture(scope="module")
def server():
    return _server(TOY)


def _engine(server, **kw):
    from paddlefleetx_tpu.core.continuous_batching import PagedDecodeEngine

    kw.setdefault("max_batch", 2)
    kw.setdefault("block", BLOCK)
    return PagedDecodeEngine(server, **kw)


# -- the configuration, the tree and the pools ------------------------------------------


def test_the_published_configuration_is_accepted_and_says_its_kinds():
    cfg = GPTConfig(**CONF["model"])
    G.check_servable(cfg)
    assert cfg.layer_pattern == "WEWEWE*E" * 7 and cfg.num_layers == 56
    assert (cfg.kv_layers, cfg.window_layers, cfg.ssm_layers) == (7, 21, 0) and cfg.row_state == ()
    assert cfg.kv_block_default == 128 and cfg.ring_pages(128) == 9 and cfg.ring_pages(16) == 65
    assert cfg.layer_kind(0) == (1024, True) and cfg.layer_kind(6) == (0, True)
    assert cfg.layer_rotation("W") == (False, 1.0)
    scaled, factor = cfg.layer_rotation("*")
    assert scaled and factor == pytest.approx(1.2772588722239782, rel=1e-12)
    assert cfg.moe_dropless and cfg.experts_held == 16 and cfg.sorted_pair_products == 28 * 3
    with pytest.raises(ValueError, match="no attention layer"):
        cfg.layer_kind(1)
    # every number of the published config is in the file under its own key
    for key, want in (("hidden_size", 2304), ("num_attention_heads", 32), ("head_dim", 128),
                      ("num_key_value_heads", 4), ("moe_intermediate_size", 896),
                      ("num_experts_per_tok", 8), ("sliding_window", 1024), ("vocab_size", 98304),
                      ("num_hidden_layers", 28), ("intermediate_size", 7168)):
        assert CONF[key] == want
    assert CONF["reduced"] == ["num_experts", "max_position_embeddings"]
    assert CONF["num_experts"] == 16 and CONF["reduced_keys"]["num_experts"]["published"] == 64
    assert CONF["rope_parameters"]["full_attention"]["attention_factor"] == 1.2772588722239782
    kinds = "".join("*" if t == "full_attention" else "W" for t in CONF["layer_types"])
    assert cfg.layer_pattern == "".join(k + "E" for k in kinds)


def test_served_tree_and_both_classes_of_pages():
    cfg, params = _served(TOY)
    assert len(params["blocks"]) == 16
    win, ex, full = params["blocks"][0], params["blocks"][1], params["blocks"][6]
    assert set(win) == set(full) == {"ln_1", "attn", "mlp"} and win["mlp"] == {}
    assert win["attn"]["q_kernel"].shape == (64, 6, 16) and win["attn"]["k_kernel"].shape == (64, 2, 16)
    assert set(ex["mlp"]) == {"router_kernel", "experts", "e_score_correction_bias"}  # no shared expert
    assert ex["mlp"]["router_kernel"].shape == (64, 16) and set(ex["mlp"]["experts"]) == {"w1", "w3", "w2"}
    assert ex["mlp"]["experts"]["w1"].shape == (4, 64, 24)
    assert (cfg.kv_layers, cfg.window_layers) == (2, 6) and cfg.ring_pages(BLOCK) == 2
    pools = G.init_paged_pools(cfg, 9, BLOCK, ring_blocks=5)
    assert pools.k.shape == pools.v.shape == (2, 9, 2, BLOCK, 16)
    assert pools.wk.shape == pools.wv.shape == (6, 5, 2, BLOCK, 16)
    assert pools.fields() == ("k", "v", "wk", "wv")
    with pytest.raises(ValueError, match="ring blocks"):
        G.init_paged_pools(cfg, 9, BLOCK)


@pytest.mark.parametrize("change,named", [
    (dict(sliding_window=0), "W layer needs sliding_window"),
    (dict(layer_pattern="*E" * 8), "sliding_window a W layer"),
    (dict(global_attn_every=4), "global_attn_every"),
    (dict(qk_norm=True), "qk_norm"), (dict(attn_gate=True), "attn_gate"),
    (dict(post_norms=True), "post_norms"),
    (dict(position="none", mlp_act="relu2"), "position: rope"),
    (dict(layer_pattern="WEWEWE*-" * 2), "relu2 experts"),
    (dict(moe_gate="gshard"), "moe_gate: sigmoid or softmax"),
    (dict(moe_gate="softmax", moe_n_group=2, moe_topk_group=1), "moe_n_group needs moe_gate: sigmoid"),
    (dict(moe_experts_held=4, moe_expert_offset=14), "experts 14..17 held of 16"),
])
def test_what_the_configuration_refuses_is_refused_by_name(change, named):
    with pytest.raises(ValueError, match=named):
        GPTConfig(**_toy(**change))


# -- through the engine and both arenas, against the reference's full forward pass -------


def _serve_rows(eng, prompts, budgets, stagger=3):
    """Rows admitted ``stagger`` steps apart -> {name: (prompt, served
    tokens, the pending logits after the admission and after every step)}."""
    seen, live, tokens = {}, set(), {}
    todo = list(zip(range(len(prompts)), prompts, budgets))

    def admit():
        name, prompt, budget = todo.pop(0)
        slot = eng.admit(prompt, budget)
        seen[name] = (prompt, [np.asarray(eng._logits[slot])], slot)
        live.add(name)

    steps = 0
    admit()
    while live or todo:
        if todo and steps and steps % stagger == 0 and eng.free_slots():
            admit()
        rows = {s for s, r in enumerate(eng.slots) if r is not None and eng.active[s]}
        finished = eng.step()
        steps += 1
        for name, (_, logits, slot) in seen.items():
            if slot in rows and name in live:
                logits.append(np.asarray(eng._logits[slot]))
        for s in finished:
            name = next(k for k, v in seen.items() if v[2] == s and k in live)
            tokens[name] = list(eng.slots[s].tokens)
            assert len(eng.slots[s].ring) == eng.ring_pages  # never more, at any context
            live.discard(name)
            eng.release(s)
    return {name: (prompt, tokens[name], np.stack(logits)) for name, (prompt, logits, _) in seen.items()}


def _reference_rows(params, sizes, prompt, out, **controls):
    full = jnp.asarray([prompt + out])
    return np.asarray(ref.logits(params, full, sizes, **controls))[0, len(prompt) - 1:]


@pytest.mark.parametrize("window,heads", [(8, 6), (16, 6), (12, 8)])
def test_prefill_and_decode_through_both_arenas_equal_the_full_forward(window, heads):
    """Rows through the engine to 5 x the window: a prompt shorter than the
    window, one of several windows (its prefill keeps the last window's pages
    alone), one of 2 tokens; a row admitted into a slot AND a ring another row
    left.  After the admission and after every step the pending LOGITS equal
    the reference's at that position.  The SAME served rows against the
    reference with the window off, with YaRN off on the full layers, or with
    the chosen weights not renormalised miss by orders: the check sees each."""
    sizes = _toy(sliding_window=window, num_attention_heads=heads)
    srv = _server(sizes)
    params = srv.params
    eng = _engine(srv)
    ring = eng.ring_pages
    assert ring == -(-window // BLOCK) + 1 and eng.cache.ring_allocator.num_blocks == 2 * ring + 1
    rng = np.random.default_rng(window)
    lens = [5, 3 * window + 3, 2, window + 1]
    prompts = [rng.integers(1, 512, size=n).tolist() for n in lens]
    budgets = [5 * window - 5, 2 * window, 4 * window, 9]
    served = _serve_rows(eng, prompts, budgets)
    assert eng.cache.ring_allocator.used_count() == 0 and eng.cache.allocator.used_count() == 0
    worst = {}
    for name, (prompt, out, got) in served.items():
        assert len(out) == budgets[name] and len(got) == len(out) + 1
        assert len(prompt) + len(out) > window  # every row leaves its window
        want = _reference_rows(params, sizes, prompt, out)
        assert float(np.max(np.abs(got - want))) < F32_ROUNDINGS, name
        rows = want[:len(out)].copy()
        rows[:, 0] = -np.inf  # min_dec_len: the end token cannot be chosen
        assert rows.argmax(-1).tolist() == out, name
        for control in ("window_off", "yarn_off", "no_renorm"):
            off = _reference_rows(params, sizes, prompt, out, **{control: True})
            worst[control] = max(worst.get(control, 0.0), float(np.max(np.abs(got - off))))
    # each control moves the logits by at least a thousand tolerances
    assert all(v > 1000 * F32_ROUNDINGS for v in worst.values()), worst
    # the pages' counters: the window layers attended min(context, window) a live (row, step)
    assert 0 < eng.stats["kv_window_tokens"] < eng.stats["kv_tokens"]
    assert eng.stats["kv_window_tokens"] <= window * eng.stats["row_steps"]


def test_a_dead_slot_between_two_live_ones_is_skipped_by_both_kinds_of_layer():
    """Three slots; the middle row finishes after two tokens and nobody takes
    its slot, so most steps run with slot 1 DEAD between two live rows: the
    step's live list is [0, 2], both kinds of attention layer follow it (the
    full layers' pages and the window layers' rings), and the two live rows'
    pending logits still equal the reference's at every position, well past
    the window."""
    window = 8
    sizes = _toy(sliding_window=window, num_attention_heads=6)
    srv = _server(sizes)
    eng = _engine(srv, max_batch=3)
    slots, admit = [], eng.admit
    eng.admit = lambda *a, **kw: slots.append(admit(*a, **kw)) or slots[-1]
    rng = np.random.default_rng(43)
    prompts = [rng.integers(1, 512, size=n).tolist() for n in (5, 3, window + 3)]
    budgets = [3 * window, 2, 3 * window - 4]
    served = _serve_rows(eng, prompts, budgets, stagger=1)
    assert slots == [0, 1, 2]
    # rows 0 and 2 ran on for some twenty steps after row 1 left
    assert eng.stats["row_steps"] == sum(budgets)
    assert eng.stats["slot_steps"] - eng.stats["row_steps"] > 2 * window
    for name, (prompt, out, got) in served.items():
        assert len(out) == budgets[name]
        want = _reference_rows(srv.params, sizes, prompt, out)
        assert float(np.max(np.abs(got - want))) < F32_ROUNDINGS, name


def test_a_prefill_writes_only_the_last_window_s_pages_into_the_ring(server):
    """A prompt of 43 tokens on pages of 8 with a window of 8: the row's first
    decode step (position 43) sees tokens 36..43, pages 4 and 5; the prefill
    writes those two into ring slots 0 and 1 and nothing else of the class."""
    eng = _engine(server)
    before = np.asarray(eng.pools.wk)
    prompt = np.random.default_rng(5).integers(1, 512, size=43).tolist()
    slot = eng.admit(prompt, 4)
    after = np.asarray(eng.pools.wk)
    ring = eng.slots[slot].ring
    changed = {int(b) for b in np.nonzero(np.abs(after - before).sum(axis=(0, 2, 3, 4)))[0]}
    assert changed == set(ring) and len(ring) == 2
    # and they ARE pages 4 and 5 of the window layers' keys: what a prefill of the
    # same prompt writes into the growing class of a pattern with the window off
    # would hold there; here: page 4 -> slot 4 % 2 = 0, page 5 -> slot 1
    full = np.asarray(eng.pools.k)[:, eng.slots[slot].table[:6]]  # the * layers' pages 0..5
    assert np.abs(full).sum(axis=(0, 2, 3, 4)).min() > 0  # the growing class holds all six
    eng.release(slot)


# -- the window's edge, exactly -----------------------------------------------------------


@pytest.mark.parametrize("impl", ["lax", "pallas"])
@pytest.mark.parametrize("window,block,positions", [
    (1024, 128, (1023, 1024, 1151, 1152, 2815, 700)),
    (16, 8, (15, 16, 23, 24, 77, 3)),
    (12, 8, (11, 12, 40, 5, 0, 19)),
])
def test_the_ring_read_is_the_window_to_the_token(impl, window, block, positions):
    """``window_view`` + the windowed paged read over a ring, against dense
    softmax attention over the row's whole history with the window as a mask:
    equal; a change to the key at distance ``window`` (i - j = 1024: just
    outside) moves NOTHING, to the bit, and one at distance ``window - 1``
    (1023: the oldest key inside) moves the result."""
    kv, group, d = 2, 4, 128 if block == 128 else 16
    R = -(-window // block) + 1
    b = len(positions)
    rng = np.random.default_rng(window + block)
    longest = max(positions) + 1
    keys = rng.normal(size=(b, longest, kv, d)).astype(np.float32)
    vals = rng.normal(size=(b, longest, kv, d)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(b, 1, kv * group, d)), jnp.float32)
    rings = 1 + np.arange(b * R).reshape(b, R)[:, ::-1].copy()  # any ids, none shared

    def pools(keys, vals):
        wk = np.zeros((2, b * R + 1, kv, block, d), np.float32)
        wv = np.zeros_like(wk)
        for i, pos in enumerate(positions):
            for t in range(pos + 1):  # as the steps wrote them: newer tokens overwrite
                slot = rings[i, (t // block) % R]
                wk[1, slot, :, t % block], wv[1, slot, :, t % block] = keys[i, t], vals[i, t]
        return jnp.asarray(wk), jnp.asarray(wv)

    def read(keys, vals):
        wk, wv = pools(keys, vals)
        pos = jnp.asarray(positions, jnp.int32)
        tables, at, starts = DA.window_view(jnp.asarray(rings, jnp.int32), pos, window, block)
        return np.asarray(DA.paged_decode_attention(q, wk, wv, tables, at, layer=1, starts=starts,
                                                    impl=impl))

    got = read(keys, vals)
    for i, pos in enumerate(positions):
        lo = max(0, pos - window + 1)
        k = np.repeat(keys[i, lo:pos + 1], group, axis=1)  # [j, n, d]
        v = np.repeat(vals[i, lo:pos + 1], group, axis=1)
        s = np.einsum("nd,jnd->nj", np.asarray(q[i, 0]), k) / math.sqrt(d)
        p = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("nj,jnd->nd", p / p.sum(-1, keepdims=True), v)
        assert np.abs(got[i, 0] - want).max() < 2e-5, (i, pos)
    moved_out, moved_in = keys.copy(), keys.copy()
    for i, pos in enumerate(positions):
        if pos - window >= 0:
            moved_out[i, pos - window] += 3.0  # i - j = window: outside
        if pos - window + 1 >= 0:
            moved_in[i, pos - window + 1] += 3.0  # i - j = window - 1: the oldest inside
    assert (read(moved_out, vals) == got).all()
    inside = [i for i, pos in enumerate(positions) if pos - window + 1 >= 0]
    assert inside and all(np.abs(read(moved_in, vals)[i] - got[i]).max() > 1e-4 for i in inside)


# -- the rotation's tables ------------------------------------------------------------------


def _hf_yarn(dim, base, factor, original, beta_fast, beta_slow, attention_factor=None, truncate=True):
    """transformers' ``_compute_yarn_parameters``, transcribed literally in
    numpy (modeling_rope_utils.py) -> (inv_freq [dim / 2], attention_factor)."""
    def get_mscale(scale, mscale=1):
        return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0

    if attention_factor is None:
        attention_factor = get_mscale(factor)

    def find_correction_dim(num_rotations, dim, base, max_position_embeddings):
        return (dim * math.log(max_position_embeddings / (num_rotations * 2 * math.pi))) / (
            2 * math.log(base))

    def find_correction_range(low_rot, high_rot, dim, base, max_position_embeddings, truncate):
        low = find_correction_dim(low_rot, dim, base, max_position_embeddings)
        high = find_correction_dim(high_rot, dim, base, max_position_embeddings)
        if truncate:
            low, high = math.floor(low), math.ceil(high)
        return max(low, 0), min(high, dim - 1)

    def linear_ramp_factor(lo, hi, n):
        if lo == hi:
            hi += 0.001
        return np.clip((np.arange(n, dtype=np.float32) - lo) / (hi - lo), 0, 1)

    pos_freqs = base ** (np.arange(0, dim, 2).astype(np.float32) / dim)
    inv_freq_extrapolation = 1.0 / pos_freqs
    inv_freq_interpolation = 1.0 / (factor * pos_freqs)
    low, high = find_correction_range(beta_fast, beta_slow, dim, base, original, truncate)
    extrapolation_factor = 1 - linear_ramp_factor(low, high, dim // 2)
    inv_freq = (inv_freq_interpolation * (1 - extrapolation_factor)
                + inv_freq_extrapolation * extrapolation_factor)
    return inv_freq, attention_factor


def test_yarn_s_table_is_the_published_routine_s_and_the_window_layers_is_plain():
    cfg = GPTConfig(**CONF["model"])
    pub = CONF["rope_parameters"]["full_attention"]
    want, factor = _hf_yarn(128, pub["rope_theta"], pub["factor"], pub["original_max_position_embeddings"],
                            pub["beta_fast"], pub["beta_slow"], pub["attention_factor"])
    assert _hf_yarn(128, 500000, 16, 8192, 32, 1)[1] == pytest.approx(pub["attention_factor"], rel=1e-12)
    got = np.asarray(gpt.rope_frequencies(cfg, 128))
    np.testing.assert_allclose(got, want, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(ref.inv_frequencies(128, CONF["model"], True)[0]), want, rtol=2e-6)
    assert ref.inv_frequencies(128, CONF["model"], True)[1] == pytest.approx(factor, rel=1e-12)
    plain = 500000.0 ** (-np.arange(64, dtype=np.float32) / 64)
    assert got[0] == pytest.approx(1.0) and (got[-20:] < plain[-20:] / 15.9).all()  # the slow dims / 16
    assert 0 < np.sum(np.abs(got / plain - 1) < 1e-6) < 64  # the fast dims kept
    # through the program: a full layer's q carries the blended angles AND the factor,
    # a window layer's the plain ones and no factor
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 3, 2, 128)), jnp.float32)
    at = jnp.asarray([[0, 1000, 2815]], jnp.int32)
    for kind, (inv, m) in (("*", (want, factor)), ("W", (plain, 1.0))):
        ang = np.asarray(at, np.float64)[..., None, None] * inv.astype(np.float64)
        x1, x2 = np.asarray(x)[..., :64], np.asarray(x)[..., 64:]
        rot = np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                              x2 * np.cos(ang) + x1 * np.sin(ang)], -1) * m
        np.testing.assert_allclose(np.asarray(gpt.layer_rope_at(x, at, cfg, kind)), rot, atol=2e-3)
    assert np.abs(np.asarray(gpt.layer_rope_at(x, at, cfg, "*") - gpt.layer_rope_at(x, at, cfg, "W"))).max() > 0.5


# -- the expert layer: the softmax rule and the chip's share ----------------------------------


def test_softmax_route_is_a_float32_softmax_over_all_experts_renormalised_over_the_chosen():
    cfg = GPTConfig(**TOY)
    rng = np.random.default_rng(3)
    m = jnp.asarray(rng.normal(size=(40, 64)), jnp.float32)
    kernel = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    idx, w = moe.softmax_route(m, kernel, jnp.full((16,), 7.0), cfg)  # the bias is not read
    p = np.asarray(jax.nn.softmax(np.asarray(m) @ np.asarray(kernel), axis=-1))
    order = np.argsort(-p, axis=-1)[:, :4]
    assert (np.sort(np.asarray(idx), -1) == np.sort(order, -1)).all()
    chosen = np.take_along_axis(p, np.asarray(idx), -1)
    np.testing.assert_allclose(np.asarray(w), chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)
    assert moe.route(cfg) is moe.softmax_route
    assert moe.route(GPTConfig(**_toy(moe_gate="sigmoid"))) is moe.sigmoid_route
    ridx, rw = ref.route(m, kernel, TOY)
    assert (np.asarray(ridx) == np.asarray(idx)).all()
    np.testing.assert_allclose(np.asarray(rw), np.asarray(w), rtol=1e-5)


@pytest.mark.parametrize("every_held_expert", [False, True])
def test_the_four_shares_of_the_experts_add_up_to_the_uncut_layer(every_held_expert):
    """The guide's share test, for the softmax rule: the program's expert
    layer as each of the 4 chips of the deployment holds it (4 of 16 experts
    from offset 0, 4, 8, 12), the router whole, summed over the chips, is the
    reference's layer with all 16 experts; and a share alone is not (the
    weights are renormalised over the chosen 4 of ALL 16, not over the held)."""
    whole = _toy(moe_experts_held=16)
    cfg_all, params_all = _served(whole)
    mlp = params_all["blocks"][1]["mlp"]
    rng = np.random.default_rng(9)
    m = jnp.asarray(rng.normal(size=(48, 64)), jnp.float32)
    want = np.asarray(ref.routed_experts(m, mlp, whole))
    total, held_sum = np.zeros_like(want), 0
    for offset in (0, 4, 8, 12):
        cfg = GPTConfig(**_toy(moe_expert_offset=offset))
        share = dict(mlp, experts=jax.tree.map(lambda a: a[offset:offset + 4], mlp["experts"]))
        out, stats = moe.routed_experts(share, m, mlp["e_score_correction_bias"], cfg,
                                        every_held_expert=every_held_expert)
        part = np.asarray(ref.routed_experts(m, share, whole, offset=offset))
        assert np.abs(np.asarray(out) - part).max() < F32_ROUNDINGS
        total += np.asarray(out)
        held_sum += int(stats["pairs_held"])
        assert int(jnp.sum(stats["load"])) == 48 * 4
    assert held_sum == 48 * 4  # every pair is held by exactly one chip
    assert np.abs(total - want).max() < F32_ROUNDINGS and np.abs(want).max() > 0.1
    assert np.abs(np.asarray(out) - want).max() > 0.05  # one share alone is not the layer


# -- the manager: two classes of pages in one ---------------------------------------------------


def test_the_manager_keeps_two_classes_and_refuses_on_either():
    mgr = PagedCacheManager(1 + 3 * 22, 128, ring_blocks=1 + 2 * 9, ring_pages=9)
    a = mgr.admit(1, 2816)
    assert len(a) == 22 and len(mgr.ring(1)) == 9 and mgr.ring(1) == mgr.ring(1)
    mgr.admit(2, 1700)  # full pages follow the row's reservation, the ring does not
    assert mgr.blocks_of(2) == 14 and len(mgr.ring(2)) == 9
    assert set(mgr.ring(1)).isdisjoint(mgr.ring(2))
    st = mgr.stats()
    assert (st["kv_blocks_used"], st["kv_ring_blocks_used"], st["kv_ring_blocks_free"]) == (36, 18, 0)
    # the window layers' class is short (the growing one has room for 30 pages)
    assert not mgr.can_admit(128) and mgr.allocator.free_count() == 30
    with pytest.raises(BlockPoolExhausted):
        mgr.admit(3, 128)
    assert mgr.allocator.free_count() == 30 and mgr.live_sequences() == 2  # nothing was taken
    mgr.release(2)
    assert mgr.ring_allocator.free_count() == 9 and mgr.allocator.free_count() == 44
    # the growing class is short: the ring reserved for the row goes back
    assert not mgr.can_admit(45 * 128)
    with pytest.raises(BlockPoolExhausted):
        mgr.admit(4, 45 * 128)
    assert mgr.ring_allocator.free_count() == 9 and mgr.allocator.free_count() == 44
    mgr.release(1)
    assert mgr.allocator.used_count() == mgr.ring_allocator.used_count() == 0
    with pytest.raises(ValueError, match="come together"):
        PagedCacheManager(9, 128, ring_pages=9)
    with pytest.raises(ValueError, match="which class a page is"):
        PagedCacheManager(9, 128, prefix_blocks=4, ring_blocks=10, ring_pages=9)
    plain = PagedCacheManager(9, 128)
    plain.admit(1, 300)
    assert plain.ring(1) == [] and "kv_ring_blocks_used" not in plain.stats()


def test_forty_eight_rows_of_the_cap_close_the_accounting():
    """The cell's arena on auto: 48 slots x 22 growing pages + 48 x 9 ring
    pages; 48 rows of the 2,816 cap fill both classes to the page and a 49th
    is refused; released, both are empty.  In bytes: 343 layer-pages a row."""
    cfg = GPTConfig(**CONF["model"])
    ring = cfg.ring_pages(128)
    mgr = PagedCacheManager(1 + 48 * 22, 128, ring_blocks=1 + 48 * ring, ring_pages=ring)
    for row in range(48):
        assert mgr.can_admit(2816)
        mgr.admit(row, 2816)
    assert mgr.allocator.free_count() == mgr.ring_allocator.free_count() == 0
    assert not mgr.can_admit(1)
    page = 2 * 4 * 128 * 128 * 2  # K and V of 4 heads x 128 tokens x 128, bfloat16
    row_bytes = (22 * cfg.kv_layers + ring * cfg.window_layers) * page
    assert page == 262144 and row_bytes == 343 * 262144 and 48 * row_bytes == 4_315_938_816
    assert 22 * (cfg.kv_layers + cfg.window_layers) * page * 48 > 7.7e9  # an allocator without a window
    for row in range(48):
        mgr.release(row)
    assert mgr.allocator.used_count() == mgr.ring_allocator.used_count() == 0


def test_the_engine_sizes_both_classes_and_refuses_by_name_what_they_lack(server):
    from paddlefleetx_tpu.ops.speculative import SpecConfig

    eng = _engine(server, max_batch=3)
    assert eng.cache.allocator.num_blocks == 3 * eng.max_row_blocks + 1
    assert eng.cache.ring_allocator.num_blocks == 3 * 2 + 1 and eng.pools.wk.shape[1] == 7
    # under --kv-blocks the ring class holds as many rows as the growing one does at the cap
    small = _engine(server, max_batch=3, num_blocks=2 * 16 + 1)
    assert small.cache.ring_allocator.num_blocks == 2 * 2 + 1
    assert eng.kv_bytes_per_token() == 2 * 2 * (2 * 16) * 4  # the 2 full layers: K and V, 2 heads x 16
    assert eng.ring_bytes_per_row() == 6 * 2 * 2 * (2 * BLOCK * 16) * 4  # 6 window layers x 2 pages
    for kw, named in ((dict(prefix_cache_blocks=4), "--prefix-cache-blocks"),
                      (dict(prefill_chunk=8), "--prefill-chunk"),
                      (dict(kv_dtype="int8"), "--kv-dtype int8"),
                      (dict(spec=SpecConfig(draft_k=2)), "--draft-k")):
        with pytest.raises(ValueError, match=f"{named}.*two classes of pages"):
            _engine(server, **kw)
    slot = eng.admit([5, 6, 7], 4)
    for call, named in ((lambda: eng.preempt_row(slot), "preempt-resume"),
                        (lambda: eng.prefill_export([1, 2, 3], 4), "KV handoff"),
                        (lambda: eng.adopt({}, {}), "KV handoff")):
        with pytest.raises(ValueError, match=f"{named}.*two classes of pages"):
            call()
    # a third row finds no slot; with slots to spare and no ring left it stays queued
    two = _engine(server, max_batch=3, num_blocks=2 * 16 + 1)
    two.admit([1, 2, 3], 4), two.admit([4, 5, 6], 4)
    assert two.free_slots() == 1 and not two.can_admit(3, 4)
    with pytest.raises(BlockPoolExhausted):
        two.admit([7, 8, 9], 4)


# -- the scheduler, the counters and the gauges ---------------------------------------------------


def test_the_scheduler_serves_and_counts_both_classes(server):
    from paddlefleetx_tpu.core.continuous_batching import ContinuousScheduler

    eng = _engine(server, max_batch=3)
    sched = ContinuousScheduler(eng, max_depth=16, name="mellum-test")
    sched.start()
    try:
        rng = np.random.default_rng(17)
        prompts = [rng.integers(1, 512, size=n).tolist() for n in (19, 4, 30, 11)]
        futures = [sched.submit([p], 20 + 3 * i) for i, p in enumerate(prompts)]
        outs = [f.result(timeout=300)[0] for f in futures]
    finally:
        sched.shutdown()
    for i, (p, out) in enumerate(zip(prompts, outs)):
        assert len(out) == 20 + 3 * i
        rows = _reference_rows(server.params, TOY, p, out)[:len(out)].copy()
        rows[:, 0] = -np.inf
        assert rows.argmax(-1).tolist() == out
    got = {(name, tuple(sorted(labels.items()))): v for name, labels, v in sched.collect()}
    tokens = got[("pfx_sched_decode_kv_tokens_total", ())]
    window = got[("pfx_sched_decode_kv_window_tokens_total", ())]
    assert 0 < window < tokens and window <= 8 * got[("pfx_sched_decode_row_steps_total", ())]
    assert got[("pfx_kv_bytes_per_token", ())] == eng.kv_bytes_per_token()
    assert got[("pfx_kv_ring_bytes_per_row", ())] == eng.ring_bytes_per_row()
    for cls, total in (("full", 3 * eng.max_row_blocks), ("window", 3 * 2)):
        held = got[("pfx_kv_pages_held", (("class", cls),))]
        free = got[("pfx_kv_pages_free", (("class", cls),))]
        assert held == 0 and free == total  # every row released both
    assert got[("pfx_moe_serve_held_pairs_total", ())] > 0
    assert got[("pfx_moe_serve_grouped_calls_total", ())] == 4 * 8 * 3  # 8 expert layers x 3 matrices
    # the published widths: what the gauges read in the cell
    cfg = GPTConfig(**CONF["model"])
    assert cfg.kv_layers * 2 * 4 * 128 * 2 == 14336
    assert cfg.window_layers * cfg.ring_pages(128) * 262144 == 49_545_216


# -- what served tokens cannot see of the router ---------------------------------------------------


@pytest.mark.parametrize("control,ok", [("", True), ("window_off", True), ("router_bf16", False),
                                        ("no_renorm", False)])
def test_the_route_s_part_reads_the_router_s_precision_and_nothing_before_it(control, ok):
    """``serve_paged_child.route_verdict`` (what the chip check judges beside
    the served tokens): the reference's float32 input of the first expert
    layer through ``moe.route(cfg)`` and through the reference's router.  In
    float32 the two agree to a rounding under the limit the configuration's
    file enters; the reference with its router in bfloat16 (the control no
    share of served tokens showed on the chip, PERF.md section 6, PR 42) or
    its weights not renormalised misses it by orders of magnitude; a control
    that moves the layers BEFORE the router moves nothing here."""
    from types import SimpleNamespace

    child = _load("serve_paged_child", "runners", "serve_paged_child.py")
    cfg, params = _served(TOY)
    rng = np.random.default_rng(0)
    served = [{"prompt_ids": rng.integers(1, 512, size=40).tolist(),
               "tokens": rng.integers(1, 512, size=n).tolist()} for n in (30, 60)]
    limit = float(CONF["reference_limits"]["route_weight_err_max"])
    got = child.route_verdict(SimpleNamespace(module=SimpleNamespace(config=cfg), params=params),
                              served, dict(TOY, control=control), CONF, limit)
    assert got["tokens"] == 170 and got["ok"] is ok, got
    if ok:
        assert got["weight_err_median"] < limit / 10 and got["same_set_share"] == 1.0
    else:
        assert got["weight_err_median"] > limit * 10


def test_the_paged_child_finds_what_it_reads_in_the_arch_child():
    """``serve_paged_child.py`` runs ``serve_arch_child.py`` from its file and
    reads or sets the names in ``NAMES`` there: the file has to keep them, and
    the one ``serve.main`` call the child wraps."""
    import ast

    child = _load("serve_paged_child", "runners", "serve_paged_child.py")
    with open(os.path.join(BENCH, "runners", "serve_arch_child.py")) as f:
        tree = ast.parse(f.read())
    names, todo = set(), list(tree.body)
    while todo:
        node = todo.pop()
        if isinstance(node, ast.FunctionDef):
            names.add(node.name)
        elif isinstance(node, ast.Assign):
            names |= {leaf.id for t in node.targets for leaf in ast.walk(t)
                      if isinstance(leaf, ast.Name)}
        elif isinstance(node, ast.With):
            todo += node.body
    assert set(child.NAMES) <= names
    calls = [n for n in ast.walk(tree) if isinstance(n, ast.Call)
             and ast.unparse(n.func) == "serve.main"]
    assert len(calls) == 1
    assert set(child.reference_controls(CONF)) == set(ref.CONTROLS)
    limits = CONF["reference_limits"]
    assert {"band_in_spreads", "past_band_share_max", "argmax_agree_min",
            "route_weight_err_max"} <= set(limits)
