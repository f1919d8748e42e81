"""The described block over a residual stream of several copies mixed by maps
(its Xing4.0 spelling, docs/xing4.md) on the serving path, held to the
benchmark's plain reference (pfx_bench/reference/xing4.py) on the CPU at toy
widths with seeded weights: prefill and paged decode through the latent pool
against the reference's full forward pass, at 4 copies and at 2; the maps
alone; the two Pallas kernels (interpret mode) against their plain forms on a
decode and a prefill shape; each control of the reference; what is refused;
the scheduler end to end with its counter; the benchmark's new data, its
arithmetic and the part of the chip check that reads the maps' precision.

Everything runs in float32, where system and reference differ by
accumulation order only: the tolerances are a few float32 roundings of
values of order 1 (2e-5), and each says so where it is used."""

import ast
import dataclasses
import importlib.util
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlefleetx_tpu.models.gpt import generation as G
from paddlefleetx_tpu.models.gpt import model as gpt
from paddlefleetx_tpu.models.gpt.config import GPTConfig
from paddlefleetx_tpu.ops import hyper_connection as HC

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "pfx_bench")  # noqa: E10 — a directory, not a metric
F32_ROUNDINGS = 2e-5  # logits of order 1, float32 both sides, another summation order


def _load(name, *parts):
    spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("xing4_reference", "reference", "xing4.py")
with open(os.path.join(BENCH, "configs", "xing4.0-29b-a4b.json")) as _f:
    CONF = json.load(_f)
# the configuration's own toy sizes: 1 dense + 2 expert layers, 16 experts top-4 all
# held, a 32-wide latent with an 8-wide rotated key, YaRN and the maps as published
TOY = dict(CONF["rehearse_model"], dtype="float32")
BLOCK = 16
KEY = jax.random.PRNGKey(0)


def _served(sizes, key=KEY):
    cfg = GPTConfig(**sizes)
    params = G.init_serving_params(cfg, key)
    rng = np.random.default_rng(0)
    for blk in params["blocks"][1:]:  # a bias that moves the choice for some tokens
        blk["mlp"]["e_score_correction_bias"] = jnp.asarray(
            rng.normal(size=(sizes["num_experts"],)) * 0.05, jnp.float32)
    return cfg, params


@pytest.fixture(scope="module")
def toy():
    cfg, params = _served(TOY)
    tokens = np.random.default_rng(0).integers(1, TOY["vocab_size"], size=(2, 64))
    return cfg, params, tokens, np.asarray(ref.logits(params, jnp.asarray(tokens), TOY))


# -- the configuration and its tree ------------------------------------------------------------------


def test_the_published_configuration_is_accepted_and_its_tree_holds_the_maps():
    cfg = GPTConfig(**CONF["model"])
    assert cfg.hyper_connections and cfg.hc_maps == 24 and not cfg.classic_block
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps, cfg.hc_res_clamp) == (4, 20, 1e-6, 30.0)
    G.check_servable(cfg)
    toy_cfg, params = _served(TOY)
    assert set(params) == {"embeddings", "blocks", "final_ln", "head"} and len(params["blocks"]) == 3
    for blk in params["blocks"]:
        for name in ("hc_attn", "hc_mlp"):
            leaves = blk[name]
            assert {k: (v.shape, v.dtype.name) for k, v in leaves.items()} == {
                "phi": ((24, 4 * 64), "float32"), "alpha": ((3,), "float32"),
                "bias": ((24,), "float32")}
            assert np.all(np.asarray(leaves["alpha"]) == 1.0)
    # float32 whatever the compute dtype is, like the routers
    bf16 = G.init_serving_params(GPTConfig(**dict(TOY, dtype="bfloat16")), KEY)
    assert bf16["blocks"][1]["hc_mlp"]["phi"].dtype == jnp.float32
    assert bf16["blocks"][1]["mlp"]["router_kernel"].dtype == jnp.float32
    assert bf16["blocks"][1]["attn"]["q_a_kernel"].dtype == jnp.bfloat16


@pytest.mark.parametrize("change,named", [
    (dict(norm="layernorm", position="learned", use_bias=True, mlp_act="gelu", tie_embeddings=True,
          kv_lora_rank=0, q_lora_rank=0, num_experts=0, num_dense_layers=0, rope_scaling_factor=1.0,
          moe_gate="gshard"), "GPT-2 block"),
    (dict(layer_pattern="*E*", kv_lora_rank=0, q_lora_rank=0, num_dense_layers=0), "layer_pattern"),
    (dict(post_norms=True), "post_norms"),
    (dict(hc_sinkhorn_iters=0), "hc_sinkhorn_iters"),
])
def test_where_no_forward_holds_the_stream_it_is_refused_by_name(change, named):
    with pytest.raises(ValueError, match=named):
        GPTConfig(**dict(TOY, **change))


def test_the_training_forward_refuses_the_stream_by_name(toy):
    cfg, _, tokens, _ = toy
    stacked = jax.eval_shape(lambda: gpt.init(cfg, KEY))
    with pytest.raises(NotImplementedError, match="hc_mult"):
        jax.eval_shape(lambda p: gpt.forward(p, jnp.asarray(tokens), cfg,
                                             expert_bias=jnp.zeros((2, 16))), stacked)


def test_without_hc_mult_the_layer_is_the_one_stream_layer(toy):
    """``hc_mult`` 0 (the default) and 1 are the described block as it was:
    the tree has no maps and the forward equals the reference's ``hc_off``."""
    for n in (0, 1):
        sizes = dict(TOY, hc_mult=n)
        cfg, params = _served(sizes)
        assert not cfg.hyper_connections and "hc_attn" not in params["blocks"][0]
        tokens = np.random.default_rng(1).integers(1, 512, size=(1, 48))
        want = np.asarray(ref.logits(params, jnp.asarray(tokens), sizes, hc_off=True))
        pools = G.init_paged_pools(cfg, 5, BLOCK)
        _, last, _ = G.paged_prefill(params, jnp.asarray(tokens), jnp.int32(48), pools,
                                     jnp.arange(1, 4, dtype=jnp.int32), cfg)
        assert np.abs(np.asarray(last) - want[0, -1]).max() < F32_ROUNDINGS


# -- prefill, then decode through the latent pages -----------------------------------------------------


def _prefill_then_decode(cfg, params, tokens, lens, steps):
    """Rows of unequal length through paged_prefill then ``steps`` decode
    steps of the whole batch; -> logits at every position from each row's
    last prompt token on, [rows][steps + 1, vocab]."""
    P = 48
    pools = G.init_paged_pools(cfg, 12, BLOCK)
    tables = np.zeros((len(lens), 4), np.int32)
    out = []
    for r, n in enumerate(lens):
        tables[r] = 1 + 4 * r + np.arange(4)
        prompt = np.zeros((1, P), np.int32)
        prompt[0, :n] = tokens[r, :n]
        pools, last, counts, pairs = G.paged_prefill(
            params, jnp.asarray(prompt), jnp.int32(n), pools, jnp.asarray(tables[r, :3]), cfg,
            return_moe=True)
        assert int(pairs[0]) == n * cfg.moe_top_k * 2  # the real tokens' pairs, 2 expert layers
        out.append([np.asarray(last)])
    positions = np.array(lens, np.int32)
    step = jax.jit(lambda p, nxt, pools, tables, positions: G.paged_forward_step(
        p, nxt, pools, tables, positions, jnp.ones((len(lens),), bool), cfg))
    for i in range(steps):
        nxt = jnp.asarray([tokens[r, lens[r] + i] for r in range(len(lens))])
        lg, pools = step(params, nxt, pools, jnp.asarray(tables), jnp.asarray(positions))
        for r in range(len(lens)):
            out[r].append(np.asarray(lg[r, 0]))
        positions += 1
    return [np.stack(o) for o in out]


@pytest.mark.parametrize("copies", [4, 2])
def test_prefill_then_paged_decode_equals_the_full_forward(copies):
    """Rows of 23 and 40 tokens; 20 steps take the first over a page edge at
    32 and the second over 48; every position's logits, at the published 4
    copies and at 2 (nothing in the program knows the number)."""
    sizes = dict(TOY, hc_mult=copies)
    cfg, params = _served(sizes)
    tokens = np.random.default_rng(0).integers(1, TOY["vocab_size"], size=(2, 64))
    full = np.asarray(ref.logits(params, jnp.asarray(tokens), sizes))
    lens = [23, 40]
    got = _prefill_then_decode(cfg, params, tokens, lens, 20)
    for r, n in enumerate(lens):
        assert np.abs(got[r] - full[r, n - 1:n + 20]).max() < F32_ROUNDINGS


@pytest.mark.parametrize("control", ref.CONTROLS)
def test_every_control_of_the_reference_moves_the_logits(toy, control):
    """What the chip check's controls rest on: one stream for four, one
    Sinkhorn round for twenty, the maps in bfloat16, plain frequencies for
    YaRN's: each moves the reference's logits past this file's tolerance (by
    a hundred times it and more), so a program that took it would be seen."""
    _, params, tokens, full = toy
    moved = np.asarray(ref.logits(params, jnp.asarray(tokens), dict(TOY, control=control)))
    assert np.abs(moved - full).max() > 100 * F32_ROUNDINGS
    by_keyword = np.asarray(ref.logits(params, jnp.asarray(tokens), TOY, **{control: True}))
    assert np.array_equal(by_keyword, moved)
    with pytest.raises(ValueError, match="unknown control"):
        ref.logits(params, jnp.asarray(tokens), dict(TOY, control="no_such"))


def test_expert_load_runs_the_stream_too(toy):
    """What the routing bias's balance rule reads (``serve_arch_child``'s
    loop before warm-up): the expanded forward over the stream of copies."""
    cfg, params, tokens, _ = toy
    load = np.asarray(G.expert_load(params, jnp.asarray(tokens[:1]), cfg))
    assert load.shape == (2, 16) and load.sum() == 2 * 64 * cfg.moe_top_k


# -- the maps alone ----------------------------------------------------------------------------------


def _maps_inputs(tokens, n, width, dtype, seed=0, scale=3.0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    p = {"phi": 0.02 * jax.random.normal(k[0], (n * (2 + n), n * width)),
         "alpha": jnp.ones((3,)), "bias": jax.random.normal(k[1], (n * (2 + n),))}
    x = (scale * jax.random.normal(k[2], (tokens, n, width))).astype(dtype)
    f = jax.random.normal(k[3], (tokens, width)).astype(dtype)
    return p, x, f


def _map_cfg(n=4, width=128, **kw):
    return GPTConfig(**dict(TOY, hidden_size=width, hc_mult=n, **kw))


def test_the_maps_are_doubly_stochastic_and_inside_their_ranges():
    cfg = _map_cfg()
    p, x, _ = _maps_inputs(96, 4, 128, jnp.float32)
    maps = np.asarray(HC.hc_maps_xla(x, p, cfg))
    pre, post, res = maps[:, :4], maps[:, 4:8], maps[:, 8:].reshape(-1, 4, 4)
    assert (pre > 0).all() and (pre < 1).all() and (post > 0).all() and (post < 2).all()
    assert pre.std() > 0.1 and post.std() > 0.2  # seeded to MOVE with the stream, not static
    assert (res > 0).all()
    # twenty rounds, rows last: the rows to a rounding, the columns within 1e-5
    assert np.abs(res.sum(-1) - 1).max() < 1e-5 and np.abs(res.sum(-2) - 1).max() < 1e-5
    one = np.asarray(HC.hc_maps_xla(x, p, dataclasses.replace(cfg, hc_sinkhorn_iters=1)))
    assert np.abs(one[:, 8:].reshape(-1, 4, 4).sum(-2) - 1).max() > 1e-3  # one round is not there
    # the reference's own maps are the same numbers
    h_pre, h_post, h_res = ref.maps(x, p, dict(TOY, hidden_size=128))
    assert np.abs(np.asarray(h_pre) - pre).max() < 1e-6 and np.abs(np.asarray(h_post) - post).max() < 2e-6
    assert np.abs(np.asarray(h_res) - res).max() < 1e-6


def test_the_clamp_bounds_the_gate_before_exp():
    """A gate of 500 would overflow ``exp`` in float32 (inf / inf = nan in the
    first round); clamped to 30 it is e^30, and the rounds still end doubly
    stochastic.  A narrower clamp changes the maps: it is read."""
    cfg = _map_cfg()
    p, x, _ = _maps_inputs(32, 4, 128, jnp.float32)
    hot = dict(p, alpha=jnp.asarray([1.0, 1.0, 500.0]))
    maps = np.asarray(HC.hc_maps_xla(x, hot, cfg))
    assert np.isfinite(maps).all()
    res = maps[:, 8:].reshape(-1, 4, 4)
    assert np.abs(res.sum(-1) - 1).max() < 1e-5
    narrow = np.asarray(HC.hc_maps_xla(x, hot, dataclasses.replace(cfg, hc_res_clamp=3.0)))
    assert np.abs(narrow - maps).max() > 1e-2
    unclamped = np.asarray(HC.hc_maps_xla(x, hot, dataclasses.replace(cfg, hc_res_clamp=1e4)))
    assert not np.isfinite(unclamped).all()


@pytest.mark.parametrize("tokens,n,width,dtype", [
    pytest.param(64, 4, 256, "bfloat16", id="decode-64-rows"),
    pytest.param(256, 4, 256, "bfloat16", id="prefill-two-tiles"),
    pytest.param(128, 2, 128, "float32", id="two-copies-float32"),
])
@pytest.mark.parametrize("kernel", ["pre", "post"])
def test_each_kernel_equals_its_plain_form(kernel, tokens, n, width, dtype):
    """``pfx_hc_pre`` / ``pfx_hc_post`` (interpreted) against ``hc_pre_xla`` /
    ``hc_post_xla``: the maps to float32 roundings (another summation order
    through forty normalisations), the mixes to a rounding of the stream's
    dtype."""
    cfg = _map_cfg(n, width)
    p, x, f = _maps_inputs(tokens, n, width, jnp.dtype(dtype))
    assert HC._schedule(tokens, n, width) == ("kernel", min(tokens, 128))
    ulp = 2.0 ** -7 if dtype == "bfloat16" else 2.0 ** -22
    if kernel == "pre":
        u, maps = HC.hc_pre(x, p, cfg, impl="pallas")
        u0, maps0 = HC.hc_pre(x, p, cfg, impl="xla")
        assert maps.shape == (tokens, n * (2 + n)) and maps.dtype == jnp.float32
        assert float(jnp.abs(maps - maps0).max()) < 5e-6
        assert u.dtype == x.dtype and u.shape == (tokens, width)
        err = jnp.abs(u.astype(jnp.float32) - u0.astype(jnp.float32))
        assert float(err.max()) <= ulp * float(jnp.abs(u0.astype(jnp.float32)).max())
    else:
        maps = HC.hc_maps_xla(x, p, cfg)
        out = HC.hc_post(x, f, maps, cfg, impl="pallas")
        out0 = HC.hc_post(x, f, maps, cfg, impl="xla")
        assert out.dtype == x.dtype and out.shape == x.shape
        err = jnp.abs(out.astype(jnp.float32) - out0.astype(jnp.float32))
        assert float(err.max()) <= ulp * float(jnp.abs(out0.astype(jnp.float32)).max())


def test_the_schedule_follows_the_shapes_it_is_handed():
    assert HC._schedule(64, 4, 3584) == ("kernel", 64)  # the cell's decode step: one tile
    assert HC._schedule(2048, 4, 3584) == ("kernel", 128)  # its prefill: 16 tiles
    assert HC._schedule(2560, 4, 3584) == ("kernel", 128)  # the chip check's whole context
    assert HC._schedule(48, 4, 64)[0] == "composite"  # toy widths: no whole lane tile
    assert HC._schedule(100, 4, 128)[0] == "composite" and HC._schedule(200, 4, 128)[0] == "composite"
    assert HC._lane_chunk(3584) == 896 and HC._lane_chunk(128) == 128
    cfg = _map_cfg(4, 64)
    p, x, _ = _maps_inputs(48, 4, 64, jnp.float32)
    with pytest.raises(ValueError, match="no tile"):
        HC.hc_pre(x, p, cfg, impl="pallas")
    with pytest.raises(ValueError, match="impl"):
        HC.hc_pre(x, p, cfg, impl="mosaic")


def test_the_layer_runs_the_kernels_where_the_shapes_are_whole_tiles():
    """A layer at width 128 over 16 rows: the program's text holds the four
    kernel calls of its two sub-blocks under their scopes' names."""
    sizes = dict(TOY, hidden_size=128, num_layers=1, num_dense_layers=1, num_experts=0,
                 moe_gate="gshard", moe_experts_held=0, moe_shared_experts=0)
    cfg = GPTConfig(**sizes)
    params = jax.eval_shape(lambda: G.init_serving_params(cfg, KEY))
    pools = jax.eval_shape(lambda: G.init_paged_pools(cfg, 17, BLOCK))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    text = jax.jit(lambda p, t, pools, tables, pos, act: G.paged_forward_step(
        p, t, pools, tables, pos, act, cfg)).lower(
        params, i32(16), pools, i32(16, 1), i32(16), jax.ShapeDtypeStruct((16,), jnp.bool_)).as_text(
        debug_info=True)
    kernels = ["pfx_" + f"hc_{k}" for k in ("pre", "post")]  # kernels' names, not metrics' (lint E10)
    for name in kernels + ["pfx.hc.pre", "pfx.hc.post", "pfx.hc.out"]:
        assert name in text, name


# -- the scheduler end to end ------------------------------------------------------------------------


SERVE = {
    "Global": {"seed": 7, "local_batch_size": 1, "micro_batch_size": 1},
    "Engine": {"mix_precision": {"enable": False}, "save_load": {}},
    "Model": dict(TOY, module="GPTModule"),
    "Distributed": {},
    "Optimizer": {"name": "FusedAdamW", "lr": {"name": "Constant", "learning_rate": 1e-3}},
    "Generation": {"max_dec_len": 10, "min_dec_len": 10, "decode_strategy": "greedy_search",
                   "pad_to_multiple": 16, "eos_token_id": 0, "pad_token_id": 0},
}


@pytest.fixture(scope="module")
def server():
    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.core.serving import GenerationServer
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import AttrDict, process_configs

    cfg = process_configs(AttrDict.from_nested(SERVE), num_devices=1)
    return GenerationServer(cfg, init_dist_env(cfg, devices=jax.devices()[:1]), build_module(cfg))


def _greedy_reference(server, prompt, tokens):
    lg = np.asarray(ref.logits(server.params, jnp.asarray([prompt + tokens]), TOY))[0]
    rows = lg[len(prompt) - 1:len(prompt) - 1 + len(tokens)].copy()
    rows[:, 0] = -np.inf  # min_dec_len: the end token cannot be chosen
    return rows.argmax(-1).tolist()


def test_the_scheduler_serves_the_reference_s_greedy_tokens_and_counts_the_maps_tokens(server):
    """5 requests through GenerationServer + ContinuousScheduler with 4 rows:
    every served token is the reference's greedy choice; ``hc_tokens`` is the
    real prompt tokens plus the live rows of every committed step (once a
    forward, not times sub-blocks), on the scheduler's page under its
    registered name and among the numbers ``/admin/profile`` probes."""
    from paddlefleetx_tpu.core.continuous_batching import ContinuousScheduler, PagedDecodeEngine
    from paddlefleetx_tpu.utils import telemetry

    eng = PagedDecodeEngine(server, max_batch=4, block=BLOCK)
    assert eng.pools.v is None and eng.kv_bytes_per_token() == 3 * 40 * 4
    sched = ContinuousScheduler(eng, max_depth=16, name="xing4-test")
    sched.start()
    try:
        rng = np.random.default_rng(6)
        prompts = [rng.integers(1, 512, size=n).tolist() for n in (20, 33, 47, 17, 60)]
        base = dict(eng.stats)
        futures = [sched.submit([p], 10) for p in prompts]
        for p, f in zip(prompts, futures):
            tokens = f.result(timeout=300)[0]
            assert len(tokens) == 10 and tokens == _greedy_reference(server, p, tokens)
        d = {k: eng.stats[k] - base[k] for k in ("hc_tokens", "row_steps", "prefill_tokens")}
        assert d["prefill_tokens"] == sum(map(len, prompts))
        assert d["hc_tokens"] == d["prefill_tokens"] + d["row_steps"] > 0
        page = dict((n, v) for n, _, v in sched.collect())
        assert page["pfx_hc_tokens_total"] == float(eng.stats["hc_tokens"])
        assert "pfx_hc_tokens_total" in telemetry.METRICS
        probe = {k: v for k, v in eng.stats.items() if isinstance(v, (int, float))}
        assert probe["hc_tokens"] == eng.stats["hc_tokens"]  # what tools/serve.py's probe reads
    finally:
        assert sched.shutdown(timeout=30)


def test_a_block_with_one_stream_has_no_such_counter_on_its_page():
    from paddlefleetx_tpu.utils import telemetry

    assert telemetry.METRICS["pfx_hc_tokens_total"][0] == "counter"
    assert not GPTConfig(**dict(TOY, hc_mult=0)).hyper_connections


# -- the benchmark's new data, arithmetic and the maps' part of the chip check -------------------------


def test_the_configuration_file_states_the_cut_and_the_arithmetic_counts_the_tree():
    model = CONF["model"]
    for key, want in dict(hidden_size=3584, num_attention_heads=32, kv_lora_rank=512, q_lora_rank=768,
                          qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                          ffn_hidden_size=9216, moe_ffn_hidden_size=1024, num_experts=64,
                          moe_experts_held=64, moe_top_k=4, moe_n_group=1, moe_route_scale=2.0,
                          rope_scaling_factor=64.0, vocab_size=131072, hc_mult=4,
                          hc_sinkhorn_iters=20, hc_eps=1e-6, hc_res_clamp=30.0).items():
        assert model[key] == want, key  # every width as published, every expert and row held
    assert (CONF["hidden_size"], CONF["n_routed_experts"], CONF["vocab_size"]) == (3584, 64, 131072)
    assert (CONF["hc_mult"], CONF["mhc_h_res_clamp_min"], CONF["mhc_h_res_clamp_max"]) == (4, -30, 30)
    assert (CONF["num_hidden_layers"], CONF["first_k_dense_replace"],
            CONF["num_nextn_predict_layers"]) == (6, 1, 0)
    assert set(CONF["reduced"]) == set(CONF["reduced_keys"]) == {
        "num_hidden_layers", "first_k_dense_replace", "num_nextn_predict_layers",
        "max_position_embeddings"}
    assert not [k for k in CONF["reduced"] if k.endswith(("_dim", "_rank", "_size"))]
    math_ = _load("xing4_math", "math", "xing4.py")
    assert math_.param_count(model) == 4_792_614_912 and math_.weight_bytes(model) == 9_595_781_120
    assert math_.arena_bytes(model, 64) == 64 * 2560 * 6 * 1152
    cfg = GPTConfig(**TOY)
    tree = G.init_serving_params(cfg, KEY)
    matrices = sum(a.size for a in jax.tree.leaves(tree) if a.ndim >= 2)
    assert matrices == math_.param_count(TOY)  # the arithmetic counts the program's tree
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(
        G.init_serving_params(GPTConfig(**dict(TOY, dtype="bfloat16")), KEY)) if a.ndim >= 2)
    assert held == math_.weight_bytes(TOY)  # and its bytes: routers and maps float32
    # a PREFILL's token and forward: 12 calls of each kernel, the stream read once (and written
    # once); a decode step's rows (1.8 MB of stream a call) are counted no HBM bytes
    pre, post = math_.hc_pre_work(model, 1.0), math_.hc_post_work(model, 1.0)
    assert pre["bytes"] == 12 * 4 * 3584 * 2 and post["bytes"] == 2 * pre["bytes"]
    assert math_.hc_pre_work(model, 10.0, 4.0)["bytes"] == 6 * pre["bytes"]
    assert math_.hc_post_work(model, 3.0, 3.0)["bytes"] == 0 < math_.hc_post_work(model, 3.0, 3.0)["flops"]
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    for work in (pre, post, math_.mla_decode_work(model, 1.0, 0.0)):  # all bound by their bytes
        assert math_.roofline_seconds(work, peaks) == work["bytes"] / 819e9


def test_the_new_metrics_read_the_new_counter_and_kernels():
    import sys

    sys.path.insert(0, BENCH)
    try:
        reader = _load("kernel_roofline_at", "readers", "kernel_roofline_at.py")
        share = _load("kernel_share", "readers", "kernel_share.py")
    finally:
        sys.path.remove(BENCH)
    with open(os.path.join(BENCH, "workloads", "serve-xing4-29b-6of40-rag.json")) as f:
        cell = json.load(f)
    for which in ("pre", "post"):
        with open(os.path.join(BENCH, "layer_metrics", f"kernels.hc_{which}_roofline.json")) as f:
            d = json.load(f)
        kernel = "pfx_" + f"hc_{which}"  # a kernel's name, not a metric's (lint E10)
        assert d["args"] == {"kernel": kernel, "work": f"hc_{which}_work", "tokens_key": "hc_tokens"}
        assert d["layer"] == "residual path" and d["name"] in cell["per_layer"]
        ctx = {"math": CONF["math"], "model": CONF["model"],
               "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
               "profile_counters": [{"hc_tokens": 0, "row_steps": 0},
                                    {"hc_tokens": 50_000, "row_steps": 12_000}],
               "kernel_self_s": {kernel: 0.1}, "trace": {"busy_s": 2.0}}
        got = reader.read(ctx, **d["args"])
        assert 10 < got < 100  # 38,000 prefill tokens' stream over 0.1 s of kernel time
        # a parent commit: no such counter, no such kernel -> nothing, and no error
        assert reader.read(dict(ctx, profile_counters=[{"row_steps": 0}, {"row_steps": 9}]),
                           **d["args"]) is None
        assert reader.read(dict(ctx, kernel_self_s={}), **d["args"]) is None
        assert share.read(ctx, kernel=kernel) == pytest.approx(5.0)
        assert share.read(dict(ctx, kernel_self_s={}), kernel=kernel) is None


@pytest.mark.parametrize("control,ok", [("", True), ("yarn_off", True), ("hc_off", True),
                                        ("maps_bf16", False), ("sinkhorn_1", False)])
def test_the_maps_part_reads_the_maps_precision_and_nothing_before_it(control, ok):
    """``serve_hc_child.hc_verdict`` (what the chip check judges beside the
    served tokens): the reference's float32 stream at the input of the second
    layer through the program's ``hc_pre`` and through the reference's maps.
    In float32 the two agree to roundings, far under the limit the
    configuration's file enters; the reference with its maps in bfloat16 or
    with one Sinkhorn round misses it by orders of magnitude; a control that
    moves other things (the attention's frequencies; the stream's number,
    which this part pins to the configuration's) moves nothing here."""
    child = _load("serve_hc_child", "runners", "serve_hc_child.py")
    cfg, params = _served(TOY)
    rng = np.random.default_rng(0)
    served = [{"prompt_ids": rng.integers(1, 512, size=40).tolist(),
               "tokens": rng.integers(1, 512, size=n).tolist()} for n in (30, 60)]
    limit = float(CONF["reference_limits"]["hc_map_err_max"])
    got = child.hc_verdict(SimpleNamespace(module=SimpleNamespace(config=cfg), params=params),
                           served, dict(TOY, control=control), CONF, limit)
    assert got["tokens"] == 170 and got["ok"] is ok, got
    if ok:
        assert got["h_res_err_median"] < limit / 10
    else:
        assert got["h_res_err_median"] > limit * 10


def test_the_hc_child_finds_what_it_reads_in_the_arch_child():
    """``serve_hc_child.py`` runs ``serve_arch_child.py`` from its file and
    reads or sets the names in ``NAMES`` there: the file has to keep them, and
    the one ``serve.main`` call the child wraps.  A program whose ``GPTConfig``
    lacks a field of the configuration (a parent commit) is named."""
    child = _load("serve_hc_child", "runners", "serve_hc_child.py")
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
    assert set(child.reference_controls(CONF)) == set(ref.CONTROLS) == {
        "hc_off", "sinkhorn_1", "maps_bf16", "yarn_off"}
    assert {"band_in_spreads", "past_band_share_max", "argmax_agree_min",
            "hc_map_err_max"} <= set(CONF["reference_limits"])
    assert child.program_fields(CONF["model"]) == [] == child.program_fields(CONF["rehearse_model"])
    assert child.program_fields(dict(CONF["model"], hc_streams=4)) == ["hc_streams"]
