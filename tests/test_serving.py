"""GenerationServer + tools/serve.py HTTP endpoint (reference deploy-path
parity: InferenceEngine predictor, inference_engine.py:104)."""

import json
import os
import sys
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_OVERRIDES = {
    "Global": {"global_batch_size": 8, "seed": 3},
    "Engine": {"mix_precision": {"enable": False}, "save_load": {"save_steps": 0}},
    "Model": {
        "module": "GPTModule",
        "vocab_size": 96,
        "hidden_size": 32,
        "num_layers": 2,
        "num_attention_heads": 4,
        "max_position_embeddings": 128,
        "dtype": "float32",
    },
    "Distributed": {"mp_degree": 2},
    "Optimizer": {"name": "FusedAdamW", "lr": {"name": "Constant", "learning_rate": 1e-3}},
    "Generation": {"max_dec_len": 8, "decode_strategy": "greedy_search", "pad_to_multiple": 16,
                   "eos_token_id": 95, "pad_token_id": 0},
}


@pytest.fixture(scope="module")
def server():
    import jax

    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.core.serving import GenerationServer
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import AttrDict, process_configs

    cfg = AttrDict.from_nested(TINY_OVERRIDES)
    cfg = process_configs(cfg, num_devices=jax.device_count())
    mesh = init_dist_env(cfg)
    module = build_module(cfg)
    return GenerationServer(cfg, mesh, module)


def test_generate_ids_bucket_reuse(server):
    outs = server.generate_ids([[1, 2, 3]])
    assert len(outs) == 1 and 0 < len(outs[0]) <= 8
    # different prompt length, same bucket -> no growth in stats weirdness,
    # deterministic greedy output for identical prompt
    a = server.generate_ids([[4, 5, 6, 7, 8]])
    b = server.generate_ids([[4, 5, 6, 7, 8]])
    assert a == b
    assert server.stats["requests"] == 3


def test_generate_ids_batch_and_maxlen(server):
    outs = server.generate_ids([[1, 2], [3, 4, 5, 6]], max_dec_len=4)
    assert len(outs) == 2
    assert all(len(o) <= 4 for o in outs)


@pytest.mark.slow
def test_http_endpoint(tmp_path):
    """tools/serve.py end-to-end over HTTP with prompt_ids."""
    import socket
    import subprocess
    import time

    import yaml

    cfg_path = tmp_path / "tiny.yaml"
    cfg_path.write_text(yaml.safe_dump(TINY_OVERRIDES))
    with socket.socket() as s:
        s.bind(("", 0))
        port = s.getsockname()[1]

    env = dict(os.environ)
    env["XLA_FLAGS"] = env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    env["PFX_PLATFORM"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tools", "serve.py"),
         "-c", str(cfg_path), "--port", str(port), "--no-warmup"],
        env=env, cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    try:
        deadline = time.time() + 300
        last = None
        while time.time() < deadline:
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz", timeout=5
                ) as r:
                    last = json.load(r)
                    break
            except Exception as e:
                last = e
                if proc.poll() is not None:
                    raise AssertionError(f"server died: {proc.stdout.read()[-2000:]}")
                time.sleep(2)
        assert isinstance(last, dict) and last.get("ok"), last
        # operability fields: queue depth + latency + retrace counter
        assert {"in_flight", "last_latency_s", "traces"} <= set(last), last

        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps({"prompt_ids": [1, 2, 3], "max_tokens": 4}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=300) as r:
            out = json.load(r)
        assert "completion_ids" in out and len(out["completion_ids"]) <= 4, out

        # batched ids request
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps(
                {"prompts_ids": [[1, 2], [3, 4, 5]], "max_tokens": 4}
            ).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=300) as r:
            out = json.load(r)
        assert len(out["completions_ids"]) == 2, out

        # bad request -> 400, server keeps serving
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps({"prompt_ids": []}).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            urllib.request.urlopen(req, timeout=60)
            raise AssertionError("expected HTTP 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400
    finally:
        proc.terminate()
        proc.wait(timeout=10)


def test_max_tokens_clamped_and_bucketed(server):
    """Client max_dec_len is clamped to the model context and bucketed so
    the jit-cache cardinality stays bounded."""
    outs = server.generate_ids([[1, 2]], max_dec_len=10**9)
    assert len(outs[0]) <= server.module.config.max_position_embeddings
    server.generate_ids([[1, 2]], max_dec_len=3)
    before = len(server._compiled)
    server.generate_ids([[1, 2]], max_dec_len=7)   # same 32-bucket: no new compile
    assert len(server._compiled) == before
    outs = server.generate_ids([[1, 2]], max_dec_len=3)
    assert len(outs[0]) <= 3


def test_empty_prompt_rejected(server):
    with pytest.raises(ValueError, match="non-empty"):
        server.generate_ids([])
    with pytest.raises(ValueError, match="non-empty"):
        server.generate_ids([[]])


def test_mixed_traffic_never_retraces_a_seen_bucket(server):
    """The per-(bucket_b, bucket_len, GenerationConfig) jit memo: repeated
    mixed-size traffic must stop tracing once each bucket has been seen —
    stats["traces"] counts trace-time entries of the decode fn."""
    reqs = [
        [[1, 2, 3]],                      # batch bucket 1, prompt bucket 16
        [[4, 5], [6, 7, 8], [9, 1]],      # batch bucket 4 (padded)
        [list(range(1, 20))],             # prompt bucket 32
    ]
    for r in reqs:  # populate every bucket
        server.generate_ids(r)
    seen = server.stats["traces"]
    assert seen >= len(reqs) - 1  # at least one trace per distinct bucket
    for _ in range(3):  # repeat traffic: NO new traces allowed
        for r in reqs:
            server.generate_ids(r)
    assert server.stats["traces"] == seen


def test_decode_cache_is_donated(server):
    """The jitted decode consumes the per-request KV cache buffer: the
    compiled fn reports the cache args as donated (in-place update, no
    per-step copy of the [layers,b,heads,max_len,dim] pair)."""
    server.generate_ids([[1, 2, 3]])
    gen_key = next(iter(server._compiled))
    fn = server._compiled[gen_key]
    import jax as _jax
    import jax.numpy as _jnp

    from paddlefleetx_tpu.models.gpt.generation import init_cache

    cfg = server.module.config
    prompt = _jnp.zeros((gen_key[1], gen_key[2]), _jnp.int32)
    lens = _jnp.ones((gen_key[1],), _jnp.int32)
    cache = init_cache(cfg, gen_key[1], gen_key[2] + gen_key[0].max_dec_len)
    lowered = fn.lower(
        server.params, prompt, lens, _jax.random.key(0), cache
    )
    donated = lowered.args_info  # pytree of ArgInfo with .donated
    flags = [a.donated for a in _jax.tree.leaves(donated)]
    assert sum(flags) == 2, flags  # exactly the cache k/v pair


def test_stats_expose_last_latency_and_traces(server):
    """/healthz operability fields: last-request latency and the retrace
    counter ride server.stats (tools/serve.py spreads them into the
    health payload)."""
    server.generate_ids([[1, 2, 3]])
    assert server.stats["last_latency_s"] > 0
    assert server.stats["traces"] >= 1
    assert {"requests", "tokens_out", "time_s"} <= set(server.stats)


def test_clamp_max_tokens():
    """Per-request max_tokens clamp (tools/serve.py): cap wins over both a
    huge client value and an over-cap configured default; floor at 1."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from serve import clamp_max_tokens

    assert clamp_max_tokens(None, 64, 0) == 64       # no cap: default
    assert clamp_max_tokens(10**9, 64, 128) == 128   # cap beats client
    assert clamp_max_tokens(None, 512, 128) == 128   # cap beats default
    assert clamp_max_tokens(16, 64, 128) == 16       # sane value untouched
    assert clamp_max_tokens(0, 64, 128) == 1         # floored
    with pytest.raises((ValueError, TypeError)):
        clamp_max_tokens("lots", 64, 128)


def test_coalescing_parity_with_sequential(server):
    """The acceptance drill, in-process: N single-prompt greedy requests
    coalesced into one batched decode are token-for-token identical to
    serving them sequentially, and repeated coalesced traffic adds ZERO
    retraces (the batch rides the existing power-of-two bucketing)."""
    from paddlefleetx_tpu.core.request_queue import RequestQueue

    prompts = [[7, 8, 9], [1, 2], [3, 4, 5, 6], [2, 9]]
    seq = [server.generate_ids([p], max_dec_len=6)[0] for p in prompts]

    def runner(ps, mx):
        return server.generate_ids(ps, max_dec_len=mx)

    q = RequestQueue(runner, max_depth=8, max_coalesce=4)
    futs = [q.submit([p], 6, coalesce_key=("parity",)) for p in prompts]
    q.start()  # submitted first: one scan coalesces all four
    got = [f.result(timeout=300)[0] for f in futs]
    assert got == seq
    assert q.stats["coalesced_batches"] == 1
    assert q.stats["coalesced_requests"] == len(prompts)
    q.shutdown(timeout=10)

    # repeat coalesced traffic: no new traces — the coalesced batch hits
    # an already-compiled (bucket_b, bucket_len) artifact
    before = server.stats["traces"]
    q2 = RequestQueue(runner, max_depth=8, max_coalesce=4)
    futs = [q2.submit([p], 6, coalesce_key=("parity",)) for p in prompts]
    q2.start()
    got2 = [f.result(timeout=300)[0] for f in futs]
    assert got2 == seq
    assert server.stats["traces"] == before
    q2.shutdown(timeout=10)


def test_warmup_buckets_and_stats(server):
    """warmup accepts a list of prompt-length buckets, reports per-bucket
    compile seconds in stats, and validates loudly up front."""
    per = server.warmup([4, 20])
    assert set(per) == {"4", "20"}
    assert server.stats["warmup_s"] == per
    assert all(v >= 0 for v in per.values())
    assert "4" in server.warmup(4)  # old warmup(prompt_len) shape
    with pytest.raises(ValueError, match="decode room"):
        server.warmup([10**6])
    with pytest.raises(ValueError, match="batch size"):
        server.warmup([4], batch_sizes=[0])
    with pytest.raises(ValueError, match=">= 1"):
        server.warmup([])


def test_warmup_fails_loudly_not_half_warmed(server, monkeypatch):
    """A bucket that cannot compile raises naming what did and did not
    warm, instead of leaving a silently half-warmed server."""
    from paddlefleetx_tpu.utils import resilience

    resilience.reset_fault_state()
    monkeypatch.setenv(
        "PFX_FAULT", f"gen_crash:{int(server.stats['requests']) + 1}"
    )
    with pytest.raises(RuntimeError, match="warmup failed at bucket"):
        server.warmup([4])
    monkeypatch.delenv("PFX_FAULT")
    resilience.reset_fault_state()
    server.warmup([4])  # recovers cleanly


def test_gen_error_does_not_poison_cache_pool(server, monkeypatch):
    """A generation failure after the donated cache was popped must drop
    the (possibly donation-invalidated) pair — not return it to the pool
    — and record structured gen_error stats for /healthz."""
    from paddlefleetx_tpu.utils import resilience

    prompt = [[5, 6, 7]]
    before_rows = server.generate_ids(prompt, max_dec_len=5)
    bucket_key = next(reversed(server._cache_pool))  # MRU = this bucket
    errs0 = server.stats["gen_errors"]

    resilience.reset_fault_state()
    monkeypatch.setenv(
        "PFX_FAULT", f"gen_crash:{int(server.stats['requests']) + 1}"
    )
    with pytest.raises(RuntimeError, match="injected gen_crash"):
        server.generate_ids(prompt, max_dec_len=5)
    monkeypatch.delenv("PFX_FAULT")
    resilience.reset_fault_state()

    assert server.stats["gen_errors"] == errs0 + 1
    assert "gen_crash" in server.stats["last_error"]
    # the bucket was dropped, not left pointing at a donated pair
    assert bucket_key not in server._cache_pool
    # and the pool recovers: same bucket serves again, token-identical
    assert server.generate_ids(prompt, max_dec_len=5) == before_rows


def test_cache_pool_is_lru_bounded(server):
    """Each pooled cache pins a device k/v pair; mixed traffic across
    many buckets must not retain more than Generation.cache_pool_size
    pairs (LRU eviction, default 4)."""
    for dec in (3, 2, 1):  # distinct gen configs -> distinct bucket keys
        for prompt in ([[1, 2]], [[1, 2], [3, 4], [5, 6]]):
            server.generate_ids(prompt, max_dec_len=dec)
    assert len(server._cache_pool) <= server._cache_pool_size


# ---------------------------------------------------------------------------
# The server holds its weights in the compute dtype (cast once when built,
# not inside every decode step), LayerNorm leaves float32, same tokens
# ---------------------------------------------------------------------------

LAYOUTS = {"one_device": (1, {}), "mp2": (2, {"mp_degree": 2})}


def _bf16_module(layout, save_load=None):
    """(cfg, mesh, module) of the tiny model with ``dtype: bfloat16``."""
    import jax

    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import AttrDict, process_configs

    n, dist = LAYOUTS[layout]
    over = dict(TINY_OVERRIDES, Distributed=dist,
                Model=dict(TINY_OVERRIDES["Model"], dtype="bfloat16"))
    if save_load:
        over["Engine"] = dict(
            over["Engine"], save_load=dict(over["Engine"]["save_load"], **save_load))
    cfg = process_configs(AttrDict.from_nested(over), num_devices=n)
    return cfg, init_dist_env(cfg, devices=jax.devices()[:n]), build_module(cfg)


def _bf16_server(layout, keep_float32=False):
    """A tiny ``dtype: bfloat16`` server on ``layout``; ``keep_float32``
    builds it as the parent did (the float32 tree kept, every forward
    casting at its point of use)."""
    import jax

    from paddlefleetx_tpu.core import serving

    cfg, mesh, module = _bf16_module(layout)
    # every leaf perturbed: fresh biases (0) and scales (1) survive any cast
    leaves, treedef = jax.tree.flatten(module.init_params(jax.random.key(11)))
    keys = jax.random.split(jax.random.key(12), len(leaves))
    params = treedef.unflatten([
        x + 0.02 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)
    ])
    with pytest.MonkeyPatch.context() as mp:
        if keep_float32:
            mp.setattr(serving, "serving_params", lambda p, cfg: p)
        return serving.GenerationServer(cfg, mesh, module, params=params)


@pytest.fixture(scope="module", params=list(LAYOUTS))
def bf16_pair(request):
    return _bf16_server(request.param), _bf16_server(request.param, True)


def _leaf_dtypes(tree):
    import jax

    return {
        "/".join(str(k.key) for k in path): str(leaf.dtype)
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def test_server_holds_compute_dtype_weights_and_float32_layernorm(bf16_pair):
    cast, kept = bf16_pair
    assert set(_leaf_dtypes(kept.params).values()) == {"float32"}
    dtypes = _leaf_dtypes(cast.params)
    assert {p for p, d in dtypes.items() if d == "float32"} == {
        f"{g}/{n}" for g in ("layers/ln_1", "layers/ln_2", "final_ln")
        for n in ("scale", "bias")
    }
    assert set(dtypes.values()) == {"float32", "bfloat16"}
    # the cast kept each leaf where the placement put it
    import jax

    for a, b in zip(jax.tree.leaves(cast.params), jax.tree.leaves(kept.params)):
        assert a.sharding.is_equivalent_to(b.sharding, a.ndim)


def _serve(server, scheduler, prompts, max_new):
    if scheduler == "coalesce":
        return [server.generate_ids([p], max_dec_len=max_new)[0] for p in prompts]
    from paddlefleetx_tpu.core.continuous_batching import PagedDecodeEngine

    eng = PagedDecodeEngine(server, max_batch=4)
    slots = [eng.admit(p, max_new) for p in prompts[:2]]
    eng.step()
    slots += [eng.admit(p, max_new) for p in prompts[2:]]  # mid-decode
    for _ in range(64):
        eng.step()
        if not eng.active.any():
            break
    assert not eng.active.any()
    return [eng.slots[s].tokens for s in slots]


@pytest.mark.parametrize("scheduler", ["coalesce", "continuous"])
def test_greedy_tokens_equal_a_server_that_keeps_the_float32_tree(
    bf16_pair, scheduler
):
    cast, kept = bf16_pair
    prompts = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 10], [11, 12, 13, 14]]
    want = _serve(kept, scheduler, prompts, 6)
    assert all(len(t) == 6 for t in want)
    assert _serve(cast, scheduler, prompts, 6) == want


def _weight_converts(jaxpr, params=None):
    """convert_element_type equations, at any depth, whose operand is a
    parameter of two or more dimensions: an input of the traced function
    (which takes the tree alone) or, inside the layer scan, the layer's
    slice of one."""
    import jax

    # by identity: a jaxpr's literals are not hashable
    params = {id(v) for v in jaxpr.invars} if params is None else params
    found = []
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "convert_element_type"
                and id(eqn.invars[0]) in params
                and eqn.invars[0].aval.ndim >= 2):
            found.append(
                (eqn.invars[0].aval.str_short(), str(eqn.params["new_dtype"])))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            if len(sub.invars) == len(eqn.invars):  # scan, pjit: one to one
                found += _weight_converts(sub, {
                    id(inner) for inner, outer in zip(sub.invars, eqn.invars)
                    if id(outer) in params
                })
    return found


def _decode_step_jaxpr(server, scheduler, params):
    import jax
    import jax.numpy as jnp

    from paddlefleetx_tpu.models.gpt import generation as G

    mcfg = server.module.config
    if scheduler == "coalesce":
        cache = G.init_cache(mcfg, 2, 32)
        return jax.make_jaxpr(lambda p: G.forward_cached(
            p, jnp.zeros((2, 1), jnp.int32), cache, jnp.int32(16), mcfg,
            server.ctx))(params)
    B, v = 2, mcfg.vocab_size
    pools = G.init_paged_pools(mcfg, 9, 8)
    rows = G.PagedRows(
        jnp.zeros((B, v), jnp.float32), jnp.zeros((B, v), jnp.int32),
        jnp.full((B,), 5, jnp.int32), jnp.zeros((B,), jnp.int32),
        jnp.full((B,), 8, jnp.int32), jnp.ones((B,), bool),
        jnp.full((B,), 7, jnp.int32),
    )
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    return jax.make_jaxpr(lambda p: G.decode_step(
        p, pools, tables, rows, mcfg, server.gen, ctx=server.ctx))(params)


@pytest.mark.parametrize("scheduler", ["coalesce", "continuous"])
def test_decode_step_converts_no_weight_of_the_held_tree(bf16_pair, scheduler):
    """The guard for a weight added to a forward and not to
    ``COMPUTE_DTYPE_LEAVES``: it would be converted inside every step."""
    cast, kept = bf16_pair
    with cast.mesh:
        assert _weight_converts(
            _decode_step_jaxpr(cast, scheduler, cast.params).jaxpr) == []
        # the probe sees what the parent did: the two tables, a layer's
        # four matrices and its one bias of more than one dimension
        seen = _weight_converts(
            _decode_step_jaxpr(kept, scheduler, kept.params).jaxpr)
    assert len(seen) == 7 and {d for _, d in seen} == {"bfloat16"}


def test_metrics_show_the_held_trees_bytes_by_dtype():
    """``GET /metrics`` is ``get_registry().render_prometheus()``."""
    import jax

    from paddlefleetx_tpu.utils.telemetry import get_registry

    server = _bf16_server("one_device")
    want = {}
    for leaf in jax.tree.leaves(server.params):
        want[str(leaf.dtype)] = want.get(str(leaf.dtype), 0) + leaf.nbytes
    assert want["float32"] < want["bfloat16"]
    lines = get_registry().render_prometheus().splitlines()
    for name, nbytes in want.items():
        row = [ln for ln in lines
               if ln.startswith(f'pfx_serving_params_bytes{{dtype="{name}"}}')]
        assert len(row) == 1 and float(row[0].split()[-1]) == nbytes, row


def test_server_built_without_params_restores_the_configured_checkpoint(tmp_path):
    """The server owns the tree it casts: with no ``params=`` it restores
    ``Engine.save_load.ckpt_dir`` itself (``tools/serve.py`` passes none)."""
    import jax
    import numpy as np

    from paddlefleetx_tpu.core.serving import GenerationServer
    from paddlefleetx_tpu.utils.checkpoint import save_params_checkpoint

    cfg, mesh, module = _bf16_module(
        "one_device", save_load={"ckpt_dir": str(tmp_path / "ckpt")})
    saved = module.init_params(jax.random.key(21))
    save_params_checkpoint(str(tmp_path / "ckpt"), saved, "test", {})
    server = GenerationServer(cfg, mesh, module)
    for got, want in zip(jax.tree.leaves(server.params), jax.tree.leaves(saved)):
        np.testing.assert_array_equal(
            np.asarray(got.astype("float32")),
            np.asarray(want.astype(got.dtype).astype("float32")))
    assert str(server.params["layers"]["mlp"]["fc_in_kernel"].dtype) == "bfloat16"
