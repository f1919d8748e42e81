#!/usr/bin/env python3
"""Ask the TPU compiler, without a chip, whether the cells' real programs
compile for a described v5e:2x2 (on-chip-measurement guide, section 2).

    JAX_PLATFORMS=cpu python pfx_bench/selftest/chip_compile.py serve gpt-1.3b 32 64,256,960
    JAX_PLATFORMS=cpu python pfx_bench/selftest/chip_compile.py train train-1.3b-mp4

Nothing runs and nothing is timed: a compile that passes is not a chip run.
By hand only; not collected by tier-1 (one process may hold libtpu)."""

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import common  # noqa: E402


def _setup():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    from paddlefleetx_tpu.utils import device as device_mod

    device_mod.pallas_interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


def _mem(c):
    m = c.memory_analysis()
    return {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes") if hasattr(m, k)}


def serve(config_name, rows, buckets):
    from paddlefleetx_tpu.models.gpt import model as gpt
    from paddlefleetx_tpu.models.gpt.config import GPTConfig
    from paddlefleetx_tpu.models.gpt.generation import (
        init_paged_pools, paged_forward_step, paged_prefill)

    topo = _setup()
    one = SingleDeviceSharding(topo.devices[0])
    m = common.load_config(config_name)["model"]
    cfg = GPTConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_layers=m["num_layers"], num_attention_heads=m["num_attention_heads"],
        ffn_hidden_size=m["ffn_hidden_size"],
        max_position_embeddings=m["max_position_embeddings"],
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0, dtype="bfloat16")

    def shp(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree)

    params = shp(jax.eval_shape(lambda k: gpt.init(cfg, k), jax.random.key(0)))
    bs = 16
    ctx_len = m["max_position_embeddings"]
    nb = rows * (ctx_len // bs) + 1
    pools = shp(jax.eval_shape(lambda: init_paged_pools(cfg, nb, bs, kv_dtype="bf16")))
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)  # noqa: E731
    out = {}
    for P_ in buckets:
        t0 = time.time()
        c = jax.jit(lambda p, prompt, plen, pools, row: paged_prefill(
            p, prompt, plen, pools, row, cfg)).lower(
            params, sds((1, P_), jnp.int32), sds((), jnp.int32), pools,
            sds((P_ // bs,), jnp.int32)).compile()
        out[f"prefill_{P_}"] = {"s": round(time.time() - t0, 1), **_mem(c),
                                "kernel": "tpu_custom_call" in c.as_text()}
        print(json.dumps({f"prefill_{P_}": out[f"prefill_{P_}"]}), flush=True)
    t0 = time.time()
    c = jax.jit(lambda p, toks, pools, tb, ps, act: paged_forward_step(
        p, toks, pools, tb, ps, act, cfg)).lower(
        params, sds((rows,), jnp.int32), pools, sds((rows, ctx_len // bs), jnp.int32),
        sds((rows,), jnp.int32), sds((rows,), jnp.bool_)).compile()
    out["step"] = {"s": round(time.time() - t0, 1), **_mem(c),
                   "kernel": "tpu_custom_call" in c.as_text()}
    print(json.dumps({"step": out["step"]}), flush=True)
    return out


def train(cell_name):
    from paddlefleetx_tpu.core.engine import Engine
    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import get_config

    topo = _setup()
    cell = common.load_cell(cell_name)
    n = int(cell["chips"])
    overrides = common.train_overrides(cell, seed=1, rehearse=False)
    cfg = get_config(os.path.join(ROOT, cell["config_data"]["yaml"]),
                     overrides=overrides, num_devices=n)
    mesh = init_dist_env(cfg, devices=topo.devices[:n])
    t0 = time.time()
    with mesh:
        engine = Engine(cfg, build_module(cfg), mesh, abstract_init=True)
        b = int(cfg.Global.global_batch_size)
        s = int(cfg.Data.Train.dataset.max_seq_len)
        batch = {
            name: jax.ShapeDtypeStruct((b, s), dt, sharding=engine.batch_spec)
            for name, dt in (("tokens", np.int64), ("labels", np.int64),
                             ("loss_mask", np.float32), ("position_ids", np.int64))
        }
        c = engine._train_step.lower(engine.state, batch).compile()
    text = c.as_text()
    out = {"s": round(time.time() - t0, 1), **_mem(c),
           "kernel": "tpu_custom_call" in text,
           "collectives": {k: text.count(k + "(") + text.count(k + "-start(") for k in (
               "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
               "all-to-all")}}
    print(json.dumps({cell_name: out}), flush=True)
    # the reference check's backward program (system and reference gradient
    # of one sequence in one program): does it fit beside the train state?
    from runners.train import _load_reference, backward_fn

    t0 = time.time()
    with mesh:
        one = {name: jax.ShapeDtypeStruct((1, s), dt, sharding=engine.replicated)
               for name, dt in (("tokens", jnp.int32), ("labels", jnp.int32),
                                ("mask", jnp.float32))}
        c = jax.jit(backward_fn(engine.module, engine.ctx, _load_reference())).lower(
            engine.state.params, one["tokens"], one["labels"], one["mask"]).compile()
    check = {"s": round(time.time() - t0, 1), **_mem(c)}
    print(json.dumps({cell_name + ":reference_backward": check}), flush=True)
    out["reference_backward"] = check
    return out


if __name__ == "__main__":
    if sys.argv[1] == "serve":
        serve(sys.argv[2], int(sys.argv[3]), [int(x) for x in sys.argv[4].split(",")])
    else:
        train(sys.argv[2])
