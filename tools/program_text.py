#!/usr/bin/env python3
"""The program text of the benchmark's cells, as hashes.

A change that means to leave a cell's programs alone can show it: each
program below is LOWERED (StableHLO, shapes only, nothing runs or compiles)
for a described ``v5e:2x2`` chip at the widths of its configuration's file,
and the text is hashed.  Two trees that give the same hashes hand the TPU's
compiler the same programs.

    python tools/program_text.py                      # print the hashes of this tree
    python tools/program_text.py --root <other tree>  # of another checkout (a parent commit)
    python tools/program_text.py --write              # rewrite tests/program_text.json and tests/kernel_bodies.json
    python tools/program_text.py --check              # compare with both; exit 1 on a difference
    python tools/program_text.py --bodies             # print the kernels' hashes instead of the programs'

``tests/test_program_text.py`` runs the check in tier-1.  A PR that MEANS to
change one of these programs rewrites the files and says so; a PR that does
not (a new block family beside them) leaves them as the parent has them,
which is the proof.

One thing is masked before a program is hashed: the serialized body of each
Mosaic kernel (``tpu_custom_call``'s ``body``), because it embeds the line
numbers of the Python frames that called it, which move with any edit above
them.  ``tests/program_text.json`` therefore sees a kernel's operands,
shapes, grid-independent attributes and everything XLA gets around it, and
NOT what the kernel computes.  ``tests/kernel_bodies.json`` (PR 56) keeps
that: for each program its different kernels in the order it first calls
them, ``<kernel name>:<hash of its Mosaic module printed without
locations>``.  An edit that moves lines leaves those hashes; one that
changes a kernel's operations, their order or its tile does not (PR 56's
first draft re-ordered three operations of ``pfx_flash_fwd`` while sharing
its loop with a second kernel: seven programs' kernels moved under
unchanged program hashes).  Two trees that agree in BOTH files hand the
TPU's compiler the same programs and Mosaic the same kernels.
"""

import argparse
import base64
import hashlib
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
GOLDEN = os.path.join(os.path.dirname(HERE), "tests", "program_text.json")
KERNELS = os.path.join(os.path.dirname(HERE), "tests", "kernel_bodies.json")  # what GOLDEN's hashes mask
BENCH = "pfx_bench"  # noqa: E10 — a directory, not a metric
# configuration -> (batch slots, arena pages, prefill bucket, min_dec_len): one decode step
# and one prefill program each, shaped like the cell's (falcon-h1-34b's were written by the PR
# that added it, 40; the four before it from PR 38's commit, and PR 40 left them as they were;
# mellum2-12b-a2.5b's and the 345M train step from PR 44's commit, by PR 45; xing4.0-29b-a4b's by
# the PR that added it, 55, which left every other as it was)
SERVING = {
    "gpt-1.3b": (8, 8 * 8 + 1, 512, 32),
    "deepseek-v3": (64, 64 * 36 + 1, 1024, 1536),
    "nemotron-3-nano": (48, 48 * 14 + 1, 256, 768),
    "falcon-h1-34b": (64, 64 * 6 + 1, 256, 512),
    "mellum2-12b-a2.5b": (48, 48 * 22 + 1, 2048, 768),
    "xing4.0-29b-a4b": (64, 64 * 20 + 1, 2048, 512),
}
RING = 9  # pages a row in a window layer's ring (mellum2-12b-a2.5b: window 1,024 over pages of 128)
# configuration -> the batch of its training cell (the sequence length is the recipe's own)
TRAINING = {"trinity-mini": 2, "gpt-345m": 16}
# the order of tests/program_text.json: PR 40's nine, then PR 45's three, then PR 55's two
PROGRAMS = (tuple(f"{c}.{p}" for c in list(SERVING)[:4] for p in ("step", "prefill")) + ("trinity-mini.train_step",)
            + tuple(f"mellum2-12b-a2.5b.{p}" for p in ("step", "prefill")) + ("gpt-345m.train_step",)
            + tuple(f"xing4.0-29b-a4b.{p}" for p in ("step", "prefill")))
_BODY = re.compile(r'\\22body\\22: \\22[^\\]*\\22')
_KERNEL = re.compile(r'\\22body\\22: \\22([^\\]*)\\22.*?kernel_name = "([^"]*)"')


def digest(lowered) -> str:
    return hashlib.sha256(_BODY.sub("BODY", lowered.as_text()).encode()).hexdigest()[:20]


def kernel_bodies(lowered) -> list:
    """``<kernel name>:<hash>`` of the Mosaic kernels of a lowered program,
    each different one once, in the order the program first calls them: the
    hash is of the kernel's module (the bytecode that ``digest`` masks)
    printed WITHOUT locations, so an edit that only moves lines leaves it
    and an edit to what the kernel computes, or to the order it computes it
    in, does not."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    out = []
    for body, name in _KERNEL.findall(lowered.as_text()):
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True  # the body is serialized as ``stable_mosaic``
        with ctx:
            asm = ir.Module.parse(base64.b64decode(body)).operation.get_asm(enable_debug_info=False)
        out.append(f"{name}:{hashlib.sha256(asm.encode()).hexdigest()[:20]}")
    return list(dict.fromkeys(out))


def lowered(root: str, names=PROGRAMS):
    """(program name, its ``jax.stages.Lowered``) for each of ``names``, of the
    tree at ``root`` (imported from there)."""
    sys.path.insert(0, root)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from paddlefleetx_tpu.models.gpt import generation as G
    from paddlefleetx_tpu.models.gpt.config import GPTConfig
    from paddlefleetx_tpu.utils import device as device_mod

    if not os.path.abspath(G.__file__).startswith(os.path.abspath(root) + os.sep):
        raise SystemExit(f"paddlefleetx_tpu came from {G.__file__}, not from {root}")
    device_mod.pallas_interpret = lambda: False  # the chip's kernels, not interpret mode
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def shapes(tree):
        return jax.tree.map(lambda x: S(x.shape, x.dtype), tree)

    def serving(config, what):
        slots, blocks, bucket, min_dec = SERVING[config]
        with open(os.path.join(root, BENCH, "configs", config + ".json")) as f:
            cfg = GPTConfig(**json.load(f)["model"])
        params = shapes(jax.eval_shape(lambda: G.init_serving_params(cfg, jax.random.key(0))))
        bs = cfg.kv_block_default or 16
        kw = {"slots": slots} if cfg.row_state else {}
        if cfg.window_layers:
            kw["ring_blocks"] = slots * RING + 1
        pools = shapes(jax.eval_shape(lambda: G.init_paged_pools(cfg, blocks, bs, **kw)))

        def i32(*shape):
            return S(shape, jnp.int32)

        if what == "prefill":
            def prefill(p, prompt, plen, pools, row, ring, slot):
                row_state = {"slot": slot} if cfg.row_state else {}
                tables = (row, ring) if cfg.window_layers else row
                return G.paged_prefill(p, prompt, plen, pools, tables, cfg, return_moe=True, **row_state)

            return jax.jit(prefill, donate_argnums=(3,)).lower(
                params, i32(1, bucket), i32(), pools, i32(-(-bucket // bs)), i32(RING), i32())
        gen = G.GenerationConfig(decode_strategy="greedy_search", max_dec_len=0, min_dec_len=min_dec,
                                 eos_token_id=0, pad_token_id=0)
        width, vocab = (blocks - 1) // slots, cfg.vocab_size

        def step(p, pools, tables, rings, logits, counts, positions, gen_steps, max_news, active, forced):
            rows = G.PagedRows(logits, counts, positions, gen_steps, max_news, active, forced)
            nxt, pools, new = G.decode_step(p, pools, (tables, rings) if cfg.window_layers else tables, rows, cfg, gen)
            return nxt, pools, new.logits, new.counts, new.moe

        return jax.jit(step, donate_argnums=(1,)).lower(
            params, pools, i32(slots, width), i32(slots, RING), S((slots, vocab), jnp.float32), i32(slots, vocab),
            i32(slots), i32(slots), i32(slots), S((slots,), jnp.bool_), i32(slots))

    def train_step(name):
        from paddlefleetx_tpu.core.engine import Engine
        from paddlefleetx_tpu.core.module import build_module
        from paddlefleetx_tpu.parallel.env import init_dist_env
        from paddlefleetx_tpu.utils.config import get_config

        with open(os.path.join(root, BENCH, "configs", name + ".json")) as f:
            config = json.load(f)
        cfg = get_config(
            os.path.join(root, config["yaml"]),
            overrides=[f"Model.{k}={v}" for k, v in config["model"].items()]
            + [f"Global.{k}_batch_size={TRAINING[name]}" for k in ("global", "local", "micro")],
            num_devices=1)
        mesh = init_dist_env(cfg, devices=topo.devices[:1])
        with mesh:
            engine = Engine(cfg, build_module(cfg), mesh, abstract_init=True)
            b, s = int(cfg.Global.global_batch_size), int(cfg.Data.Train.dataset.max_seq_len)
            batch = {name: jax.ShapeDtypeStruct((b, s), dt, sharding=engine.batch_spec)
                     for name, dt in (("tokens", np.int64), ("labels", np.int64),
                                      ("loss_mask", np.float32), ("position_ids", np.int64))}
            return engine._train_step.lower(engine.state, batch)

    for name in names:
        config, what = name.rsplit(".", 1)
        yield name, train_step(config) if what == "train_step" else serving(config, what)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--root", default=os.path.dirname(HERE), help="the checkout to lower (default: this one)")
    ap.add_argument("--write", action="store_true",
                    help=f"rewrite {os.path.relpath(GOLDEN)} and {os.path.relpath(KERNELS)}")
    ap.add_argument("--check", action="store_true", help="compare with the kept hashes; exit 1 if any differs")
    ap.add_argument("--bodies", action="store_true",
                    help="print every program's kernels (<name>:<hash of its body without locations>) instead")
    args = ap.parse_args(argv)
    got = {GOLDEN: {}, KERNELS: {}}
    for name, program in lowered(os.path.abspath(args.root)):
        got[GOLDEN][name], got[KERNELS][name] = digest(program), kernel_bodies(program)
    print(json.dumps(got[KERNELS if args.bodies else GOLDEN], indent=1))
    rc = 0
    for path, mine in got.items():
        if args.write:
            with open(path, "w") as f:
                json.dump(mine, f, indent=1)
                f.write("\n")
        if args.check:
            with open(path) as f:
                kept = json.load(f)
            differ = sorted(n for n in set(mine) | set(kept) if mine.get(n) != kept.get(n))
            if differ:
                print(f"differs from tests/{os.path.basename(path)}:", ", ".join(differ), file=sys.stderr)
                rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
