"""Flash-attention kernel microbench: fwd and fwd+bwd wall time at the
headline bench shape, across backward schedule x block size combos.

Much cheaper per data point than a full bench.py run (~20 s vs ~3 min),
so a short chip run can answer the kernel questions (does the
bf16-dot change deliver? fused vs split? block optimum?) before the
end-to-end re-measures.  One JSON row per combo to stdout and
benchmarks/kernel_results.jsonl.

  python benchmarks/kernel_bench.py [--bh 256] [--seq 1024] [--d 64]
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--bh", type=int, default=256)  # b16 x h16
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args(argv)

    from paddlefleetx_tpu.utils.device import apply_platform_env

    apply_platform_env()
    import jax
    import jax.numpy as jnp

    b, n = 16, args.bh // 16
    shape = (b, args.seq, n, args.d)
    dt = jnp.dtype(args.dtype)
    kq, kk, kv, kg = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(kq, shape, jnp.float32).astype(dt)
    k = jax.random.normal(kk, shape, jnp.float32).astype(dt)
    v = jax.random.normal(kv, shape, jnp.float32).astype(dt)
    ct = jax.random.normal(kg, shape, jnp.float32).astype(dt)

    # attention FLOPs at this shape (fwd): 2 matmuls x 2*b*n*s^2*d, causal
    # halves the useful work but the kernels still run the masked tiles'
    # dots, so report dense FLOPs for the occupancy view
    flops_fwd = 2 * 2 * b * n * args.seq * args.seq * args.d

    from bench import host_fence

    def timed(fn, *xs):
        host_fence(fn(*xs))  # compile + warm
        t0 = time.perf_counter()
        for _ in range(args.iters):
            host_fence(fn(*xs))
        return (time.perf_counter() - t0) / args.iters

    rows = []
    # (block_q, block_k): symmetric points plus asymmetric K/V blocks — a
    # bigger K block amortizes HBM streaming without growing the q tile
    combos = list(dict.fromkeys(
        [(256, 256), (512, 512), (512, args.seq), (256, args.seq)]))
    from bench import knob_env

    for bwd_mode in ("split", "fused"):
        for block, block_k in combos:
            if args.seq % block or args.seq % block_k:
                continue
            # knob_env restores the pre-combo values (pop if unset) even on
            # error: the last combo's knobs must not leak out of main() and
            # poison an in-process caller that traces flash attention later
            with knob_env({"PFX_FLASH_BWD": bwd_mode,
                           "PFX_FLASH_BLOCK": block,
                           "PFX_FLASH_BLOCK_K": block_k}):
                from paddlefleetx_tpu.ops.flash_attention import flash_attention

                fwd = jax.jit(lambda a, b_, c: flash_attention(a, b_, c))

                def loss(a, b_, c):
                    return jnp.sum(
                        flash_attention(a, b_, c).astype(jnp.float32)
                        * ct.astype(jnp.float32)
                    )

                grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                try:
                    t_fwd = timed(fwd, q, k, v)
                    t_all = timed(grad, q, k, v)
                except Exception as e:  # noqa: BLE001 - report the combo, keep sweeping
                    rows.append({"bwd": bwd_mode, "block": block,
                                 "block_k": block_k,
                                 "error": str(e)[:200],
                                 "platform": jax.default_backend()})
                    print(json.dumps(rows[-1]))
                    continue
                row = {
                    "bwd": bwd_mode, "block": block, "block_k": block_k,
                    "dtype": args.dtype,
                    "fwd_ms": round(t_fwd * 1e3, 2),
                    "fwd_bwd_ms": round(t_all * 1e3, 2),
                    "fwd_tflops": round(flops_fwd / t_fwd / 1e12, 1),
                    # CPU-interpret smoke rows must never read as chip evidence
                    "platform": jax.default_backend(),
                }
                rows.append(row)
                print(json.dumps(row))

    with open(os.path.join(ROOT, "benchmarks", "kernel_results.jsonl"), "a") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


if __name__ == "__main__":
    main()
