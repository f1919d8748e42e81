"""Auto-parallel entry point + parallel-strategy tuner.

Re-design of the reference AutoEngine path (tools/auto.py:40-69 +
core/engine/auto_engine.py: fit :104, tune :146).  Under pjit/GSPMD the
"semi-auto parallel static graph" IS the normal path — `fit` here is
train.py's loop — so the part worth keeping is `tune()`: the reference
delegates to Paddle's parallel-strategy tuner; the TPU equivalent is a
mesh-layout sweep, timing a few real steps per candidate layout and
picking the highest tokens/s.

Usage:
  python tools/auto.py -c configs/gpt/pretrain_gpt_345M_single.yaml          # = train
  python tools/auto.py -c ... --tune [--tune-steps 8]                        # sweep
      [-o overrides...]   candidates: Tuning.candidates (list of
      {dp,mp,pp,sharding,sep} dicts) or auto-enumerated factorizations.

The sweep runs each candidate as a tools/train.py subprocess (fresh XLA
per layout) and writes auto_tune_results.json next to the config output.
The sweeping parent never initializes a jax backend: a chip belongs to one
process at a time, and a parent holding it would starve every candidate.
"""

import argparse
import json
import os
import re
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddlefleetx_tpu.utils.device import apply_platform_env

apply_platform_env()  # tpu unless a CPU pin is set; before backend init

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
IPS_RE = re.compile(r"ips: ([\d,]+) tokens/s")


def enumerate_layouts(n_devices: int, max_candidates: int = 12):
    """Divisor factorizations n = dp * mp * pp (sharding folded into dp
    slot as a variant); smallest-mp-first so cheap layouts run first.

    Beyond pure layout, the grammar covers the execution knobs the
    reference tuner sweeps (auto Strategy tuning blocks, reference
    utils/config.py:515-590) and that docs/performance_tuning.md
    describes: recompute granularity, gradient accumulation, and
    precision mode — attached as variants of the leading layout."""
    outs = []
    for mp in [d for d in (1, 2, 4, 8) if n_devices % d == 0]:
        rest = n_devices // mp
        for pp in [d for d in (1, 2, 4) if rest % d == 0]:
            dp = rest // pp
            outs.append({"dp": dp, "mp": mp, "pp": pp})
            if dp > 1 and pp == 1:
                outs.append({"dp": 1, "mp": mp, "pp": 1, "sharding": dp})
    # non-layout knobs on the first (cheapest) layout: recompute trades
    # HBM for FLOPs, accumulate trades HBM for step latency, amp halves
    # the matmul cost — these frequently beat a layout change
    if outs:
        base = outs[0]
        outs[1:1] = [
            dict(base, recompute="selective"),
            dict(base, recompute="full"),
            dict(base, accumulate=2),
            dict(base, amp="bf16"),
            # bf16 grads: frees one param-size fp32 buffer per microbatch
            # accumulator (engine main_grad, measured 1.3B-fit lever)
            dict(base, amp="bf16", main_grad=False),
            # no fp32 masters at all: THE memory knob for models that
            # otherwise do not fit the chip (bf16 params + moments)
            dict(base, amp="bf16", main_grad=False, multi_precision=False),
        ]
    seen, uniq = set(), []
    for c in outs:
        key = tuple(sorted(c.items()))
        if key not in seen:
            seen.add(key)
            uniq.append(c)
    # the cap is authoritative: callers bound sweep wall-time by it, so
    # the knob variants spend slots WITHIN max_candidates (they sit right
    # after the lead layout, so they survive truncation and tail layout
    # factorizations drop first) — and a truncated grid must say so, not
    # silently report a "best" from an incomplete sweep
    if len(uniq) > max_candidates:
        print(
            f"tuner grid truncated: {len(uniq)} candidates -> "
            f"{max_candidates} (raise max_candidates to sweep all)",
            file=sys.stderr,
        )
    return uniq[:max_candidates]


def overrides_for(c: dict, global_batch: int) -> list:
    dp_world = c.get("dp", 1) * c.get("sharding", 1)
    local = max(global_batch // dp_world, 1)
    accum = max(int(c.get("accumulate", 1)), 1)
    if local % accum:
        # a non-dividing factor would either fail config validation or run
        # a different accumulation than the row reports — reject up front
        raise ValueError(
            f"accumulate={accum} does not divide local batch {local}"
        )
    micro = max(local // accum, 1)
    ov = [
        f"Distributed.dp_degree={c.get('dp', 1)}",
        f"Distributed.mp_degree={c.get('mp', 1)}",
        f"Distributed.pp_degree={c.get('pp', 1)}",
        f"Global.local_batch_size={local}",
        f"Global.micro_batch_size={micro}",
    ]
    if c.get("sharding"):
        ov += [
            f"Distributed.sharding.sharding_degree={c['sharding']}",
            f"Distributed.sharding.sharding_stage={int(c.get('sharding_stage', 2))}",
        ]
    if c.get("sep"):
        ov.append(f"Distributed.sep_degree={c['sep']}")
    if c.get("attn") is not None:
        # flash vs ring(+zigzag) is the lever long-context configs sweep
        ov.append(f"Model.attn_impl={c['attn']}")
    if c.get("zigzag") is not None:
        ov.append(f"Distributed.sep_zigzag={bool(c['zigzag'])}")
    if c.get("recompute") is not None:
        if c["recompute"] in (False, "none", "off"):
            ov.append("Model.use_recompute=False")
        else:
            ov += [
                "Model.use_recompute=True",
                f"Model.recompute_granularity={c['recompute']}",
            ]
    if c.get("amp") is not None:
        if c["amp"] in (False, "fp32", "off"):
            ov.append("Engine.mix_precision.enable=False")
        else:
            dtype = {"bf16": "bfloat16", "fp16": "float16"}.get(c["amp"], c["amp"])
            ov += [
                "Engine.mix_precision.enable=True",
                f"Engine.mix_precision.dtype={dtype}",
            ]
    if c.get("main_grad") is not None:
        ov.append(f"Engine.mix_precision.main_grad={bool(c['main_grad'])}")
    if c.get("multi_precision") is not None:
        ov.append(f"Optimizer.multi_precision={bool(c['multi_precision'])}")
    return ov


def run_candidate(config: str, base_overrides: list, cand: dict, tune_steps: int, global_batch: int):
    try:
        cand_overrides = overrides_for(cand, global_batch)
    except ValueError as e:
        return {"layout": cand, "ok": False, "ips": None, "error": str(e)}
    cmd = [sys.executable, os.path.join(ROOT, "tools", "train.py"), "-c", config]
    for o in base_overrides + cand_overrides + [
        f"Engine.max_steps={tune_steps}",
        "Engine.logging_freq=2",
        "Engine.eval_freq=0",
        "Engine.save_load.save_steps=0",
    ]:
        cmd += ["-o", o]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=900)
        log = proc.stdout + proc.stderr
        ips = [float(m.group(1).replace(",", "")) for m in IPS_RE.finditer(log)]
        return {"layout": cand, "ok": proc.returncode == 0 and bool(ips),
                "ips": ips[-1] if ips else None}
    except subprocess.TimeoutExpired:
        return {"layout": cand, "ok": False, "ips": None}


def _device_count() -> int:
    """The device count as a short-lived CHILD sees it (same platform
    resolution as every entry point); it has exited — and let go of the
    chip — before the first candidate starts."""
    out = subprocess.run(
        [sys.executable, "-c",
         "from paddlefleetx_tpu.utils.device import apply_platform_env; "
         "apply_platform_env(); import jax; print(jax.device_count())"],
        capture_output=True, text=True, cwd=ROOT, timeout=300,
    )
    if out.returncode != 0:
        sys.exit(f"cannot count devices:\n{out.stderr[-2000:]}")
    return int(out.stdout.split()[-1])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("-c", "--config", required=True)
    ap.add_argument("-o", "--override", action="append", default=[])
    ap.add_argument("--tune", action="store_true")
    ap.add_argument("--tune-steps", type=int, default=8)
    args = ap.parse_args(argv)

    if not args.tune:
        # fit: pjit IS the auto-parallel engine — same loop as train.py
        from tools.train import main as train_main

        return train_main(["-c", args.config] + sum([["-o", o] for o in args.override], []))

    from paddlefleetx_tpu.utils.config import get_config

    n = _device_count()
    cfg = get_config(args.config, overrides=args.override, num_devices=n)
    cands = cfg.get("Tuning", {}).get("candidates") or enumerate_layouts(n)
    gbs = int(cfg.Global.global_batch_size)
    print(f"tuning over {len(cands)} layouts on {n} devices (steps={args.tune_steps})")
    results = []
    for cand in cands:
        r = run_candidate(args.config, args.override, cand, args.tune_steps, gbs)
        results.append(r)
        print(json.dumps(r))
    ok = [r for r in results if r["ok"]]
    out_path = os.path.join(
        cfg.get("Engine", {}).get("save_load", {}).get("output_dir", "."), "auto_tune_results.json"
    )
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(results, f, indent=1)
    if not ok:
        print("no layout succeeded", file=sys.stderr)
        sys.exit(1)
    best = max(ok, key=lambda r: r["ips"])
    print(f"best layout: {json.dumps(best['layout'])} @ {best['ips']:,.0f} tokens/s")
    print(f"results -> {out_path}")


if __name__ == "__main__":
    main()
