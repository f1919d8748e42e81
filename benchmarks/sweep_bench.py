"""Bench knob sweep: run bench.py across tuning-knob combinations on the
real chip and append one JSON row per combo to benchmarks/sweep_results.jsonl.

The round-3 verdict's MFU push (docs/performance_tuning.md) needs measured
evidence for which lever moves the 345M headline: chunked CE (streams the
vocab so the fp32 logits buffer never materializes — enables bigger batch),
remat granularity, batch size, dropout impl.  This driver makes the whole
sweep one command:

  python benchmarks/sweep_bench.py [--combos default|quick] [--steps N]

Each combo runs bench.py as a subprocess (inheriting its signal-safe
always-emit contract) with a per-run deadline, so one wedged run cannot eat
the window.
"""

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "benchmarks", "sweep_results.jsonl")

# name -> env overrides on top of bench.py defaults (batch16, seq1024,
# selective remat, fused ln, rbg dropout, chunked CE off)
COMBOS = {
    "baseline_b16": {},
    "chunked_ce_b16": {"BENCH_CHUNKED_CE": "1"},
    "chunked_ce_b24": {"BENCH_CHUNKED_CE": "1", "BENCH_BATCH": "24"},
    "chunked_ce_b32": {"BENCH_CHUNKED_CE": "1", "BENCH_BATCH": "32"},
    "no_remat_b8": {"BENCH_RECOMPUTE": "0", "BENCH_BATCH": "8"},
    "no_remat_chunked_b12": {
        "BENCH_RECOMPUTE": "0", "BENCH_CHUNKED_CE": "1", "BENCH_BATCH": "12",
    },
    "full_remat_b32": {"BENCH_REMAT": "full", "BENCH_BATCH": "32"},
    "full_remat_chunked_b48": {
        "BENCH_REMAT": "full", "BENCH_CHUNKED_CE": "1", "BENCH_BATCH": "48",
    },
    "no_dropout_b16": {"BENCH_DROPOUT": "0.0"},
}
QUICK = ["baseline_b16", "chunked_ce_b16", "chunked_ce_b32"]


def run_combo(name: str, env_over: dict, steps: int, deadline_s: float) -> dict:
    env = dict(os.environ)
    env.update(env_over)
    env["BENCH_STEPS"] = str(steps)
    env["BENCH_DEADLINE_S"] = str(deadline_s)
    t0 = time.time()
    row = {"combo": name, "env": env_over}
    try:
        out = subprocess.run(
            [sys.executable, os.path.join(ROOT, "bench.py")],
            env=env, capture_output=True, text=True,
            timeout=deadline_s + 120,
        )
    except subprocess.TimeoutExpired as te:
        # a child wedged in native code past its own deadline machinery:
        # record the honest row and keep sweeping — one wedged run must
        # not eat the sweep
        row.update({"wall_s": round(time.time() - t0, 1),
                    "metric": "gpt345m_pretrain_throughput_per_chip",
                    "value": 0.0,
                    "unit": "tokens/s/chip (combo wedged past hard timeout)",
                    "vs_baseline": 0.0})
        if te.stderr:
            stderr = te.stderr
            if isinstance(stderr, bytes):
                stderr = stderr.decode("utf-8", "replace")
            row["stderr_tail"] = stderr[-800:]
        return row
    row["wall_s"] = round(time.time() - t0, 1)
    for line in out.stdout.splitlines():
        try:
            parsed = json.loads(line)
        except ValueError:
            continue
        if isinstance(parsed, dict) and "metric" in parsed:
            row.update(parsed)
    if "value" not in row:
        row.update({"metric": "gpt345m_pretrain_throughput_per_chip",
                    "value": 0.0, "unit": f"no JSON (rc={out.returncode})",
                    "vs_baseline": 0.0})
    if row.get("value") == 0.0 and out.stderr:
        # a dead combo's cause (e.g. the OOM allocator report) must survive
        # into the sweep record — round 4's no-remat rows died with nothing
        # but an rc
        row["stderr_tail"] = out.stderr[-800:]
    return row


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--combos", default="default", help="default|quick|name,name,...")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--per-run-deadline", type=float, default=420.0)
    args = ap.parse_args(argv)

    if args.combos == "default":
        names = list(COMBOS)
    elif args.combos == "quick":
        names = QUICK
    else:
        names = [n.strip() for n in args.combos.split(",") if n.strip()]
        unknown = [n for n in names if n not in COMBOS]
        if unknown:
            # a typo must not turn the sweep into a silent no-op
            ap.error(f"unknown combos {unknown}; have {sorted(COMBOS)}")

    best = None
    for name in names:
        row = run_combo(name, COMBOS[name], args.steps, args.per_run_deadline)
        print(json.dumps(row), flush=True)
        with open(OUT, "a") as f:
            f.write(json.dumps(row) + "\n")
        if row.get("value", 0.0) and (best is None or row["value"] > best["value"]):
            best = row
    if best:
        print(f"# best: {best['combo']} {best['value']} {best.get('unit', '')}",
              file=sys.stderr)


if __name__ == "__main__":
    main()
