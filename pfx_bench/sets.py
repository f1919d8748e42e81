#!/usr/bin/env python3
"""Measure a cell the way its bounds are set: two sets of runs with the same
seeds in both, each run of a set with another seed, all in one call; then,
for every metric, each set's median and spread (distance between the first
and third quartile over the median, ``statistics.quantiles(values, n=4)``).

    chiprun --timeout 3400 -- python3 pfx_bench/sets.py --workload serve-1.3b-docs

Every run's result line and ``info:`` line go to
``chiprun_out/pfx_bench/sets_<cell>.log``, and every run keeps its own records
(child log, step records, result) under ``<cell>/set<k>-seed<n>/``, so a run
that reads far off can be looked into afterwards.  A bound is about five times the
widest spread over the cells, never under 1%."""

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import common  # noqa: E402
import stats  # noqa: E402

SEEDS = "101,20260927,1234567891,2147483659,2200000033,987654321"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=None, help="default: run_seconds")
    ap.add_argument("--seeds", default=SEEDS)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    seconds = args.seconds
    if seconds is None:
        with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
            seconds = json.load(f)["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",")]
    log_dir = os.path.join(common.ROOT, "chiprun_out", common.BENCH_REL)
    os.makedirs(log_dir, exist_ok=True)
    sets = []
    with open(os.path.join(log_dir, f"sets_{args.workload}.log"), "w") as log:
        for n in (1, 2):
            runs = []
            for seed in seeds:
                cmd = [common.python(), os.path.join(BENCH, "run.py"), "--workload",
                       args.workload, "--seed", str(seed), "--seconds", str(seconds),
                       "--trace", "0"] + (["--rehearse"] if args.rehearse else [])
                out = subprocess.run(cmd, cwd=common.ROOT, capture_output=True, text=True).stdout
                lines = out.strip().splitlines()
                log.write(f"== set {n} seed {seed}\n" + "\n".join(
                    ln for ln in lines if ln.startswith(("info:", "check failed", "FAILED", "{"))
                ) + "\n")
                log.flush()
                kept = os.path.join(log_dir, args.workload, f"set{n}-seed{seed}")
                shutil.rmtree(kept, ignore_errors=True)
                os.rename(common.out_dir(args.workload, seed, 0), kept)
                try:
                    line = json.loads(lines[-1])
                except (IndexError, ValueError):
                    print(f"set {n} seed {seed}: no result line", flush=True)
                    continue
                print(f"set {n} seed {seed}: correct {line['correct']} " + json.dumps(
                    {k: v["value"] for k, v in line["metrics"].items()}), flush=True)
                if not line["correct"]:
                    print("\n".join(lines[-8:]), flush=True)
                    return 1  # a set with a run that is not correct sets no bound
                runs.append(line)
            sets.append(runs)
    for name in sorted({k for runs in sets for r in runs for k in r["metrics"]}):
        cols = [[r["metrics"][name]["value"] for r in runs if name in r["metrics"]]
                for runs in sets]
        print(f"{name}: " + "; ".join(
            f"set {i + 1} median {statistics.median(v):.6g} spread {100 * stats.spread(v):.3f}%"
            for i, v in enumerate(cols) if len(v) >= 2), flush=True)
    ok = all(r["correct"] for runs in sets for r in runs) and \
        all(len(runs) == len(seeds) for runs in sets)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
