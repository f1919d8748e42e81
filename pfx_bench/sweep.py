#!/usr/bin/env python3
"""The knee finder for fixed-rate cells: boot the cell's server once, then
offer the cell's traffic at each rate in turn, one window each.

    python3 pfx_bench/sweep.py --workload serve-1.3b-chat --rates 2,3,4,5,6,8 --seconds 30

The knee is the highest rate at which at least 97% of the requests due in
the window completed AND time-to-first-token did not grow from the
window's first half to its second (median of the second half within
1.25x of the first plus 20 ms): past the knee the queue grows all through
the window.  The sweep stops at the first rate past it.  The cell's
traffic file then takes ``rate_rps`` = 0.8 x knee (by hand: the benchmark
never searches for a rate while it measures)."""

import argparse
import json
import os
import sys
import time

T0 = time.time()
BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import common  # noqa: E402
from common import Fail, say  # noqa: E402
from run import load_module  # noqa: E402


def sustained(info: dict) -> bool:
    done = info["completed"] >= 0.97 * info["requests_due_in_window"]
    a, b = info["ttft_p50_first_half_ms"], info["ttft_p50_second_half_ms"]
    return bool(done and b == b and b <= 1.25 * a + 20.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s, ascending")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=20260927)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--all", action="store_true", help="do not stop past the knee")
    args = ap.parse_args(argv)
    args.trace = 0
    try:
        cell = common.load_cell(args.workload)
        runner = load_module("runners", cell["runner"])
        server = runner.Server(cell, args, T0)
        rows, knee = [], None
        try:
            for rate in [float(r) for r in args.rates.split(",")]:
                raw = server.window(args.seconds, rate=rate)
                raw["memory_peak_bytes"] = 0
                res = runner.judge(cell, raw, args)
                info = res["info"]
                row = {"rate_rps": rate, "sustained": sustained(info),
                       "due": info["requests_due_in_window"], "completed": info["completed"],
                       "ttft_p50_ms": info["ttft_p50_ms"], "ttft_p95_ms": info["ttft_p95_ms"],
                       "ttft_p50_halves_ms": [info["ttft_p50_first_half_ms"],
                                              info["ttft_p50_second_half_ms"]],
                       "itl_p50_ms": info["itl_p50_ms"], "itl_p95_ms": info["itl_p95_ms"],
                       "serve_tokens_per_s": info["serve_tokens_per_s"],
                       "loadgen_late_p95_ms": info["loadgen_late_p95_ms"],
                       "notes": res["notes"][:3]}
                rows.append(row)
                say("sweep: " + json.dumps(row))
                if row["sustained"]:
                    knee = rate
                elif not args.all:
                    break
                if not server.quiet():
                    say("sweep: the server did not go quiet; stopping")
                    break
        finally:
            peak, _ = server.stop()
        out = common.out_dir(cell["name"], args.seed, 0)
        result = {"workload": cell["name"], "seconds": args.seconds, "knee_rps": knee,
                  "rate_at_0.8_knee": None if knee is None else 0.8 * knee,
                  "memory_peak_bytes": peak, "rows": rows}
        with open(os.path.join(out, "sweep.json"), "w") as f:
            json.dump(result, f, indent=1)
        print(json.dumps({k: v for k, v in result.items() if k != "rows"}), flush=True)
        return 0
    except Fail as e:
        say(f"FAILED: {e}")
        return 1


if __name__ == "__main__":
    sys.exit(main())
