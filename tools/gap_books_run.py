#!/usr/bin/env python3
"""One run of the benchmark with the token gap's books read beside it:

    python3 tools/gap_books_run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|2> [--rehearse]

This is ``pfx_bench/run.py``'s own ``main`` in this process (the same cell
files, runner, load, checks and result line; like it, the process stays
off jax), with two things held in memory only:

* the cell's per-layer list gains the metrics whose files wait under
  ``pfx_bench/layer_metrics/`` (a cell's list is a benchmark file that
  only a ``benchmark`` issue edits, so a ``--trace 2`` line here carries
  them and ``run.py``'s does not yet): a serving cell the three that read
  the books (``sched.gap_admission_share``, ``sched.gap_flush_share``,
  ``sched.admit_host_share``) and ``sched.stall_share`` (the slow
  iterations' excess over the scheduler's non-idle wall: 0.0 in a sound
  run), a train cell ``engine.stall_share`` and ``engine.host_gap_share``
  (and nothing more: a train run has no books to print);
* the runner's ``judge`` is watched, so that after the run the window's
  ``/metrics`` delta and the last, quiet scrape can be held against the
  client's own frames.

After ``run.py``'s result line it prints one more, ``gap_books: {...}``
(docs/observability.md "Goodput ledger"): the gaps and their seconds by
what the interval held, the server's mean token gap beside the client's
``itl_mean_ms``, and whether the books close.  The driver never runs this
file; a builder and ``tests/test_gap_books_rehearsal.py`` do."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "pfx_bench")  # noqa: E10 — a directory, not a metric
sys.path.insert(0, BENCH)

import common  # noqa: E402
import run  # noqa: E402

BOOK_METRICS = ("sched.gap_admission_share", "sched.gap_flush_share",
                "sched.admit_host_share", "sched.stall_share")
TRAIN_METRICS = ("engine.stall_share", "engine.host_gap_share")
HELD = ("decode", "admission", "flush")
PATHS = ("behind_step", "after_flush", "idle")
GAPS = "pfx_sched_token_gaps_total"
SECONDS = "pfx_sched_token_gap_seconds_total"


def books(raw: dict, res: dict, warm_requests: list) -> dict:
    """The books of one judged run, from what the runner already holds."""
    delta = res["context"].get("scrape_delta") or {}
    final = raw.get("final_metrics") or {}
    w0, w1 = raw["w0"], raw["w1"]
    gaps = {h: common.metric_sum(delta, GAPS, held=h) for h in HELD}
    secs = {h: common.metric_sum(delta, SECONDS, held=h) for h in HELD}
    n, s = sum(gaps.values()), sum(secs.values())
    # the client's gaps whose later frame fell inside the window, over
    # every request (lead-in too): the rows the server's delta covers
    client = [b - a for r in raw["requests"]
              for (a, _), (b, _) in zip(r["frames"], r["frames"][1:]) if w0 <= b < w1]
    out = {
        "window": {
            "gaps": gaps, "seconds": {h: round(v, 6) for h, v in secs.items()},
            "server_gap_mean_ms": 1e3 * s / n if n else None,
            "itl_mean_ms": res["values"].get("itl_mean_ms"),
            "client_gap_mean_in_window_ms":
                1e3 * sum(client) / len(client) if client else None,
            "client_gaps_in_window": len(client),
            "admit_host_s": round(common.metric_sum(
                delta, "pfx_sched_admit_host_seconds_total"), 6),
            # the window's admissions and the grouped products their prefills
            # dispatched (pfx_grouped_matmul; none without expert layers)
            "prefill_admits": common.metric_sum(delta, "pfx_prefill_admits_total"),
            # the same by where their dispatch found the device (PR 44; all
            # 0 on a program from before it): behind the step in flight,
            # after a flush of it, with nothing in flight
            "admissions": {p: common.metric_sum(delta, "pfx_sched_admissions_total", path=p)
                           for p in PATHS},
            "moe_grouped_calls": common.metric_sum(delta, "pfx_moe_serve_grouped_calls_total"),
            # frames less first frames by the token ledger and the admissions
            # counter: off by the rows seated but not yet framed at an edge,
            # and by one commit's rows where that commit landed between the
            # reads of the two families (a scrape does not stop the scheduler)
            "ledger_frames_less_rows": (
                common.metric_sum(delta, "pfx_token_ledger_total", disposition="admitted")
                - common.metric_sum(delta, "pfx_prefill_admits_total")),
        },
        "errors": common.metric_sum(final, "pfx_sched_gap_books_errors_total"),
        "series_present": any(k.startswith(GAPS) for k in final),
    }
    if n and s:
        out["window"]["share_of_seconds_pct"] = {h: 100.0 * secs[h] / s for h in HELD}
    # since boot, at the quiet scrape after the drain: the server's gaps,
    # the same by the token ledger, and the same counted by the clients
    framed = [len(r["frames"]) for r in raw["requests"] if r["frames"]]
    out["since_boot"] = {
        "gaps": common.metric_sum(final, GAPS),
        "ledger_frames_less_rows": (
            common.metric_sum(final, "pfx_token_ledger_total", disposition="admitted")
            - common.metric_sum(final, "pfx_prefill_admits_total")),
        "client_frames_less_rows": (
            sum(framed) - len(framed)
            + sum(int(w["max_tokens"]) - 1 for w in warm_requests)),
    }
    sb = out["since_boot"]
    out["closed"] = bool(out["series_present"] and out["errors"] == 0 and sb["gaps"] > 0
                         and sb["gaps"] == sb["ledger_frames_less_rows"]
                         == sb["client_frames_less_rows"])
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    seen = {}
    load_cell, load_module = common.load_cell, run.load_module

    def cell_with_books(name):
        cell = load_cell(name)
        more = TRAIN_METRICS if "train_tokens_per_s" in cell["end_to_end"] else BOOK_METRICS
        cell["per_layer"] = list(cell["per_layer"]) + [
            m for m in more if m not in cell["per_layer"]]
        seen["cell"] = cell
        return cell

    def watched(kind, name):
        mod = load_module(kind, name)
        if kind == "runners":
            judge = mod.judge

            def judge_and_keep(cell, raw, args):
                res = judge(cell, raw, args)
                seen.update(raw=raw, res=res, rehearse=args.rehearse)
                return res

            mod.judge = judge_and_keep
        return mod

    common.load_cell, run.load_module = cell_with_books, watched
    try:
        rc = run.main(argv)
    finally:
        common.load_cell, run.load_module = load_cell, load_module
    if "res" in seen and "train_tokens_per_s" in seen["cell"]["end_to_end"]:
        return rc  # a train cell: its two shares are on the result line
    if "res" not in seen or "requests" not in seen["raw"]:
        common.say("gap_books: no judged serving run to read")
        return rc or 1
    traffic = dict(seen["cell"]["traffic_data"])
    if seen["rehearse"]:
        traffic.update(traffic["rehearse"])
    out = books(seen["raw"], seen["res"], traffic.get("warm_requests", []))
    print("gap_books: " + json.dumps(out), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
