#!/usr/bin/env python3
"""The harness's own checks, by hand (not under tests/, not collected by
tier-1):

    python3 pfx_bench/selftest/run.py            # everything, ~3 min on the CPU
    python3 pfx_bench/selftest/run.py --quick    # no rehearsals

1. BENCHMARK.json and every data file against the contract's limits;
2. trace_reduce.py on the recorded trace beside this file (union, not sum);
3. model_math.py against the parameter counts;
4. loadgen.py: every seed gets the same sizes and gaps in another order;
5. without a chip the command exits non-zero, in seconds, with no result;
6. every cell end to end on the CPU at toy widths (--rehearse), with
   --trace 0 and --trace 2 (every other cell with --trace 1 too, and there
   the --trace 2 line must carry what the other two carry together)."""

import collections
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import common  # noqa: E402
import loadgen  # noqa: E402
import model_math  # noqa: E402
import trace_reduce  # noqa: E402

FAILS = []
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def check(ok, what):
    print(("ok   " if ok else "FAIL ") + what, flush=True)
    if not ok:
        FAILS.append(what)


def close(a, b, tol=1e-9):
    return abs(a - b) <= tol * max(1.0, abs(a), abs(b))


def check_contract():
    path = os.path.join(ROOT, "BENCHMARK.json")
    with open(path) as f:
        text = f.read()
    bench = json.loads(text)
    check(len(text) <= 64 * 1024, "BENCHMARK.json is at most 64 KiB")
    seven = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end",
             "per_layer"}
    check(seven <= set(bench) <= seven | {"trace_in_run"},
          "BENCHMARK.json has its seven keys (and, optionally, trace_in_run)")
    check(isinstance(bench.get("trace_in_run", False), bool), "trace_in_run is a boolean")
    check(bench["paths"] == [common.BENCH_REL], f"paths is [{common.BENCH_REL}]")
    check(1 <= int(bench["run_seconds"]) <= 51, "run_seconds within 1..51")
    n_cells = 24  # the limit must hold with the full 24 cells
    need = (2 + 14 * n_cells) * (bench["run_seconds"] + 60) + n_cells * 180 + 1200
    check(need <= 43200, f"a full check of 24 cells fits 43200 s ({need} s)")
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    layer = {m["name"]: m for m in bench["per_layer"]}
    names = list(configs) + list(cells) + list(e2e) + list(layer)
    check(all(common.NAME_RE.match(n) for n in names), "every name is a name")
    check(len(set(e2e) | set(layer)) == len(e2e) + len(layer), "no two metrics share a name")
    check("setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1, "setup_s is there, bound <= 0.1")
    for c in bench["configs"]:
        check(set(c) == {"name", "source", "file", "reduced", "why"}, f"config {c['name']}: keys")
        data = common.load_config(c["name"])
        check(c["file"] == f"{common.BENCH_REL}/configs/{c['name']}.json"
              and os.path.isfile(os.path.join(ROOT, c["file"])), f"config {c['name']}: file")
        check(data["source"] == c["source"] and data["reduced"] == c["reduced"],
              f"config {c['name']}: source and reduced agree with its file")
        bad = [k for k in c["reduced"] if k.endswith(("_dim", "_rank", "_size"))]
        check(not bad and len(c["reduced"]) <= 16, f"config {c['name']}: reduced names no width")
        check(any(w["config"] == c["name"] for w in bench["workloads"]),
              f"config {c['name']} is used by a cell")
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    check(len(four) <= max(1, len(cells) // 4), "at most 25% of the cells (one always) take 4 chips")
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    check(len(set(pairs)) == len(pairs), "a pair of configuration and traffic appears once")
    reports = {}
    for w in bench["workloads"]:
        check(set(w) == {"name", "config", "traffic", "chips", "why"}, f"cell {w['name']}: keys")
        check(len(w["why"]) <= 200 and "\n" not in w["why"], f"cell {w['name']}: why fits")
        cell = common.load_cell(w["name"])
        check((cell["config"], cell["traffic"], cell["chips"]) ==
              (w["config"], w["traffic"], w["chips"]) and cell["why"] == w["why"],
              f"cell {w['name']}: its file agrees with BENCHMARK.json")
        check(os.path.isfile(os.path.join(BENCH, "runners", f"{cell['runner']}.py")),
              f"cell {w['name']}: runner {cell['runner']} exists")
        reports[w["name"]] = (set(cell["end_to_end"]), set(cell["per_layer"]))
    for m in bench["end_to_end"]:
        check(set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
              and common.UNIT_RE.match(m["unit"]) and m["better"] in ("lower", "higher")
              and m["source"] in ("host_clock", "device_trace") and 0 < m["bound"] <= 0.1,
              f"end-to-end {m['name']}: unit, better, source, bound")
        where = set(m.get("workloads", cells))
        check(where == {c for c, (e, _) in reports.items() if m["name"] in e},
              f"end-to-end {m['name']}: its cells are the cells that report it")
    for m in bench["per_layer"]:
        d = common.load_layer_metric(m["name"])
        check(set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
              and common.UNIT_RE.match(m["unit"]) and m["better"] in ("lower", "higher")
              and m["source"] in SOURCES, f"per-layer {m['name']}: unit, better, source")
        check(all(m[k] == d[k] for k in ("unit", "better", "source", "layer", "moves")),
              f"per-layer {m['name']}: agrees with layer_metrics/{m['name']}.json")
        check(os.path.isfile(os.path.join(BENCH, "readers", f"{d['reader']}.py")),
              f"per-layer {m['name']}: reader {d['reader']} exists")
        where = set(m.get("workloads", cells))
        check(where == {c for c, (_, p) in reports.items() if m["name"] in p},
              f"per-layer {m['name']}: its cells are the cells that report it")
        check(m["moves"] in e2e and all(m["moves"] in reports[c][0] for c in where),
              f"per-layer {m['name']}: every reporting cell also reports {m['moves']}")
    for c, (e, p) in reports.items():
        check("setup_s" in e and len(e) >= 2 and len(p) >= 1,
              f"cell {c}: setup_s, another end-to-end metric and a per-layer metric")
    for dirpath, _, files in os.walk(BENCH):
        if "__pycache__" in dirpath:
            continue
        for fn in files:
            rel = os.path.relpath(os.path.join(dirpath, fn), ROOT)
            if not (all(ch.isalnum() or ch in "_.-/" for ch in rel) and len(rel) <= 200):
                check(False, f"file name {rel}")


def check_trace():
    with open(os.path.join(HERE, "trace_fixture.json")) as f:
        fx = json.load(f)
    r, want = trace_reduce.reduce(fx["planes"]), fx["expect"]
    check(r["device_planes"] == 2, "trace: two device planes found")
    check(close(r["window_s"], want["window_s"]), "trace: window is first op to last op")
    check(close(r["busy_s"], want["busy_s"]),
          f"trace: busy is the union, mean over chips ({r['busy_s']} s)")
    check(r["busy_s"] <= r["window_s"], "trace: overlapping ops give no more than 100% busy")
    check(close(r["idle_share"], want["idle_share"]), "trace: idle share")
    check(close(r["category_share"]["pallas"], want["pallas_share"]), "trace: pallas share")
    check(close(r["category_share"]["collective"], want["collective_share"]),
          "trace: collective share")
    check(close(r["op_self_s_over_busy_s"], 1.0),
          "trace: self times leave out a while's children")
    gaps = dict(r["idle_gaps"])
    check(all(close(gaps.get(k, -1), v) for k, v in want["gaps_device0"].items()),
          f"trace: gaps go to the innermost host span over them ({gaps})")


def check_math():
    for name, want in (("gpt-345m", 354_871_296), ("gpt-1.3b", 1_313_722_368)):
        m = common.load_config(name)["model"]
        got = model_math.param_count(m)
        check(got == want, f"model_math: {name} has {got:,} parameters")
        f = model_math.train_flops_per_token(m, 1024)
        check(5.5 * got < f < 7.0 * got, f"model_math: {name} {f / 1e9:.3f} GFLOP/token "
              f"is near 6N ({6 * got / 1e9:.3f})")


def check_loadgen():
    for mix in ("chat-decode", "doc-prefill"):
        t = common.load_traffic(mix)
        a = loadgen.build_plan(t, 3, 30.0, 50304)
        b = loadgen.build_plan(t, 3_000_000_007, 30.0, 50304)
        again = loadgen.build_plan(t, 3, 30.0, 50304)
        win = [r for r in a["requests"] if r["phase"] == "window"]
        check(a == again, f"loadgen {mix}: the same seed gives the same plan")
        check(len(win) == round(t["rate_rps"] * 30.0), f"loadgen {mix}: rate x seconds requests")
        check(all(t["lead_in_s"] <= r["due"] < t["lead_in_s"] + 30.0 for r in win),
              f"loadgen {mix}: window requests are due inside the window")

        def sizes(p):
            return sorted((len(r["prompt_ids"]), r["max_tokens"]) for r in p["requests"])

        check(sorted(len(r["prompt_ids"]) for r in a["requests"]) ==
              sorted(len(r["prompt_ids"]) for r in b["requests"])
              and sorted(r["max_tokens"] for r in a["requests"]) ==
              sorted(r["max_tokens"] for r in b["requests"]),
              f"loadgen {mix}: another seed, the same sizes")
        check([r["prompt_ids"] for r in a["requests"]] != [r["prompt_ids"] for r in b["requests"]],
              f"loadgen {mix}: another seed, other prompts")
        def gaps(p, phase):
            due = [r["due"] for r in p["requests"] if r["phase"] == phase]
            return collections.Counter(round(y - x, 6) for x, y in zip(due, due[1:]))

        check([(r["due"], len(r["prompt_ids"])) for r in a["requests"]] !=
              [(r["due"], len(r["prompt_ids"])) for r in b["requests"]],
              f"loadgen {mix}: another seed, another order")
        # n gaps place n requests, so each plan shows all but its last gap
        check(sum((gaps(a, "window") - gaps(b, "window")).values()) <= 1,
              f"loadgen {mix}: another seed, the same gaps")
        lo, hi = t["prompt_len"]["min"], t["prompt_len"]["max"]
        check(all(lo <= len(r["prompt_ids"]) <= hi for r in a["requests"]),
              f"loadgen {mix}: prompt lengths within {lo}-{hi}")


def last_json(text):
    try:
        return json.loads(text.strip().splitlines()[-1])
    except (IndexError, ValueError):
        return None


def run_cmd(argv, timeout):
    t0 = time.time()
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py")] + argv,
                       cwd=ROOT, capture_output=True, text=True, timeout=timeout)
    return p.returncode, p.stdout, time.time() - t0


def check_no_chip(cells):
    rc, out, took = run_cmd(["--workload", cells[0], "--seed", "1", "--seconds", "5",
                             "--trace", "0"], 300)
    check(rc != 0 and last_json(out) is None and took < 60,
          f"no chip: exit {rc}, no result line, {took:.0f} s")


def check_rehearsals(cells):
    for i, cell in enumerate(cells):
        names = {}
        for trace in ((0, 1, 2) if i % 2 == 0 else (0, 2)):
            rc, out, took = run_cmd(["--workload", cell, "--seed", "3000000007", "--seconds",
                                     "5", "--trace", str(trace), "--rehearse"], 900)
            line = last_json(out) or {}
            ok = (rc == 0 and line.get("correct") is True and line.get("failed") == 0
                  and line.get("device", {}).get("platform") == "cpu" and line.get("metrics")
                  and set(line) <= {"correct", "attempted", "failed", "metrics", "device",
                                    "breakdown"})
            check(ok, f"rehearse {cell} --trace {trace}: {took:.0f} s, "
                      f"metrics {sorted(line.get('metrics', {}))}")
            if not ok:
                print(out[-1500:])
            names[trace] = set(line.get("metrics", {}))
        if 1 in names:
            check(names[2] >= names[0] | names[1],
                  f"rehearse {cell}: the --trace 2 line carries every metric of the "
                  f"--trace 0 and --trace 1 lines (lacks {sorted((names[0] | names[1]) - names[2])})")


def main():
    check_contract()
    check_trace()
    check_math()
    check_loadgen()
    cells = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "workloads")))
    if "--quick" not in sys.argv:
        check_no_chip(cells)
        check_rehearsals(cells)
    print(f"{len(FAILS)} failure(s)")
    return 1 if FAILS else 0


if __name__ == "__main__":
    sys.exit(main())
