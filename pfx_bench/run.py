#!/usr/bin/env python3
"""The benchmark's one command:

    python3 pfx_bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1|2>

The parent is standard-library Python and never imports jax; the cell's
runner starts one chip-owning child.  The last line of stdout is one JSON
object: correct, attempted, failed, metrics, device (and, with a trace,
breakdown).  ``--trace 0`` reports the end-to-end metrics, ``--trace 1`` (a
run of its own, traced in mid-window) the per-layer metrics, ``--trace 2``
both: it is a ``--trace 0`` run until its window has closed, then traces a
short stretch of the same traffic in the same process.  Without a chip, or
outside the repository it measures, it prints no result and exits non-zero.
``--rehearse`` (self-test only) runs toy widths on the CPU and says so in
``device``."""

import argparse
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import time

T0 = time.time()  # process start, as near as Python lets us see it
BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import common  # noqa: E402
from common import Fail, say  # noqa: E402


def load_module(kind: str, name: str):
    """runners/<name>.py or readers/<name>.py, found by name."""
    if not common.NAME_RE.match(name):
        raise Fail(f"bad {kind} name {name!r}")
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise Fail(f"no {kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(f"pfx_bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reduce_trace(trace_dir: str, out: str):
    """trace_reduce.py in a process of its own: it needs jax to read the
    xplane file, and the parent stays off jax."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PFX_PLATFORM="cpu")
    p = subprocess.run(
        [common.python(), os.path.join(BENCH, "trace_reduce.py"), trace_dir,
         os.path.join(out, "trace_structure.json")],
        cwd=common.ROOT, env=env, capture_output=True, text=True, timeout=900)
    try:
        return json.loads(p.stdout.strip().splitlines()[-1])
    except (IndexError, ValueError):
        say(f"trace reduction failed ({p.returncode}): {p.stderr[-800:]}")
        return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    try:
        for needed in ("paddlefleetx_tpu", os.path.join("tools", "train.py"),
                       os.path.join("tools", "serve.py")):
            if not os.path.exists(os.path.join(common.ROOT, needed)):
                raise Fail(f"{needed} not found beside {common.BENCH_REL}/: the "
                           "benchmark measures the repository it sits in")
        cell = common.load_cell(args.workload)
        e2e_defs = common.load_json("end_to_end.json")
        runner = load_module("runners", cell["runner"])
        res = runner.judge(cell, runner.run(cell, args, T0), args)
        out = common.out_dir(cell["name"], args.seed, args.trace)
        dev = dict(res["device"])
        want = "cpu" if args.rehearse else "tpu"
        if dev["platform"] != want or dev["count"] != int(cell["chips"]):
            raise Fail(f"ran on {dev}; the cell needs {cell['chips']} {want} device(s)")
        values = dict(res["values"])
        values["setup_s"] = res["t_window_start"] - T0
        for note in res["notes"]:
            say(f"check failed: {note}")
        say("info: " + json.dumps(res["info"]))

        metrics, breakdown = {}, None
        if args.trace != 1:  # the window of a run that was not traced in its middle
            for name in cell["end_to_end"]:
                if name in values:
                    metrics[name] = {"value": values[name], "unit": e2e_defs[name]["unit"]}
        if args.trace:
            # device_trace readers and the breakdown read the trace (--trace 2:
            # of the stretch after the window); counters, spans and host
            # clocks read the measured window through the runner's context
            t_reduce = time.time()
            trace = reduce_trace(res["trace_dir"], out) if res.get("trace_dir") else None
            if args.trace == 2 and res.get("trace_dir"):
                shutil.rmtree(res["trace_dir"], ignore_errors=True)
            if trace and trace.get("busy_s"):
                dev["busy_s"], dev["window_s"] = trace["busy_s"], trace["window_s"]
                breakdown = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
            kind = dev["kind"]
            ctx = dict(res["context"], trace=trace, end_to_end=values,
                       model=(cell["config_data"]["rehearse_model"] if args.rehearse
                              else cell["config_data"]["model"]),
                       peaks=(common.load_json("peaks.json")["rehearse"]["cpu"]
                              if args.rehearse else common.load_peaks(kind)))
            for name in cell["per_layer"]:
                d = common.load_layer_metric(name)
                val = load_module("readers", d["reader"]).read(ctx, **d.get("args", {}))
                if val is not None:
                    metrics[name] = {"value": val, "unit": d["unit"]}
            if args.trace == 1:
                say("end_to_end (traced run, not judged): " + json.dumps(values))
            say("trace: " + json.dumps({
                **{k: v for k, v in (trace or {}).items()
                   if k not in ("device_ops", "idle_gaps")},
                "reduce_s": round(time.time() - t_reduce, 2)}))
        line = {"correct": bool(res["correct"]), "attempted": int(res["attempted"]),
                "failed": int(res["failed"]), "metrics": metrics, "device": dev}
        if breakdown:
            line["breakdown"] = breakdown
        with open(os.path.join(out, "result.json"), "w") as f:
            json.dump({**line, "info": res["info"], "notes": res["notes"],
                       "end_to_end": values}, f, indent=1)
        print(json.dumps(line), flush=True)
        return 0
    except Fail as e:
        say(f"FAILED: {e}")
        return 1


if __name__ == "__main__":
    sys.exit(main())
