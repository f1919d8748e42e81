"""TIPC-style benchmark harness.

Re-design of the reference benchmark layer (benchmarks/test_tipc/:
<model>/<graph-mode>/<parallel-mode>/<Nnodes-Ccards>/<case>.sh calling
benchmark_common/run_benchmark.sh, which shrinks the model to 4 layers/4
heads, runs tools/train.py under the launcher with a timeout, and regex-
parses logs for `ips:` tokens/s + `loss:` — SURVEY §4).

Here a case is a JSON file (benchmarks/cases/*.json):

  {"config": "<yaml>", "devices": 8, "platform": "cpu"|null,
   "overrides": ["Model.num_layers=4", ...], "timeout_s": 600}

Run:  python benchmarks/run_benchmark.py [case ...]  (default: all cases)
Output: one JSON line per case {case, ips, ips_per_device, last_loss, ok}
plus benchmarks/results.jsonl.  Loss keys double as the convergence
regression signal, exactly like the reference's convergence_key.
"""

import argparse
import glob
import json
import os
import re
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

IPS_RE = re.compile(r"ips: ([\d,]+) tokens/s \(([\d,]+)/device\)")
# train-step lines only — 'eval loss:' must not pollute the convergence key
LOSS_RE = re.compile(r"step \d+/\d+ loss: ([\d.]+)")


def _provision(name: str, spec: dict, writer, writer_kwargs: dict,
               marker_file: str, returns_prefix: bool):
    """Cache-keyed synthetic corpus generation shared by all dataset
    families: regenerate when the case spec changes, not on mere
    existence.  Returns the value to point input_dir at (the corpus
    prefix or its directory, per the dataset's convention)."""
    data_dir = os.path.join("/tmp", "pfx_bench_data", name)  # noqa — dir, not a metric
    prefix = os.path.join(data_dir, "corpus")
    spec_path = os.path.join(data_dir, "spec.json")
    spec_str = json.dumps(spec, sort_keys=True)
    stale = True
    if os.path.exists(spec_path):
        with open(spec_path) as f:
            stale = f.read() != spec_str
    if stale or not os.path.exists(os.path.join(data_dir, marker_file)):
        os.makedirs(data_dir, exist_ok=True)
        writer(prefix, **writer_kwargs)
        with open(spec_path, "w") as f:
            f.write(spec_str)
    return prefix if returns_prefix else data_dir


def _ensure_synthetic_data(case: dict, name: str) -> list:
    """Generate a tiny corpus for the case (reference run_benchmark.sh
    points cases at pre-staged data; we self-provision).  Every knob in
    the case spec is forwarded to the writer — an unknown knob fails
    loudly rather than silently regenerating identical data."""
    sys.path.insert(0, ROOT)  # before the writer imports below
    espec = case.get("synthetic_ernie_data")
    if espec:
        from paddlefleetx_tpu.data.ernie_dataset import (
            write_synthetic_sentence_corpus,
        )

        target = _provision(
            name, espec, write_synthetic_sentence_corpus, dict(espec),
            marker_file="corpus_ids.npy", returns_prefix=True,
        )
    else:
        spec = case.get("synthetic_gpt_data")
        if not spec:
            return []
        from paddlefleetx_tpu.data.gpt_dataset import write_synthetic_corpus

        target = _provision(
            name, spec, write_synthetic_corpus, dict(spec),
            marker_file="corpus_ids.npy", returns_prefix=False,
        )
    return [
        f"Data.Train.dataset.input_dir={target}",
        f"Data.Eval.dataset.input_dir={target}",
    ]


def run_case(path: str) -> dict:
    with open(path) as f:
        case = json.load(f)
    name = os.path.splitext(os.path.basename(path))[0]
    cmd = [sys.executable, os.path.join(ROOT, "tools", "train.py"), "-c",
           os.path.join(ROOT, case["config"])]
    for o in case.get("overrides", []) + _ensure_synthetic_data(case, name):
        cmd += ["-o", o]
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    if case.get("platform") == "cpu":
        # the repo's own CPU pin (utils/device.resolve_platform)
        env["PFX_PLATFORM"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={case.get('devices', 8)}"
        )
    t0 = time.time()
    try:
        proc = subprocess.run(
            cmd, env=env, capture_output=True, text=True,
            timeout=case.get("timeout_s", 900),
        )
        log = proc.stdout + proc.stderr
        ok = proc.returncode == 0
    except subprocess.TimeoutExpired as e:
        log = (e.stdout or "") + (e.stderr or "")
        ok = False
    ips = [float(m.group(1).replace(",", "")) for m in IPS_RE.finditer(log)]
    ips_dev = [float(m.group(2).replace(",", "")) for m in IPS_RE.finditer(log)]
    losses = [float(m.group(1)) for m in LOSS_RE.finditer(log)]
    result = {
        "case": name,
        "ok": ok and bool(ips),
        # steady-state: last window (first includes compile)
        "ips": ips[-1] if ips else None,
        "ips_per_device": ips_dev[-1] if ips_dev else None,
        "first_loss": losses[0] if losses else None,
        "last_loss": losses[-1] if losses else None,
        "wall_s": round(time.time() - t0, 1),
    }
    if not result["ok"]:
        result["log_tail"] = log[-2000:]
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cases", nargs="*", help="case json paths (default: all)")
    args = ap.parse_args(argv)
    cases = args.cases or sorted(
        glob.glob(os.path.join(ROOT, "benchmarks", "cases", "*.json"))
    )
    results = []
    for path in cases:
        r = run_case(path)
        results.append(r)
        print(json.dumps(r))
    out = os.path.join(ROOT, "benchmarks", "results.jsonl")
    with open(out, "a") as f:
        for r in results:
            f.write(json.dumps(r) + "\n")
    bad = [r["case"] for r in results if not r["ok"]]
    if bad:
        print(f"FAILED cases: {bad}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
