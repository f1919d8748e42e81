"""Serve runner for a configuration whose block family is not the dense
GPT's (``runners/serve_arch.md``).  It loads ``runners/serve.py`` by path and
keeps its flow (``Server.window``, ``trace_stretch``, ``quiet``, ``stop``, the
open loop, ``judge``) and replaces what is literal there:

* the child is ``serve_arch_child.py``: the plain reference comes from the
  configuration file's ``reference`` key, the routing bias is made from the
  seed before warm-up, and the band lets a stated share of tokens sit past
  it (a router's top-k is a discontinuity);
* the readers' context gains ``math``, the work counters that
  ``/admin/profile`` read at the start and the stop of its capture, and the
  self time of every Pallas kernel in that capture, by kernel."""

import importlib.util
import json
import os
import subprocess
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import common  # noqa: E402
import loadgen  # noqa: E402
from common import Fail  # noqa: E402


def _load(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


base = _load("pfx_bench_runners_serve", os.path.join(BENCH, "runners", "serve.py"))
CHILD = os.path.join(BENCH, "runners", "serve_arch_child.py")


class Server(base.Server):
    """``serve.Server`` with this runner's child: the same boot, warm
    requests, window, traced stretch and stop."""

    def __init__(self, cell: dict, args, t0: float):
        config, traffic = cell["config_data"], dict(cell["traffic_data"])
        flags, overrides = list(cell["server_flags"]), list(cell["overrides"])
        if args.rehearse:
            traffic.update(traffic["rehearse"])
            flags = list(cell["rehearse"]["server_flags"])
            overrides = list(cell["rehearse"]["overrides"])
        model = config["rehearse_model"] if args.rehearse else config["model"]
        self.args, self.traffic = args, traffic
        self.vocab = int(model["vocab_size"])
        self.buckets = loadgen.prompt_buckets(traffic, base._pad_multiple(overrides))
        out = common.out_dir(cell["name"], args.seed, args.trace)
        self.log = os.path.join(out, "serve_child.log")
        self.mem_path = os.path.join(out, "serve_memory.json")
        self.served_path = os.path.join(out, "served_sequences.json")
        self.ref_path = os.path.join(out, "serve_reference.json")
        for path in (self.mem_path, self.served_path, self.ref_path):
            if os.path.exists(path):
                os.unlink(path)
        self.port = common.free_port()
        argv = [common.python(), CHILD, self.mem_path, self.served_path, self.ref_path,
                cell["config"], "rehearse" if args.rehearse else "real",
                "-c", os.path.join(common.ROOT, config["yaml"]),
                "--port", str(self.port), "--replica-id", f"bench-{cell['name']}",
                "--warmup-buckets", ",".join(map(str, self.buckets))] + flags
        for o in common.model_overrides(config, args.rehearse) + overrides + [
                f"Global.seed={args.seed % (2 ** 31)}", "Distributed.mp_degree=1",
                "Distributed.sequence_parallel=False"]:
            argv += ["-o", o]
        env = common.child_env(args.rehearse, int(cell["chips"]))
        env["PFX_PROFILE_MAX_SECONDS"] = "60"
        self._logf = open(self.log, "w")
        self.proc = subprocess.Popen(argv, cwd=common.ROOT, env=env, stdout=self._logf,
                                     stderr=subprocess.STDOUT, start_new_session=True)
        try:
            health = base._wait_healthz(self.proc, self.port, 1500, self.log)
            self.boot_s = time.time() - t0
            self.identity = health["identity"]
            for i, w in enumerate(traffic.get("warm_requests", [])):
                code, body = common.http(self.port, "/generate", {
                    "prompt_ids": [1 + (args.seed + 7 * j) % (self.vocab - 1)
                                   for j in range(int(w["prompt_len"]))],
                    "max_tokens": int(w["max_tokens"]), "deadline_s": 600}, timeout=900)
                if code != 200:
                    raise Fail(f"warm request {i} {w}: HTTP {code}: {body[:300]}")
            self.warm_s = time.time() - t0 - self.boot_s
        except BaseException:
            self.stop()
            raise


def kernel_self_seconds(trace_dir: str) -> dict:
    """Self time of every Mosaic call in the newest trace under
    ``trace_dir``, summed by kernel (the ``name=`` of its ``pallas_call``:
    the instruction's name without its ``.N``), mean over device planes.
    In a process of its own: reading an xplane file needs jax."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PFX_PLATFORM="cpu")
    p = subprocess.run([common.python(), os.path.abspath(__file__), "--kernel-times", trace_dir],
                       cwd=common.ROOT, env=env, capture_output=True, text=True, timeout=900)
    try:
        return json.loads(p.stdout.strip().splitlines()[-1])
    except (IndexError, ValueError):
        common.say(f"kernel times failed ({p.returncode}): {p.stderr[-600:]}")
        return {}


def _kernel_times_main(trace_dir: str) -> int:
    import trace_reduce

    totals, planes = {}, 0
    for path in trace_reduce.newest_xplanes(trace_dir):
        for plane in trace_reduce.load_xplane(path):
            if not trace_reduce.DEVICE_PLANE.match(plane["name"]):
                continue
            planes += 1
            for line in plane["lines"]:
                if line["name"] != trace_reduce.OPS_LINE:
                    continue
                events = [e for e in line["events"] if e[2] > 0]
                for i, ns in trace_reduce._self_times(events).items():
                    label, cat = trace_reduce.describe(events[i][0])
                    if cat == "pallas":
                        kernel = label.split(" ")[0].split(".")[0]
                        totals[kernel] = totals.get(kernel, 0.0) + ns
    print(json.dumps({k: v / max(1, planes) / 1e9 for k, v in totals.items()}))
    return 0


def run(cell: dict, args, t0: float) -> dict:
    """``serve.run`` with this file's ``Server``."""
    server = Server(cell, args, t0)
    raw, served = None, None
    try:
        raw = server.window(float(args.seconds), profile=args.trace == 1)
        done = [r for r in raw["requests"] if r["phase"] == "window"
                and r.get("status") == 200 and not r.get("error")
                and len(r["tokens"]) == r["max_tokens"]]
        step = max(1, len(done) // base.REFERENCE_SEQUENCES)
        served = [{"idx": r["idx"], "prompt_ids": raw["plan_prompts"][r["idx"]],
                   "tokens": r["tokens"]} for r in done[::step][:base.REFERENCE_SEQUENCES]]
        if args.trace == 2:
            raw["trace_stretch"] = server.trace_stretch()
    finally:
        peak, ref = server.stop(served)
    raw.pop("plan_prompts")
    raw["memory_peak_bytes"], raw["reference"] = peak, ref
    return raw


def judge(cell: dict, raw: dict, args) -> dict:
    res = base.judge(cell, raw, args)
    ctx = res["context"]
    ctx["math"] = cell["config_data"].get("math")
    prof = ((raw.get("trace_stretch") if args.trace == 2 else raw) or {}).get("profile") or {}
    if "counters_at_start" in prof:
        ctx["profile_counters"] = [prof["counters_at_start"], prof["counters_at_stop"]]
    if args.trace and res.get("trace_dir"):
        ctx["kernel_self_s"] = kernel_self_seconds(res["trace_dir"])
        res["info"]["kernel_self_s"] = ctx["kernel_self_s"]
    return res


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--kernel-times":
        sys.exit(_kernel_times_main(sys.argv[2]))
    sys.exit(2)
