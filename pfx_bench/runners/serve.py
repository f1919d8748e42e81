"""Serve runner (stdlib; the parent IS the load generator).  The child is
``tools/serve.py``'s own ``main`` (through serve_child.py, which adds a
peak-memory file and the reference check) on a free port with the cell's
flags, random weights from ``--seed`` and ``--warmup-buckets`` equal to the
traffic's own prompt buckets.  Wait for /healthz, send the lead-in at the
cell's rate (set-up: the batch is full when the window opens), scrape
/metrics, run the window, scrape again, stop sending, drain, hand the child
a few served sequences to hold against the plain reference, stop the child.
With ``--trace 2`` a short stretch of the same mix follows the drained
window, on the same server, with one ``POST /admin/profile`` in its middle:
the device trace comes from there, every other number from the window."""

import json
import os
import re
import shutil
import subprocess
import sys
import threading
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import common  # noqa: E402
import loadgen  # noqa: E402
import stats  # noqa: E402
from common import Fail  # noqa: E402


def _pad_multiple(overrides) -> int:
    """The server's prompt-bucket width, from the cell's Generation override
    (64 is the program's default)."""
    for o in overrides:
        m = re.search(r"pad_to_multiple:\s*(\d+)", o)
        if m:
            return int(m.group(1))
    return 64


def _wait_healthz(proc, port, timeout, log):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if proc.poll() is not None:
            raise Fail(f"serve.py exited {proc.returncode} before /healthz "
                       f"(log: {log})\n{common.tail(log)}")
        try:
            code, body = common.http(port, "/healthz", timeout=5)
            if code == 200 and json.loads(body).get("ok"):
                return json.loads(body)
        except (OSError, ValueError):
            pass
        time.sleep(0.5)
    raise Fail(f"no /healthz within {timeout:.0f}s (log: {log})\n{common.tail(log)}")


class Sidecar(threading.Thread):
    """The few blocking HTTP calls beside the open loop: /metrics at the
    window's two edges and (traced run) one POST /admin/profile in
    mid-window."""

    def __init__(self, port, w0, w1, profile_s, profile_body=None):
        super().__init__(daemon=True)
        self.port, self.w0, self.w1, self.profile_s = port, w0, w1, profile_s
        self.profile_body = profile_body or {"top": 5}
        self.before = self.after = None
        self.profile, self.errors = None, []

    def _scrape(self):
        code, body = common.http(self.port, "/metrics", timeout=20)
        if code != 200:
            raise OSError(f"/metrics HTTP {code}")
        return common.parse_metrics(body)

    def _profile(self):
        try:
            code, body = common.http(self.port, "/admin/profile",
                                     {"seconds": self.profile_s, **self.profile_body},
                                     timeout=600)
            self.profile = json.loads(body) if code == 200 else {"error": body[:300]}
        except (OSError, ValueError) as e:
            self.profile = {"error": repr(e)}

    def run(self):
        try:
            time.sleep(max(0.0, self.w0 - time.monotonic()))
            self.before = self._scrape()
            prof = None
            if self.profile_s:
                at = self.w0 + max(1.0, (self.w1 - self.w0 - self.profile_s) / 2)
                prof = threading.Timer(max(0.0, at - time.monotonic()), self._profile)
                prof.daemon = True
                prof.start()
            time.sleep(max(0.0, self.w1 - time.monotonic()))
            self.after = self._scrape()
            if prof is not None:
                prof.join(timeout=600)
        except (OSError, ValueError) as e:
            self.errors.append(repr(e))


class Server:
    """tools/serve.py for one cell, booted and warmed; ``window`` replays one
    plan against it (the sweep replays several), ``stop`` ends it."""

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
        self.buckets = loadgen.prompt_buckets(traffic, _pad_multiple(overrides))
        out = common.out_dir(cell["name"], args.seed, args.trace)
        self.log = os.path.join(out, "serve_child.log")
        self.mem_path = os.path.join(out, "serve_memory.json")
        self.served_path = os.path.join(out, "served_sequences.json")
        self.ref_path = os.path.join(out, "serve_reference.json")
        for path in (self.mem_path, self.served_path, self.ref_path):
            if os.path.exists(path):
                os.unlink(path)
        self.port = common.free_port()
        argv = [common.python(), os.path.join(BENCH, "runners", "serve_child.py"),
                self.mem_path, self.served_path, self.ref_path,
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
            health = _wait_healthz(self.proc, self.port, 1000, self.log)
            self.boot_s = time.time() - t0
            self.identity = health["identity"]
            # decode-step compile families the server's own warm-up misses
            # (it warms one block-table width; lighter batches use narrower
            # ones): one request each, alone in the batch, before the lead-in
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

    def window(self, seconds: float, rate: float = None, profile: bool = False,
               profile_body: dict = None) -> dict:
        traffic, port = self.traffic, self.port
        plan = loadgen.build_plan(traffic, self.args.seed, seconds, self.vocab, rate=rate)
        lead = plan["lead_in_s"]
        m0 = time.monotonic() + 0.2          # start of the lead-in
        w0, w1 = m0 + lead, m0 + lead + seconds
        raw = {"port": port, "buckets": self.buckets, "vocab": self.vocab,
               "n_requests": len(plan["requests"]), "rate_rps": plan["rate_rps"],
               "boot_s": self.boot_s, "warm_s": self.warm_s, "identity": self.identity,
               "t_window_start": time.time() + (w0 - time.monotonic())}
        profile_s = 0.0
        if profile:
            profile_s = min(float(traffic.get("trace_s", 4.0)), max(0.5, seconds - 2.0))
        side = Sidecar(port, w0, w1, profile_s, profile_body)
        side.start()
        loop = loadgen.OpenLoop(port, plan, float(traffic["deadline_s"]))
        loop.run(m0, stop_sending_at=w1, give_up_at=w1 + float(traffic["drain_s"]))
        raw["drained_s"] = time.monotonic() - w1
        side.join(timeout=700)
        code, body = common.http(port, "/metrics", timeout=20)
        raw["final_metrics"] = common.parse_metrics(body) if code == 200 else {}
        try:
            state = json.loads(common.http(port, "/debug/state", timeout=20)[1])
            raw["compile_events_tail"] = state.get("compile_events", [])[-6:]
        except (OSError, ValueError):
            raw["compile_events_tail"] = []
        raw.update(w0=w0, w1=w1, requests=loop.results(),
                   plan_prompts=[r["prompt_ids"] for r in plan["requests"]],
                   before=side.before, after=side.after, profile=side.profile,
                   sidecar_errors=side.errors)
        return raw

    def trace_stretch(self) -> dict:
        """--trace 2, after the window has drained: wait until the server
        is quiet, start and stop its profiler once (that trace is thrown
        away: the first start costs most), then offer a short stretch of
        the same mix with one capture in its middle.  The server neither
        parses the trace nor runs the Python call tracer (TRACE_BODY)."""
        if not self.quiet():
            raise Fail("the server did not go quiet after the window")
        code, body = common.http(self.port, "/admin/profile",
                                 {"seconds": 0.2, **TRACE_BODY}, timeout=600)
        if code != 200:
            raise Fail(f"the profiler's first start: HTTP {code}: {body[:300]}")
        shutil.rmtree(json.loads(body)["trace_dir"], ignore_errors=True)
        raw = self.window(float(self.traffic.get("trace_s", 4.0)) + 2.0,
                          profile=True, profile_body=TRACE_BODY)
        return {k: raw[k] for k in ("w0", "w1", "requests", "profile", "sidecar_errors",
                                    "drained_s")}

    def quiet(self, timeout: float = 180.0) -> bool:
        """Wait until the queue and the running batch are empty."""
        t_end = time.time() + timeout
        while time.time() < t_end:
            m = common.parse_metrics(common.http(self.port, "/metrics", timeout=20)[1])
            if not m.get("pfx_queue_depth") and not m.get("pfx_batch_occupancy"):
                return True
            time.sleep(1.0)
        return False

    def stop(self, served=None):
        """Stop the child; -> (peak memory bytes, reference verdict).  With
        ``served`` (a few window requests as the server answered them) the
        child, once it has drained, teacher-forces them through the plain
        reference with the weights it served from, before it exits."""
        if served:
            with open(self.served_path, "w") as f:
                json.dump(served, f)
        common.stop_child(self.proc, grace=300 if served else 60)
        self._logf.close()
        peak, ref = 0, None
        try:
            with open(self.mem_path) as f:
                peak = int(json.load(f)["memory_peak_bytes"])
        except (OSError, ValueError):
            pass
        if served:
            try:
                with open(self.ref_path) as f:
                    ref = json.load(f)
            except (OSError, ValueError):
                ref = {"ok": False, "error": f"the child wrote no verdict\n{common.tail(self.log, 12)}"}
        return peak, ref


REFERENCE_SEQUENCES = 4  # served sequences held against the reference per run
# /admin/profile body of the --trace 2 captures: the trace is reduced by the
# parent afterwards, and the program's pfx.* spans name the gaps
TRACE_BODY = {"summary": False, "python_tracer": False}


def run(cell: dict, args, t0: float) -> dict:
    server = Server(cell, args, t0)
    raw, served = None, None
    try:
        raw = server.window(float(args.seconds), profile=args.trace == 1)
        done = [r for r in raw["requests"] if r["phase"] == "window"
                and r.get("status") == 200 and not r.get("error")
                and len(r["tokens"]) == r["max_tokens"]]
        step = max(1, len(done) // REFERENCE_SEQUENCES)
        served = [{"idx": r["idx"], "prompt_ids": raw["plan_prompts"][r["idx"]],
                   "tokens": r["tokens"]} for r in done[::step][:REFERENCE_SEQUENCES]]
        if args.trace == 2:
            raw["trace_stretch"] = server.trace_stretch()
    finally:
        peak, ref = server.stop(served)
    raw.pop("plan_prompts")
    raw["memory_peak_bytes"], raw["reference"] = peak, ref
    return raw


def judge(cell: dict, raw: dict, args) -> dict:
    notes = []
    w0, w1 = raw["w0"], raw["w1"]
    reqs = raw["requests"]
    window = [r for r in reqs if r["phase"] == "window"]
    vocab = raw["vocab"]

    def ok(r):
        return (r.get("sent") and r.get("status") == 200 and not r.get("error")
                and len(r["tokens"]) == r["max_tokens"] and r.get("contiguous")
                and all(isinstance(t, int) and 0 <= t < vocab for t in r["tokens"]))

    bad = [r for r in window if not ok(r)]
    lead_bad = [r for r in reqs if r["phase"] == "lead" and not ok(r)]
    for r in (bad + lead_bad)[:5]:
        notes.append(f"request {r['idx']} ({r['phase']}, prompt {r['prompt_len']}, "
                     f"max_tokens {r['max_tokens']}): status {r.get('status')}, "
                     f"{len(r['tokens'])} tokens, error {r.get('error')}")
    if len(bad) + len(lead_bad) > 5:
        notes.append(f"... {len(bad)} window and {len(lead_bad)} lead-in requests failed")
    ttft, gaps, late, longest = [], [], [], []
    for r in window:
        if r.get("sent_at") is not None:
            late.append((r["sent_at"] - r["due"]) * 1e3)
        if r["frames"]:
            ttft.append((r["frames"][0][0] - r["due"]) * 1e3)
            times = [t for t, _ in r["frames"]]
            gaps += [(b - a) * 1e3 for a, b in zip(times, times[1:])]
            longest += [[round(a - w0, 3), round((b - a) * 1e3, 1), r["idx"]]
                        for a, b in zip(times, times[1:]) if b - a >= 0.5]
    tokens_in_window = sum(n for r in reqs for t, n in r["frames"] if w0 <= t < w1)
    seconds = w1 - w0
    values = {}
    if ttft:
        values["ttft_p50_ms"] = stats.percentile(ttft, 50)
        values["ttft_p95_ms"] = stats.percentile(ttft, 95)
    if gaps:
        values["itl_mean_ms"] = sum(gaps) / len(gaps)
        for q in (50, 90, 95, 99):
            values[f"itl_p{q}_ms"] = stats.percentile(gaps, q)
    if tokens_in_window:
        values["serve_tokens_per_s"] = tokens_in_window / seconds

    before, after, final = raw.get("before"), raw.get("after"), raw.get("final_metrics") or {}
    delta = None
    if before is None or after is None:
        notes.append(f"/metrics was not scraped at the window's edges: {raw['sidecar_errors']}")
    else:
        delta = {k: after[k] - before.get(k, 0.0) for k in after}
        compiles = common.metric_sum(delta, "pfx_compile_events_total")
        if compiles:
            notes.append(f"{compiles:.0f} compile event(s) inside the window; newest: "
                         f"{json.dumps(raw.get('compile_events_tail'))[:1500]}")
    if final:
        admitted = common.metric_sum(final, "pfx_token_ledger_total", disposition="admitted")
        booked = (common.metric_sum(final, "pfx_token_ledger_total") - admitted
                  + common.metric_sum(final, "pfx_token_ledger_in_flight"))
        if admitted != booked or admitted <= 0:
            notes.append(f"token ledger does not close: admitted {admitted}, booked {booked}")
    else:
        notes.append("no final /metrics scrape")
    ident = raw["identity"]
    want = "cpu" if args.rehearse else "tpu"
    if ident["platform"] != want:
        notes.append(f"/healthz says platform {ident['platform']}, not {want}")
    if raw["sidecar_errors"]:
        notes.append(f"sidecar errors: {raw['sidecar_errors'][:3]}")
    ref = raw.get("reference")  # absent in the knee sweep, which judges no tokens
    if "reference" in raw and not (ref and ref.get("ok")):
        notes.append(f"served tokens off the plain reference: {json.dumps(ref)[:1500]}")
    trace_dir, stretch_info = None, None
    if args.trace:
        stretch = raw.get("trace_stretch") or {}
        prof = (stretch if args.trace == 2 else raw).get("profile") or {}
        trace_dir = prof.get("trace_dir")
        if not trace_dir:
            notes.append(f"/admin/profile gave no trace: {prof}")
        if args.trace == 2:
            # nothing of the traced stretch is judged but that it was served
            s_reqs = stretch.get("requests", [])
            s_bad = [r for r in s_reqs if not ok(r)]
            for r in s_bad[:3]:
                notes.append(f"traced stretch: request {r['idx']} ({r['phase']}): status "
                             f"{r.get('status')}, {len(r['tokens'])} tokens, "
                             f"error {r.get('error')}")
            if stretch.get("sidecar_errors"):
                notes.append(f"traced stretch: sidecar errors: {stretch['sidecar_errors'][:3]}")
            s_gaps = [(b - a) * 1e3 for r in s_reqs
                      for (a, _), (b, _) in zip(r["frames"], r["frames"][1:])]
            stretch_info = {
                "requests": len(s_reqs), "failed": len(s_bad),
                "itl_mean_ms": sum(s_gaps) / len(s_gaps) if s_gaps else None,
                "gaps_over_500ms": sum(1 for g in s_gaps if g >= 500.0),
                "capture_s": prof.get("seconds"),
            }
    half = (w0 + w1) / 2
    first = [(r["frames"][0][0] - r["due"]) * 1e3 for r in window
             if r["frames"] and r["due"] < half]
    second = [(r["frames"][0][0] - r["due"]) * 1e3 for r in window
              if r["frames"] and r["due"] >= half]
    info = {
        "rate_rps": raw["rate_rps"], "requests_due_in_window": len(window),
        "completed": len(window) - len(bad), "lead_in_failed": len(lead_bad),
        **values,
        "ttft_p50_first_half_ms": stats.percentile(first, 50),
        "ttft_p50_second_half_ms": stats.percentile(second, 50),
        "ttft_p95_first_half_ms": stats.percentile(first, 95),
        "ttft_p95_second_half_ms": stats.percentile(second, 95),
        "itl_samples": len(gaps), "ttft_samples": len(ttft),
        # a stall of the server shows as one long gap in every live row at the
        # same offset: [seconds into the window, gap ms, request] of gaps >= 0.5 s
        "gaps_over_500ms": sorted(longest)[:16],
        "loadgen_late_p95_ms": stats.percentile(late, 95),
        "boot_s": raw["boot_s"], "drained_s": raw["drained_s"],
        "prompt_buckets": raw["buckets"], "reference": ref,
    }
    if stretch_info is not None:
        info["trace_stretch"] = stretch_info
    if delta is not None:
        info["sched_time_delta_s"] = {
            k.split('"')[1]: round(v, 3) for k, v in delta.items()
            if k.startswith("pfx_sched_time_seconds_total")}
        info["host_gap_delta_s"] = round(
            common.metric_sum(delta, "pfx_sched_host_gap_seconds_total"), 3)
    return {
        "correct": not notes and bool(window),
        "attempted": len(window), "failed": len(bad),
        "values": values, "notes": notes, "info": info,
        "context": {"scrape_delta": delta, "window_s": seconds, "chips": int(cell["chips"]),
                    "seq_len": int(cell["config_data"]["model"]["max_position_embeddings"])},
        "t_window_start": raw["t_window_start"],
        "device": {"platform": ident["platform"], "kind": ident["device_kind"],
                   "count": ident["device_count"],
                   "memory_peak_bytes": raw["memory_peak_bytes"]},
        "trace_dir": trace_dir,
    }
