"""Slow iterations leave a record (docs/observability.md "Slow iterations"):

  rule      ``telemetry.StallWatch`` judges every kind apart, nothing under
            16 observations, and its median follows a step that got faster
  held      the ONE label of an event, over its six values, from made-up
            observations; the machine's keys are absent without ``/proc``
  loops     a CPU scheduler run under ``cb_step_hang`` and a fit with a
            sleeping loader each leave one event whose ``iter`` / ``step``
            is the open span's argument in a ``ProfileData`` trace;
            counters, record keys and ``/debug/state`` agree with it; sound
            toy runs leave none
  baselines with the trace buffer off ``_iterate`` takes no decision-log
            baseline; with it on the row has the columns it always had
  ring      ``PFX_FLIGHT_RECORDER_CAP`` bounds what the ring keeps
  metrics   the two metric files read a made-up window through readers the
            benchmark has
"""

import json
import os
import sys
import time

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_engine import tiny_cfg  # noqa: E402 — the shared tiny GPT config
from test_goodput import PROMPTS, TINY  # noqa: E402 — the shared tiny serve config
from test_trace_in_run import _host_events  # noqa: E402 — ProfileData alone

from paddlefleetx_tpu.utils import telemetry as T  # noqa: E402

SCHED_BUCKETS = ("device_decode", "device_prefill", "readback", "stream_flush",
                 "host_sched")
TRAIN_BUCKETS = ("data_wait", "put_dispatch", "log_fetch", "log_write", "other")


class _Clock:
    """Made-up observations on a clock of their own: ``feed`` hands the
    watcher one iteration of ``wall`` seconds whose buckets are ``wall``
    split as ``shares`` says."""

    def __init__(self, watch, shares):
        self.watch, self.shares = watch, shares
        self.t = time.monotonic()

    def feed(self, kind, wall, shares=None):
        t0, self.t = self.t, self.t + wall
        return self.watch.observe(
            kind, t0, self.t, tuple(wall * s for s in (shares or self.shares)))


def _sched_watch():
    w = T.StallWatch("sched.iterate", SCHED_BUCKETS, device_wait=("readback",))
    return w, _Clock(w, (0.3, 0.0, 0.5, 0.1, 0.1))


# ---------------------------------------------------------------------------
# rule
# ---------------------------------------------------------------------------


def test_each_kind_is_judged_against_its_own_median():
    w, c = _sched_watch()
    for _ in range(20):
        assert c.feed("decode", 0.010) is None
        assert c.feed("admit:2048", 0.300) is None
    # a 2,048-token prefill among 2,048-token prefills is no stall ...
    assert c.feed("admit:2048", 0.330) is None
    # ... and the same seconds in a decode iteration are
    ev = c.feed("decode", 0.330)
    assert ev is not None and ev["kind"] == "decode" and ev["where"] == "sched.iterate"
    assert ev["median_s"] == pytest.approx(0.010) and ev["excess_s"] == pytest.approx(0.320)
    assert ev["wall_s"] == pytest.approx(0.330)
    assert ev["t1_monotonic_ns"] - ev["t0_monotonic_ns"] == pytest.approx(0.330e9, rel=1e-6)
    assert abs(ev["time_ns"] - time.time_ns()) < 600e9
    # both constants of the rule: past max(A, B x median) only
    assert c.feed("decode", 0.010 + T.STALL_MIN_EXCESS_S - 0.001) is None
    assert c.feed("decode", 0.010 + T.STALL_MIN_EXCESS_S + 0.001) is not None
    long = 2.0 * T.STALL_MIN_EXCESS_S / T.STALL_MEDIAN_SHARE  # B x median = 2 A
    for _ in range(20):
        assert c.feed("step:eval", long) is None
    assert c.feed("step:eval", long * (1 + T.STALL_MEDIAN_SHARE) - 0.001) is None
    assert c.feed("step:eval", long * (1 + T.STALL_MEDIAN_SHARE) + 0.001) is not None


def test_a_kind_under_sixteen_observations_judges_nothing():
    w, c = _sched_watch()
    for _ in range(T.STALL_JUDGE_EVERY - 2):
        assert c.feed("decode", 0.010) is None
    assert c.feed("decode", 5.0) is None  # the 15th: the warm-up's compile
    assert c.feed("decode", 0.010) is None  # the 16th sets the first median
    ev = c.feed("decode", 5.0)
    assert ev is not None and ev["median_s"] == pytest.approx(0.010)
    # a kind seen for the first time starts from nothing again
    assert c.feed("admit:512", 5.0) is None


def test_the_median_follows_a_step_that_got_faster():
    w, c = _sched_watch()
    for _ in range(T.STALL_WINDOW):
        assert c.feed("decode", 1.0) is None
    # 0.45 s is no stall while the last 64 ran a second each ...
    assert c.feed("decode", 0.45) is None
    for _ in range(T.STALL_WINDOW):
        assert c.feed("decode", 0.2) is None
    # ... and is one once they ran 0.2 s: the window forgot the old step
    ev = c.feed("decode", 0.45)
    assert ev is not None and ev["median_s"] == pytest.approx(0.2)
    # recomputed every 16th observation, not every one
    w, c = _sched_watch()
    for _ in range(T.STALL_JUDGE_EVERY):
        c.feed("decode", 0.2)
    st = w._kinds["decode"]
    assert st.median == pytest.approx(0.2)
    for _ in range(T.STALL_JUDGE_EVERY - 1):
        c.feed("decode", 0.1)
        assert st.median == pytest.approx(0.2)
    c.feed("decode", 0.1)
    assert st.n == 2 * T.STALL_JUDGE_EVERY and st.median == pytest.approx(0.15)


# ---------------------------------------------------------------------------
# held
# ---------------------------------------------------------------------------

# the bucket the excess lies in (as shares of the slow iteration's wall),
# what happened meanwhile, the label
HELD_CASES = {
    # a compile event wins over everything, a device wait included
    "compile": dict(where="sched", shares=(0.0, 0.0, 1.0, 0.0, 0.0), compiles=1),
    # collector pauses of half the excess win over the bucket
    "gc": dict(where="sched", shares=(0.0, 0.0, 0.0, 0.0, 1.0), gc_s=0.2),
    "device_wait": dict(where="sched", shares=(0.0, 0.0, 1.0, 0.0, 0.0)),
    "data_wait": dict(where="train", shares=(1.0, 0.0, 0.0, 0.0, 0.0)),
    # a host bucket: the thread's CPU clock stood still, or ran
    "host_off_cpu": dict(where="sched", shares=(0.0, 0.0, 0.0, 0.0, 1.0)),
    "host_on_cpu": dict(where="sched", shares=(0.0, 0.0, 0.0, 0.0, 1.0), cpu_s=0.3),
}


@pytest.mark.parametrize("held", T.STALL_HELD)
def test_held_is_one_label_by_the_written_order(held, monkeypatch):
    case = HELD_CASES[held]
    cpu = [100.0]
    monkeypatch.setattr(T.time, "thread_time", lambda: cpu[0])
    if case["where"] == "train":
        w = T.StallWatch("train.step", TRAIN_BUCKETS, device_wait=("log_fetch",),
                         data_wait=("data_wait",))
        sound = (0.05, 0.05, 0.8, 0.05, 0.05)
    else:
        w = T.StallWatch("sched.iterate", SCHED_BUCKETS, device_wait=("readback",))
        sound = (0.3, 0.0, 0.5, 0.1, 0.1)
    c = _Clock(w, sound)
    for _ in range(20):
        cpu[0] += 0.001
        assert c.feed("k", 0.010) is None
    gc0 = list(T._gc_pauses)
    monkeypatch.setattr(T, "_gc_pauses", [gc0[0] + case.get("gc_s", 0.0),
                                          gc0[1] + (3 if case.get("gc_s") else 0), 0.0])
    monkeypatch.setattr(T, "_compile_events",
                        [T._compile_events[0] + case.get("compiles", 0)])
    cpu[0] += 0.001 + case.get("cpu_s", 0.0)
    # 0.010 s as the sound ones ran, and 0.4 s more in one bucket
    t0, c.t = c.t, c.t + 0.410
    buckets = tuple(0.010 * s + 0.4 * x for s, x in zip(sound, case["shares"]))
    ev = w.observe("k", t0, c.t, buckets)
    assert ev is not None and ev["held"] == held
    assert ev["excess_s"] == pytest.approx(0.4)
    assert ev["grew_most"] == w.buckets[case["shares"].index(1.0)]
    assert ev["compile_events"] == case.get("compiles", 0)
    assert ev["gc_s"] == pytest.approx(case.get("gc_s", 0.0))
    assert ev["gc_collections"] == (3 if case.get("gc_s") else 0)
    assert ev["thread_cpu_s"] == pytest.approx(0.001 + case.get("cpu_s", 0.0))
    assert set(ev["buckets"]) == set(w.buckets)
    # the pure rule says the same of the same numbers
    assert T.stall_held(
        excess_s=ev["excess_s"], compile_events=ev["compile_events"], gc_s=ev["gc_s"],
        bucket=ev["grew_most"], thread_cpu_grew_s=ev["thread_cpu_s"] - 0.001,
        device_wait=w.device_wait, data_wait=w.data_wait) == held


def test_the_gc_hook_sums_pauses_process_wide():
    import gc

    T.StallWatch("t", ("a",))  # installs the one hook, once
    T.StallWatch("t", ("a",))
    assert gc.callbacks.count(T._gc_hook) == 1
    s0, n0 = T._gc_pauses[0], T._gc_pauses[1]
    gc.collect()
    assert T._gc_pauses[1] == n0 + 1 and T._gc_pauses[0] > s0


@pytest.mark.parametrize("proc", ["there", "absent"])
def test_machine_keys_are_absent_where_their_file_is(proc, tmp_path, monkeypatch):
    if proc == "absent":
        monkeypatch.setattr(T, "_PROC", str(tmp_path / "no-proc"))
    w, c = _sched_watch()
    for _ in range(20):
        c.feed("decode", 0.010)
    machine = c.feed("decode", 0.5)["machine"]
    from_files = {"runq_wait_s", "iowait_s", "steal_s"}
    if proc == "absent":
        assert not from_files & set(machine)
    elif os.path.exists("/proc/thread-self/schedstat"):
        assert from_files <= set(machine)
        assert all(machine[k] >= 0.0 for k in from_files)
    # the baseline's age is said beside them whatever there is to read
    assert 0.0 <= machine["since_s"] <= T.STALL_BASELINE_S + 0.5 + 0.011
    assert {"invol_ctx_switches", "major_faults", "loadavg_1m"} <= set(machine)


# ---------------------------------------------------------------------------
# loops
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def server():
    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.core.serving import GenerationServer
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import AttrDict, process_configs

    cfg = process_configs(AttrDict.from_nested(TINY), num_devices=jax.device_count())
    mesh = init_dist_env(cfg)
    return GenerationServer(cfg, mesh, build_module(cfg))


def _serve(server, tokens=48, trace_dir=None):
    from paddlefleetx_tpu.core.continuous_batching import (
        ContinuousScheduler, PagedDecodeEngine)
    from paddlefleetx_tpu.utils.profiler import start_trace

    eng = PagedDecodeEngine(server, max_batch=4)
    sched = ContinuousScheduler(eng, max_depth=16)
    sched.warmup([4])
    if trace_dir:
        start_trace(trace_dir, python_tracer=False)
    try:
        sched.start()
        futs = [sched.submit([p], tokens, deadline_s=120) for p in PROMPTS]
        outs = [f.result(timeout=300)[0] for f in futs]
        dbg = sched.debug_state()
        assert sched.shutdown(timeout=60)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    return sched, outs, dbg


def _stall_counters(where):
    reg = T.get_registry()
    return {held: (reg.value("pfx_stall_events_total", where=where, held=held),
                   reg.value("pfx_stall_seconds_total", where=where, held=held))
            for held in T.STALL_HELD}


def test_a_hung_scheduler_step_leaves_one_event_that_names_its_span(
        server, tmp_path, monkeypatch):
    from paddlefleetx_tpu.utils import resilience

    # a shared CPU under six test workers hiccups by tenths of a second:
    # the floor above them, the injected sleep well above the floor
    monkeypatch.setattr(T, "STALL_MIN_EXCESS_S", 0.5)
    monkeypatch.setenv("PFX_FAULT", "cb_step_hang:30")
    monkeypatch.setenv("PFX_FAULT_HANG_S", "1.2")
    resilience.reset_fault_state()
    before = _stall_counters("sched.iterate")
    ring0 = len([e for e in T.get_flight_recorder().events()
                 if e.get("event") == "pfx.stall"])
    log_dir = str(tmp_path / "trace")
    sched, outs, dbg = _serve(server, trace_dir=log_dir)
    assert [len(o) for o in outs] == [48] * 4
    summary = sched._stall.summary()
    assert summary["events"] == 1, summary
    ev = summary["last"][0]
    assert ev["where"] == "sched.iterate" and ev["kind"] == "decode"
    assert ev["held"] == "host_off_cpu" and ev["grew_most"] == "host_sched"
    assert ev["excess_s"] == pytest.approx(1.2, abs=0.3)
    assert ev["thread_cpu_s"] < 0.5 * ev["excess_s"]
    assert ev["buckets"]["host_sched"] >= 1.2
    assert {"active", "admitted", "finished", "width_bucket", "waiting"} <= set(ev)
    assert 1 <= ev["active"] <= 4 and ev["admitted"] == 0 and ev["waiting"] == 0
    # the event finds its span: the same number, and the same interval on
    # the profiler's clock as on the event's
    spans = [e for e in _host_events(log_dir) if e[1] == "pfx.sched.iterate"]
    mine = [s for s in spans if s[4]["iter"] == ev["iter"]]
    assert len(mine) == 1
    assert mine[0][3] - mine[0][2] == pytest.approx(ev["wall_s"] * 1e9, rel=0.05)
    assert max(spans, key=lambda s: s[3] - s[2]) is mine[0]
    # counters, /debug/state and the ring say what the event says
    after = _stall_counters("sched.iterate")
    grew = {h: (after[h][0] - before[h][0], after[h][1] - before[h][1])
            for h in T.STALL_HELD}
    assert grew.pop("host_off_cpu") == pytest.approx((1, ev["excess_s"]))
    assert all(v == (0, 0) for v in grew.values())
    # /debug/state was read before the shutdown: the same event
    assert dbg["stalls"]["events"] == 1
    assert dbg["stalls"]["seconds"] == pytest.approx(ev["excess_s"])
    assert dbg["stalls"]["last"][0]["iter"] == ev["iter"]
    ring = [e for e in T.get_flight_recorder().events() if e.get("event") == "pfx.stall"]
    assert len(ring) == ring0 + 1 and ring[-1]["iter"] == ev["iter"]
    assert ring[-1]["held"] == "host_off_cpu"


def test_a_sound_scheduler_run_leaves_no_event(server, monkeypatch):
    monkeypatch.delenv("PFX_FAULT", raising=False)
    monkeypatch.setattr(T, "STALL_MIN_EXCESS_S", 2.0)  # a shared CPU's hiccups
    sched, outs, dbg = _serve(server)
    assert [len(o) for o in outs] == [48] * 4
    assert sched._stall.summary() == {"events": 0, "seconds": 0.0, "last": []}
    assert dbg["stalls"] == {"events": 0, "seconds": 0.0, "last": []}
    # every iteration was observed under a kind of its own
    kinds = sched._stall._kinds
    assert sum(k.n for k in kinds.values()) == sched._iter_counter
    assert kinds["decode"].n >= T.STALL_JUDGE_EVERY and kinds["decode"].median > 0
    admits = [k for k in kinds if k.startswith("admit:")]
    assert admits and all(int(k.split(":")[1].split("+")[0]) % 16 == 0 for k in admits)
    # the iteration after an admission waits for its prefill: a kind apart
    assert any(k.startswith("after_admit:") for k in kinds)


class _SleepAt:
    """A loader wrapper that sleeps before handing out given batches."""

    def __init__(self, inner, plan):
        self.inner, self.plan, self.asked = inner, dict(plan), 0

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def __iter__(self):
        self._it = iter(self.inner)
        return self

    def __next__(self):
        self.asked += 1
        time.sleep(self.plan.get(self.asked, 0.0))
        return next(self._it)


def _fit(tmp_path, steps, sleep_plan=None, trace=None):
    from paddlefleetx_tpu.core.engine import Engine
    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.data.builders import build_dataloader
    from paddlefleetx_tpu.parallel.env import init_dist_env

    cfg = tiny_cfg(tmp_path)
    cfg.Engine.metrics_file = str(tmp_path / "metrics.jsonl")
    cfg.Engine.logging_freq = 1
    cfg.Engine.max_steps = steps
    if trace:
        cfg["Profiler"] = {"enable": True, "scheduler": list(trace[1]),
                           "log_dir": trace[0], "summary": False}
    mesh = init_dist_env(cfg)
    module = build_module(cfg)
    loader = build_dataloader(cfg, "Train")
    with mesh:
        engine = Engine(cfg, module, mesh)
        engine.fit(_SleepAt(loader, sleep_plan or {}))
    with open(cfg.Engine.metrics_file) as f:
        records = [r for r in map(json.loads, f) if "loss" in r]
    return engine, records


def test_a_sleeping_loader_leaves_one_event_that_names_its_step(
        tmp_path, devices8, monkeypatch):
    monkeypatch.setattr(T, "STALL_MIN_EXCESS_S", 0.5)
    before = _stall_counters("train.step")
    log_dir = str(tmp_path / "trace")
    engine, records = _fit(tmp_path, 30, sleep_plan={24: 1.2}, trace=(log_dir, (22, 26)))
    summary = engine._stall.summary()
    assert summary["events"] == 1, summary
    ev = summary["last"][0]
    assert ev["where"] == "train.step" and ev["kind"] == "step" and ev["step"] == 24
    assert ev["held"] == "data_wait" and ev["grew_most"] == "data_wait"
    assert ev["excess_s"] == pytest.approx(1.2, abs=0.3)
    assert ev["buckets"]["data_wait"] >= 1.2 and set(ev["buckets"]) == set(TRAIN_BUCKETS)
    assert ev["consumed_samples"] == 24 * 16
    # the same number the open span carries, the same interval
    spans = [e for e in _host_events(log_dir) if e[1] == "pfx.train.step"]
    mine = [s for s in spans if s[4]["step_num"] == ev["step"]]
    assert len(mine) == 1
    assert mine[0][3] - mine[0][2] == pytest.approx(ev["wall_s"] * 1e9, rel=0.05)
    # cumulative record keys beside host_gap_s: a record carries the slow
    # steps before it
    assert [r["stall_events"] for r in records] == [0] * 24 + [1] * 6
    assert all(r["stall_s"] == 0.0 for r in records[:24])
    assert all(r["stall_s"] == pytest.approx(ev["excess_s"], abs=1e-3) for r in records[24:])
    after = _stall_counters("train.step")
    assert after["data_wait"][0] - before["data_wait"][0] == 1
    assert after["data_wait"][1] - before["data_wait"][1] == pytest.approx(ev["excess_s"])
    assert sum(v[0] for v in after.values()) - sum(v[0] for v in before.values()) == 1
    # what record_share makes of those records: the excess over the window
    sys.path.insert(0, os.path.join(REPO, "pfx_bench"))  # noqa: E10 — a directory
    import common
    from readers import record_share  # noqa: F401 — importable as the harness loads it

    d = common.load_layer_metric("engine.stall_share")
    share = record_share.read(
        {"engine_records": records, "engine_base_record": records[2], "window_s": 12.0},
        **d["args"])
    assert share == pytest.approx(100.0 * ev["excess_s"] / 12.0, rel=1e-3)


def test_a_sound_fit_leaves_no_event(tmp_path, devices8, monkeypatch):
    monkeypatch.setattr(T, "STALL_MIN_EXCESS_S", 2.0)  # a shared CPU's hiccups
    engine, records = _fit(tmp_path, 24)
    assert engine._stall.summary() == {"events": 0, "seconds": 0.0, "last": []}
    assert all(r["stall_events"] == 0 and r["stall_s"] == 0.0 for r in records)
    assert engine._stall._kinds["step"].n == 24


# ---------------------------------------------------------------------------
# baselines
# ---------------------------------------------------------------------------

ROW_COLUMNS = [
    "iter", "t", "admitted", "evicted", "shed", "finished", "active", "width_bucket",
    "blocks_free", "blocks_delta", "spec_proposed", "spec_accepted", "prefix_hits",
    "prefix_hit_tokens", "prefix_evictions", "chunks", "spills", "readmits",
    "spill_discards", "migrate_adopted", "tok_admitted", "tok_delivered",
    "tok_evicted_lost", "tok_preempt_refunded", "tok_shed_after_admit", "preempted",
]


@pytest.mark.parametrize("buffer", ["off", "on"])
def test_decision_log_baselines_are_taken_only_with_the_trace_buffer_on(
        buffer, server, monkeypatch):
    from paddlefleetx_tpu.core.continuous_batching import (
        ContinuousScheduler, PagedDecodeEngine)
    from paddlefleetx_tpu.utils import tracing

    monkeypatch.delenv("PFX_FAULT", raising=False)
    monkeypatch.setattr(
        tracing, "_buffer", tracing.TraceBuffer(sample=0.0 if buffer == "off" else 1.0))
    taken = []
    take = ContinuousScheduler._decision_baselines
    monkeypatch.setattr(ContinuousScheduler, "_decision_baselines",
                        lambda self: taken.append(1) or take(self))
    eng = PagedDecodeEngine(server, max_batch=4)
    sched = ContinuousScheduler(eng, max_depth=16)
    sched.start()
    futs = [sched.submit([p], 6, deadline_s=120) for p in PROMPTS]
    outs = [f.result(timeout=300)[0] for f in futs]
    assert sched.shutdown(timeout=60)
    rows = list(sched.decision_log)
    if buffer == "off":
        assert taken == [] and rows == []
        return
    assert len(taken) == len(rows) == sched._iter_counter
    for row in rows:
        assert [k for k in row if k not in ("tenants", "preempted_tenants")] == ROW_COLUMNS
    assert [r["iter"] for r in rows] == list(range(1, len(rows) + 1))
    # the columns are this iteration's own deltas: they fold to the totals
    assert sum(r["admitted"] for r in rows) == int(sched.stats["prefill_admits"]) == 4
    assert sum(r["finished"] for r in rows) == 4
    assert sum(r["tok_admitted"] for r in rows) == sched._tok_ledger["admitted"]
    assert sum(r["tok_delivered"] for r in rows) == sum(len(o) for o in outs)
    assert sum(r["blocks_delta"] for r in rows) == 0  # every block came back
    assert sum(sum(r.get("tenants", {}).values()) for r in rows) == 4


# ---------------------------------------------------------------------------
# ring
# ---------------------------------------------------------------------------


def test_flight_recorder_cap_keeps_the_last_stall_events(monkeypatch):
    monkeypatch.setenv("PFX_FLIGHT_RECORDER_CAP", "2")
    ring = T.FlightRecorder()
    monkeypatch.setattr(T, "_flight", ring)
    w, c = _sched_watch()
    for _ in range(20):
        c.feed("decode", 0.010)
    for i in range(1, 4):
        w.publish(c.feed("decode", 0.5), iter=100 + i)
    kept = ring.events()
    assert [e["iter"] for e in kept] == [102, 103]
    assert all(e["event"] == "pfx.stall" and e["seq"] for e in kept)
    assert w.summary()["events"] == 3  # the watcher's own sums forget nothing
    monkeypatch.setenv("PFX_FLIGHT_RECORDER_CAP", "0")
    with pytest.raises(ValueError, match="PFX_FLIGHT_RECORDER_CAP"):
        T.FlightRecorder()


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def test_the_two_metric_files_read_a_window_through_readers_that_are_there():
    sys.path.insert(0, os.path.join(REPO, "pfx_bench"))  # noqa: E10 — a directory
    import common
    import run

    eng = common.load_layer_metric("engine.stall_share")
    assert (eng["layer"], eng["source"], eng["better"], eng["moves"], eng["unit"]) == (
        "train engine", "program_span", "lower", "train_tokens_per_s", "%")
    read = run.load_module("readers", eng["reader"]).read
    recs = [{"step": 3, "stall_s": 0.0}, {"step": 120, "stall_s": 3.2}]
    ctx = {"engine_records": recs[1:], "engine_base_record": recs[0], "window_s": 51.0}
    assert read(ctx, **eng["args"]) == pytest.approx(6.27, abs=0.01)
    # a sound window reads 0.0, a program without the key nothing
    assert read(dict(ctx, engine_records=[{"step": 120, "stall_s": 0.0}]), **eng["args"]) == 0.0
    assert read(dict(ctx, engine_records=[{"step": 120}]), **eng["args"]) is None

    sch = common.load_layer_metric("sched.stall_share")
    assert (sch["layer"], sch["source"], sch["better"], sch["moves"], sch["unit"]) == (
        "serving scheduler", "program_counter", "lower", "itl_mean_ms", "%")
    read = run.load_module("readers", sch["reader"]).read
    delta = {
        'pfx_stall_seconds_total{held="host_off_cpu",where="sched.iterate"}': 0.3,
        'pfx_stall_seconds_total{held="device_wait",where="sched.iterate"}': 0.2,
        'pfx_stall_seconds_total{held="data_wait",where="train.step"}': 9.0,
        "pfx_sched_wall_seconds_total": 60.0,
        'pfx_sched_time_seconds_total{bucket="idle"}': 10.0,
        'pfx_sched_time_seconds_total{bucket="host_sched"}': 20.0,
    }
    assert read({"scrape_delta": delta}, **sch["args"]) == pytest.approx(1.0)
    # the same denominator as sched.admit_host_share
    assert sch["args"]["denominator"] == common.load_layer_metric(
        "sched.admit_host_share")["args"]["denominator"]
    sound = {k: v for k, v in delta.items() if not k.startswith("pfx_stall_seconds_total")}
    assert read({"scrape_delta": sound}, **sch["args"]) == 0.0
