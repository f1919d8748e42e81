"""Tracing inside the measuring run (docs/observability.md "On-demand
profiling" + "Goodput ledger"):

  hook      ``ProfilerHook.arm`` starts and stops a trace in a running fit,
            twice in one process; the config block is the same path; an
            unarmed hook starts nothing
  spans     ``telemetry.ledger_span`` books a span's seconds into its
            ledger bucket (an exception inside still books them), and with
            a profiler session open the ``pfx.sched.*`` / ``pfx.train.*``
            spans are in the host plane, children inside their parents
  counters  the decode work counters of a toy continuous run against a
            hand count; the train loop's ``host_gap_s`` / ``log_fetch_s`` /
            ``log_write_s`` against its wall clock
  capture   ``capture_profile(summary=False)`` parses nothing;
            ``device_host_split`` is a union, not a sum
  kernels   every ``pallas_call`` carries its ``pfx_*`` name into the
            lowered program
  harness   the benchmark's window loader arms the hook after its window
            has closed, and its ``record_share`` reader reads the new keys
"""

import glob
import gzip
import importlib.util
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_engine import tiny_cfg  # noqa: E402 — the shared tiny GPT config
from test_goodput import PROMPTS, TINY  # noqa: E402 — the shared tiny serve config


def _host_events(log_dir):
    """[(line, name, start_ns, end_ns, stats)] of the newest trace's host
    planes, through jax.profiler.ProfileData alone."""
    from jax.profiler import ProfileData

    runs = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*")))
    assert runs, f"no profile run under {log_dir}"
    out = []
    for path in glob.glob(os.path.join(runs[-1], "*.xplane.pb")):
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("pfx."):
                        out.append((line.name, ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return out


def _inside(events, child, parent):
    """Every ``child`` span lies inside some ``parent`` span of its line
    (a trace that starts and stops in mid-pass holds children whose parent
    opened before it or closed after it: those outside the recorded
    parents' reach are left aside)."""
    parents = [e for e in events if e[1] == parent]
    assert parents, f"no {parent} span"
    first, last = min(p[2] for p in parents), max(p[3] for p in parents)
    kids = [e for e in events if e[1] == child and first <= e[2] and e[3] <= last]
    assert kids, f"no {child} span"
    for line, _, a, b, _ in kids:
        assert any(p[0] == line and p[2] <= a and b <= p[3] for p in parents), (child, parent)


def _fit(tmp_path, loader_wrap=None, **engine_cfg):
    from paddlefleetx_tpu.core.engine import Engine
    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.data.builders import build_dataloader
    from paddlefleetx_tpu.parallel.env import init_dist_env

    cfg = tiny_cfg(tmp_path)
    cfg.Engine.metrics_file = str(tmp_path / "metrics.jsonl")
    cfg.Engine.logging_freq = 1
    for k, v in engine_cfg.items():
        cfg[k] = v
    mesh = init_dist_env(cfg)
    module = build_module(cfg)
    loader = build_dataloader(cfg, "Train")
    with mesh:
        engine = Engine(cfg, module, mesh)
        t0 = time.monotonic()
        engine.fit(loader_wrap(loader, engine) if loader_wrap else loader)
        wall = time.monotonic() - t0
    with open(cfg.Engine.metrics_file) as f:
        records = [r for r in map(json.loads, f) if "loss" in r]
    return engine, records, wall


class _ArmAt:
    """A loader wrapper that arms the engine's profiler when given batches
    are asked for: how a benchmark or an operator profiles N steps of a
    running job without knowing its step numbers beforehand."""

    def __init__(self, inner, engine, plan):
        self.inner, self.engine, self.plan = inner, engine, dict(plan)
        self.asked = 0

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def __iter__(self):
        self._it = iter(self.inner)
        return self

    def __next__(self):
        self.asked += 1
        if self.asked in self.plan:
            log_dir, steps = self.plan[self.asked]
            self.engine.profiler.arm(log_dir, steps, python_tracer=False, summary=False)
        return next(self._it)


# ---------------------------------------------------------------------------
# hook
# ---------------------------------------------------------------------------


def test_arm_traces_a_running_fit_twice_and_spans_nest(tmp_path, devices8):
    """Two windows armed from the loader in one fit: each writes its own
    trace; the trace holds one ``pfx.train.step`` per traced step with the
    loop's spans inside it; the flight recorder has the start clocks."""
    from paddlefleetx_tpu.utils import telemetry

    first, second = str(tmp_path / "t1"), str(tmp_path / "t2")
    engine, records, _ = _fit(
        tmp_path,
        loader_wrap=lambda ld, eng: _ArmAt(ld, eng, {3: (first, 3), 8: (second, 2)}),
    )
    assert engine.profiler.traces == 2
    assert [r["step"] for r in records] == list(range(1, 13))
    for log_dir, want in ((first, [4, 5]), (second, [9])):
        ev = _host_events(log_dir)
        # armed when batch k is asked for: the trace starts once step k is
        # dispatched and stops once step k + steps is: the dispatches of
        # `steps` steps, and every whole pass of the loop between them
        assert sorted(e[4]["step_num"] for e in ev if e[1] == "pfx.train.step") == want
        for child in ("pfx.train.data_wait", "pfx.train.put_dispatch",
                      "pfx.train.log_fetch", "pfx.train.log_write"):
            _inside(ev, child, "pfx.train.step")
    starts = [e for e in telemetry.get_flight_recorder().events()
              if e.get("event") == "profiler_trace_start"
              and e.get("log_dir") in (first, second)]
    assert len(starts) == 2
    for e in starts:
        assert abs(e["monotonic_ns"] - time.monotonic_ns()) < 600e9
        assert abs(e["time_ns"] - time.time_ns()) < 600e9


def test_arm_refuses_a_second_window_while_one_is_open(tmp_path):
    from paddlefleetx_tpu.utils.profiler import ProfileBusy, ProfilerHook

    hook = ProfilerHook(None)
    hook.arm(str(tmp_path / "p"), 2)
    with pytest.raises(ProfileBusy):
        hook.arm(str(tmp_path / "q"), 1)
    with pytest.raises(ValueError):
        ProfilerHook(None).arm(str(tmp_path / "r"), 0)
    hook.close()  # an armed window that never started leaves nothing behind
    assert not os.path.exists(str(tmp_path / "p"))
    hook.arm(str(tmp_path / "q"), 1)  # and the hook can be armed again


def test_config_block_is_the_same_path_and_unarmed_hook_starts_nothing(
        tmp_path, monkeypatch):
    from paddlefleetx_tpu.utils import profiler

    calls = []
    monkeypatch.setattr(profiler, "start_trace",
                        lambda d, python_tracer=True: calls.append(("start", d))
                        or {"monotonic_ns": 1, "time_ns": 2})
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: calls.append(("stop",)))

    idle = profiler.ProfilerHook({"enable": False, "log_dir": str(tmp_path / "a")})
    for step in range(1, 20):
        idle.step(step)
    idle.close()
    assert calls == []

    log_dir = str(tmp_path / "b")
    hook = profiler.ProfilerHook(
        {"enable": True, "scheduler": [2, 5], "log_dir": log_dir, "summary": False})
    seen = []
    for step in range(1, 8):
        hook.step(step)
        seen.append((step, len(calls)))
    # started at step 2, stopped at step 5, nothing after
    assert calls == [("start", log_dir), ("stop",)]
    assert [n for _, n in seen] == [0, 1, 1, 1, 2, 2, 2]
    assert hook.traces == 1
    # a run that resumes past its config window traces nothing
    late = profiler.ProfilerHook(
        {"enable": True, "scheduler": [2, 5], "log_dir": log_dir, "summary": False})
    late.step(9)
    late.step(10)
    assert len(calls) == 2 and late.traces == 0
    # armed at run time: "the next n steps", whatever the step number
    hook.arm(str(tmp_path / "c"), 3)
    for step in range(40, 46):
        hook.step(step)
    assert calls[2:] == [("start", str(tmp_path / "c")), ("stop",)]
    assert hook.traces == 2


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_ledger_span_books_its_seconds_also_on_an_exception():
    from paddlefleetx_tpu.utils.telemetry import ledger_span

    led = {"a": 0.0, "b": 1.0}
    with ledger_span("pfx.test.a", led, "a", rows=3) as sp:
        time.sleep(0.02)
    assert led["a"] == pytest.approx(sp.seconds) and 0.015 < sp.seconds < 1.0
    assert sp.t1 - sp.t0 == sp.seconds and led["b"] == 1.0
    with pytest.raises(KeyError):
        with ledger_span("pfx.test.b", led, "b"):
            time.sleep(0.01)
            raise KeyError("inside")
    assert 1.005 < led["b"] < 2.0
    with ledger_span("pfx.test.c") as bare:  # no ledger: a span and a timer
        pass
    assert bare.seconds >= 0.0 and set(led) == {"a", "b"}


@pytest.fixture(scope="module")
def server():
    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.core.serving import GenerationServer
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import AttrDict, process_configs

    cfg = process_configs(AttrDict.from_nested(TINY), num_devices=jax.device_count())
    mesh = init_dist_env(cfg)
    return GenerationServer(cfg, mesh, build_module(cfg))


def _serve(server, trace_dir=None, stream=False):
    from paddlefleetx_tpu.core.continuous_batching import (
        ContinuousScheduler, PagedDecodeEngine)
    from paddlefleetx_tpu.utils.profiler import start_trace

    eng = PagedDecodeEngine(server, max_batch=4)
    sched = ContinuousScheduler(eng, max_depth=16)
    sched.warmup([4])
    base = dict(eng.stats)
    if trace_dir:
        start_trace(trace_dir, python_tracer=False)
    try:
        sched.start()
        sink = (lambda *a: None) if stream else None
        futs = [sched.submit([p], 6, deadline_s=120, stream=sink) for p in PROMPTS]
        outs = [f.result(timeout=300)[0] for f in futs]
        assert sched.shutdown(timeout=60)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    return eng, sched, base, outs


def test_scheduler_spans_are_in_the_host_plane(server, tmp_path):
    """With a CPU profiler session open the scheduler's ledger stamps are
    host spans: dispatch, readback and flush inside ``pfx.sched.iterate``,
    the parked wait as ``pfx.sched.idle``; the ledger still closes."""
    log_dir = str(tmp_path / "trace")
    eng, sched, _, _ = _serve(server, trace_dir=log_dir, stream=True)
    ev = _host_events(log_dir)
    names = {e[1] for e in ev}
    assert {"pfx.sched.iterate", "pfx.sched.prefill", "pfx.sched.decode_dispatch",
            "pfx.sched.readback", "pfx.sched.stream_flush", "pfx.sched.idle"} <= names
    for child in ("pfx.sched.prefill", "pfx.sched.decode_dispatch",
                  "pfx.sched.readback", "pfx.sched.stream_flush"):
        _inside(ev, child, "pfx.sched.iterate")
    it = next(e for e in ev if e[1] == "pfx.sched.iterate")
    assert {"iter", "active", "width_bucket"} <= set(it[4])
    pre = next(e for e in ev if e[1] == "pfx.sched.prefill")
    assert {"slot", "prompt_len", "bucket"} <= set(pre[4])
    # an idle span is never inside an iterate span: the two tile the thread
    for line, _, a, b, _ in (e for e in ev if e[1] == "pfx.sched.idle"):
        assert not any(p[0] == line and p[1] == "pfx.sched.iterate"
                       and p[2] < b and a < p[3] for p in ev)
    tl = sched.time_ledger()
    assert abs(sum(tl["buckets"].values()) - tl["wall_s"]) <= 0.01 * tl["wall_s"]
    assert tl["buckets"]["stream_flush"] > 0.0


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------


def test_decode_work_counters_against_a_hand_count(server):
    """Four prompts, six tokens each: every decode step counts its live
    rows against the batch's slots and their context against the tokens
    the paged kernel computes on."""
    eng, sched, base, outs = _serve(server)
    d = {k: eng.stats[k] - base[k] for k in
         ("steps", "row_steps", "slot_steps", "kv_tokens", "grid_tokens")}
    assert d["steps"] > 0 and d["slot_steps"] == eng.capacity * d["steps"]
    assert 0 < d["row_steps"] <= d["slot_steps"]
    # each row decodes one token a step until it has its six
    assert d["row_steps"] == sum(len(o) for o in outs) == 24
    assert 0 < d["kv_tokens"] <= d["grid_tokens"]
    # a row's context at a step is at least its prompt, at most prompt + 6
    lo = sum(len(p) * len(o) for p, o in zip(PROMPTS, outs))
    hi = sum((len(p) + 6) * len(o) for p, o in zip(PROMPTS, outs))
    assert lo <= d["kv_tokens"] <= hi
    # every context here fits one page, so the kernel computes one grid
    # step of one page for each LIVE slot (its grid follows the step's live
    # list: an empty slot is not visited) and nothing past it
    assert d["grid_tokens"] == eng.block * d["row_steps"]
    mets = {name: v for name, labels, v in sched.collect() if not labels}
    assert mets["pfx_sched_decode_steps_total"] == eng.stats["steps"]
    assert mets["pfx_sched_decode_row_steps_total"] == eng.stats["row_steps"]
    assert mets["pfx_sched_decode_slot_steps_total"] == eng.stats["slot_steps"]
    assert mets["pfx_sched_decode_kv_tokens_total"] == eng.stats["kv_tokens"]
    assert mets["pfx_sched_decode_grid_tokens_total"] == eng.stats["grid_tokens"]


def test_train_records_carry_host_gap_fetch_and_write_seconds(tmp_path, devices8):
    from paddlefleetx_tpu.utils import telemetry

    _, records, wall = _fit(tmp_path)
    keys = ("host_gap_s", "log_fetch_s", "log_write_s")
    assert all(k in r for r in records for k in keys)
    for k in keys:  # cumulative
        vals = [r[k] for r in records]
        assert vals == sorted(vals) and vals[0] >= 0.0
    last = records[-1]
    assert last["log_fetch_s"] > 0.0 and last["host_gap_s"] > 0.0
    assert last["host_gap_s"] + last["log_fetch_s"] + last["log_write_s"] <= wall
    # every step logs here, so every step but the first closes a gap, and a
    # gap holds at least the write of the record before it
    assert last["host_gap_s"] >= last["log_write_s"]
    assert telemetry.get_registry().value(
        "pfx_train_host_gap_seconds_total") == pytest.approx(last["host_gap_s"], abs=1e-3)
    led = last["time_ledger"]
    assert set(led) == {"compile", "device_step", "data_wait", "host", "eval"}


# ---------------------------------------------------------------------------
# capture
# ---------------------------------------------------------------------------


def test_capture_without_summary_parses_nothing(tmp_path, monkeypatch):
    from paddlefleetx_tpu.utils import profiler

    def boom(*a, **k):
        raise AssertionError("summary=False must not parse the trace")

    monkeypatch.setattr(profiler, "op_summary_rows", boom)
    monkeypatch.setattr(profiler, "device_host_split", boom)
    before = {m for m in sys.modules if m.split(".")[0] in ("xprof", "tensorflow")}
    log_dir = str(tmp_path / "cap")
    t = time.monotonic_ns()
    out = profiler.capture_profile(0.05, log_dir, summary=False, python_tracer=False)
    assert out["trace_dir"] == log_dir and out["seconds"] > 0
    assert set(out) == {"seconds", "trace_dir", "started_monotonic_ns",
                        "started_time_ns", "python_tracer"}
    assert out["python_tracer"] is False
    assert t <= out["started_monotonic_ns"] <= time.monotonic_ns()
    assert abs(out["started_time_ns"] - time.time_ns()) < 600e9
    assert glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    assert {m for m in sys.modules
            if m.split(".")[0] in ("xprof", "tensorflow")} == before


def _write_chrome_trace(log_dir, events):
    run = os.path.join(log_dir, "plugins", "profile", "2026_01_01_00_00_00")
    os.makedirs(run)
    with gzip.open(os.path.join(run, "host.trace.json.gz"), "wt") as f:
        json.dump({"traceEvents": events}, f)


def test_device_host_split_is_a_union_not_a_sum(tmp_path):
    from paddlefleetx_tpu.utils.profiler import device_host_split

    def meta(pid, name, tid=None, thread=None):
        out = [{"ph": "M", "pid": pid, "name": "process_name", "args": {"name": name}}]
        if tid is not None:
            out.append({"ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
                        "args": {"name": thread}})
        return out

    def x(pid, tid, ts, dur):
        return {"ph": "X", "pid": pid, "tid": tid, "name": "e", "ts": ts, "dur": dur}

    log_dir = str(tmp_path / "hand")
    _write_chrome_trace(log_dir, (
        meta(1, "/device:TPU:0", 10, "XLA Ops") + meta(1, "/device:TPU:0", 11, "XLA Modules")
        + meta(2, "/host:CPU")
        # device ops: a while [0,100) with two children, then [150,200)
        + [x(1, 10, 0, 100), x(1, 10, 10, 30), x(1, 10, 50, 40), x(1, 10, 150, 50)]
        # the module line spans the gap between the ops: not counted
        + [x(1, 11, 0, 200)]
        # host: two threads overlapping on [20,60), one event apart
        + [x(2, 1, 0, 60), x(2, 2, 20, 80), x(2, 1, 300, 10)]))
    device_us, host_us = device_host_split(log_dir)
    assert device_us == 150.0  # the sum would say 220 (420 with the module line)
    assert host_us == 110.0    # the sum would say 150


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _flash(bwd):
    from paddlefleetx_tpu.ops.flash_attention import _flash_bsnd

    q = jnp.zeros((1, 256, 2, 64), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(_flash_bsnd(q, k, v, 0.125, (128, 128), bwd))

    return jax.grad(loss, (0, 1, 2)), (q, q, q)


def _layernorm():
    from paddlefleetx_tpu.ops.fused_layernorm import fused_layer_norm

    x, w = jnp.zeros((2, 128, 128), jnp.float32), jnp.ones((128,), jnp.float32)
    # the value too: the backward reads nothing the forward wrote, so the forward
    # kernel of a bare gradient is dead code
    return (jax.value_and_grad(lambda x, s, b: jnp.sum(fused_layer_norm(x, s, b)), (0, 1, 2)),
            (x, w, w))


def _contig(int8):
    from paddlefleetx_tpu.ops import decode_attention as da

    b, n, d, max_len = 2, 2, 64, 512
    q = jnp.zeros((b, 1, n, d), jnp.float32)
    kv = jnp.zeros((b, n, max_len, d), jnp.int8 if int8 else jnp.float32)
    scale = {"k_scale": jnp.ones((b, n, max_len), jnp.float32),
             "v_scale": jnp.ones((b, n, max_len), jnp.float32)} if int8 else {}
    return (lambda q, k, v: da.decode_attention(q, k, v, 7, impl="pallas", **scale),
            (q, kv, kv))


def _paged():
    from paddlefleetx_tpu.ops import decode_attention as da

    b, n, d, bs, nb, m = 2, 2, 64, 16, 9, 4
    q = jnp.zeros((b, 1, n, d), jnp.float32)
    pool = jnp.zeros((nb, n, bs, d), jnp.float32)
    tables = jnp.zeros((b, m), jnp.int32)
    pos = jnp.asarray([5, 20], jnp.int32)
    return (lambda q, k, v, t, p: da.paged_decode_attention(q, k, v, t, p, impl="pallas"),
            (q, pool, pool, tables, pos))


KERNELS = {  # the kernel's name in the device trace is pfx_<key>
    "flash_fwd": lambda: _flash("split"),
    "flash_bwd_dq": lambda: _flash("split"),
    "flash_bwd_dkv": lambda: _flash("split"),
    "flash_bwd_fused": lambda: _flash("fused"),
    "ln_fwd": _layernorm,
    "ln_bwd": _layernorm,
    "decode_contig": lambda: _contig(False),
    "decode_contig_q8": lambda: _contig(True),
    "decode_paged": _paged,
}


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_pallas_call_carries_its_kernel_name(kernel):
    """The name reaches the lowered program (on the chip it becomes the
    HLO instruction's name, which is what a device trace shows)."""
    fn, args = KERNELS[kernel]()
    name = f"pfx_{kernel}"
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    # whole words: pfx_decode_contig is a prefix of pfx_decode_contig_q8
    words = set(text.replace('"', " ").replace("/", " ").replace("(", " ")
                .replace(")", " ").split())
    assert name in words, f"{name} not in the lowered text of its wrapper"


def test_lint_takes_a_kernel_name_for_no_metric(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from lint import check_file

    src = tmp_path / "k.py"
    src.write_text('"""m."""\n'
                   'from jax.experimental import pallas as pl\n\n'
                   'f = pl.pallas_call(None, out_shape=None, name="pfx_some_kernel")\n')
    assert not [c for _, _, c, _ in check_file(str(src)) if c == "E10"]
    src.write_text('"""m."""\nNAME = "pfx_some_kernel"\n')
    assert [c for _, _, c, _ in check_file(str(src)) if c == "E10"]


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------


def _bench_module(rel):
    bench = os.path.join(REPO, "pfx_bench")  # noqa: E10 — a directory, not a metric
    if bench not in sys.path:
        sys.path.insert(0, bench)
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(rel)[:-3], os.path.join(bench, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_window_loader_arms_only_after_its_window_has_closed(monkeypatch):
    """--trace 2: the loader hands out what --trace 0 hands out until the
    seconds are spent and the window is closed; then a throwaway window of
    one step and the traced steps, each with the one batch more a window
    needs to start, then it stops."""
    train = _bench_module(os.path.join("runners", "train.py"))

    class Hook:
        def __init__(self):
            self.armed = []

        def arm(self, log_dir, steps, **kw):
            self.armed.append((log_dir, steps, loader.handed, loader.m_end is not None, kw))

    class Eng:
        state = None
        profiler = Hook()

    clock = [0.0]
    monkeypatch.setattr(train.time, "monotonic", lambda: clock[0])
    monkeypatch.setattr(train.WindowLoader, "_fence", lambda self: None)
    loader = train.WindowLoader(iter(range(10 ** 6)), Eng, warmup=3, seconds=10.0,
                                compile_count=lambda: 0, trace=("/t", 4))
    got = 0
    for _ in iter(loader):
        got += 1
        clock[0] += 1.0
    # 3 warm-up + 10 window batches, then (1 + 1) and (4 + 1) for the two windows
    assert loader.window_steps == 10 and got == 3 + 10 + 2 + 5
    assert loader.m_end - loader.m_start == 10.0 and not loader.exhausted
    assert [(a[0], a[1], a[2], a[3]) for a in Eng.profiler.armed] == [
        ("/t-first-start", 1, 13, True), ("/t", 4, 15, True)]
    assert all(a[4] == {"python_tracer": train.PYTHON_TRACER, "summary": False}
               for a in Eng.profiler.armed)
    # --trace 0: the same window, nothing armed, nothing handed out after it
    Eng.profiler.armed.clear()
    clock[0] = 0.0
    plain = train.WindowLoader(iter(range(10 ** 6)), Eng, warmup=3, seconds=10.0,
                               compile_count=lambda: 0)
    n = 0
    for _ in iter(plain):
        n += 1
        clock[0] += 1.0
    assert n == 13 and plain.window_steps == 10 and Eng.profiler.armed == []


def test_record_share_reads_a_cumulative_key_over_the_window():
    reader = _bench_module(os.path.join("readers", "record_share.py"))
    ctx = {"engine_base_record": {"step": 3, "host_gap_s": 0.5},
           "engine_records": [{"step": 4, "host_gap_s": 0.6}, {"step": 5, "host_gap_s": 1.0}],
           "window_s": 25.0}
    assert reader.read(ctx, "host_gap_s") == pytest.approx(2.0)
    # a program whose records lack the key gives nothing, and does not raise
    assert reader.read(ctx, "log_fetch_s") is None
    assert reader.read({**ctx, "engine_records": []}, "host_gap_s") is None
    assert reader.read({**ctx, "engine_base_record": None}, "host_gap_s") is None
