"""Profiler integration (config-gated, reference eager_engine.py:250-272,
419-420, 866-925: paddle.profiler scheduler window + chrome-trace export +
sorted Device/Kernel/Operator/Memory summary tables on finish).

TPU-native: ``jax.profiler`` writes an XPlane/TensorBoard trace for the
configured step window.  Config block::

    Profiler:
      enable: True
      scheduler: [3, 8]     # [start_step, stop_step)
      log_dir: ./profiler_log
      summary: True         # emit sorted op/memory summaries on close
      summary_top: 20       # rows in the printed op table

On trace close the hook additionally converts the captured XPlane into
the reference's printed summary views (eager_engine.py:866-925):
``summary_ops.txt`` (per-HLO-op total/self time, sorted), the raw
``hlo_stats.json``, and ``summary_memory.txt`` (live device memory stats
when the backend exposes them).  Conversion uses the xprof toolchain when
importable and degrades to trace-only with a warning otherwise.

``ProfilerHook.arm(log_dir, steps)`` starts a window at run time (the
config block is the same path: it arms itself at ``scheduler[0]``), so a
loader wrapper or callback can profile N steps of a running job without
knowing its step numbers beforehand (docs/observability.md "On-demand
profiling").

The parsing layer is module-level (``newest_run_dir`` / ``hlo_stats_rows``
/ ``trace_event_rows`` / ``op_summary_rows`` / ``device_host_split``) so
the on-demand serving capture (``capture_profile``, behind ``POST
/admin/profile`` in tools/serve.py) reuses the exact same toolchain as
the training hook.  ``capture_profile`` enforces the two safety rules
for profiling a *production* replica: one capture at a time per process
(``ProfileBusy`` -> HTTP 409) and a hard duration cap
(``PFX_PROFILE_MAX_SECONDS``, default 30 -> HTTP 400 when exceeded).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax

from paddlefleetx_tpu.utils.log import logger

# one capture at a time per process: jax.profiler.start_trace is a global
# singleton, so a second concurrent capture would either crash or corrupt
# the first — refuse loudly instead (serve.py maps ProfileBusy to 409)
_CAPTURE_LOCK = threading.Lock()


class ProfileBusy(RuntimeError):
    """A profile capture is already active in this process."""


def profile_max_seconds() -> float:
    """Hard cap on an on-demand capture window (PFX_PROFILE_MAX_SECONDS,
    default 30): profiling stalls nothing, but traces grow with wall time
    and an unbounded window on a production replica is an outage hazard."""
    from paddlefleetx_tpu.utils.telemetry import _env_float

    return _env_float("PFX_PROFILE_MAX_SECONDS", 30.0, minimum=0.001)


def newest_run_dir(log_dir: str) -> str:
    """The newest TensorBoard profile run directory under ``log_dir``."""
    import glob

    runs = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*")))
    if not runs:
        raise FileNotFoundError(f"no profile runs under {log_dir}")
    return runs[-1]


def _newest_xplanes(log_dir: str):
    import glob

    run = newest_run_dir(log_dir)
    planes = sorted(glob.glob(os.path.join(run, "*.xplane.pb")))
    if not planes:
        raise FileNotFoundError(f"no xplane.pb under {run}")
    return planes


def hlo_stats_rows(log_dir: str) -> List[Dict[str, Any]]:
    """Per-HLO-op rows from xprof's hlo_stats tool (populated on real
    accelerator traces; CPU traces carry no device-op events)."""
    import json

    from xprof.convert import raw_to_tool_data  # lazy: pulls in TF

    planes = _newest_xplanes(log_dir)
    data, _ = raw_to_tool_data.xspace_to_tool_data(planes, "hlo_stats", {})
    if isinstance(data, bytes):
        data = data.decode()
    with open(os.path.join(log_dir, "hlo_stats.json"), "w") as f:
        f.write(data)

    table = json.loads(data)
    cols = [c["id"] for c in table["cols"]]
    idx = {name: cols.index(name) for name in
           ("category", "hlo_op_name", "occurrences", "total_time",
            "total_self_time")}
    rows = []
    for row in table.get("rows", []):
        vals = [cell.get("v") if isinstance(cell, dict) else cell for cell in row["c"]]
        rows.append({
            "op": vals[idx["hlo_op_name"]],
            "category": vals[idx["category"]],
            "occurrences": int(vals[idx["occurrences"]] or 0),
            "total_us": float(vals[idx["total_time"]] or 0.0),
            "self_us": float(vals[idx["total_self_time"]] or 0.0),
        })
    return rows


def _newest_trace_events(log_dir: str) -> List[Dict[str, Any]]:
    import glob
    import gzip
    import json

    run = newest_run_dir(log_dir)
    traces = sorted(glob.glob(os.path.join(run, "*.trace.json.gz")))
    if not traces:
        raise FileNotFoundError(f"no trace.json.gz under {run}")
    with gzip.open(traces[-1], "rt") as f:
        return json.load(f).get("traceEvents", [])


def trace_event_rows(log_dir: str) -> List[Dict[str, Any]]:
    """Fallback aggregation over the chrome-trace events: op name ->
    occurrences + summed duration.  Available on every backend."""
    agg: Dict[str, list] = {}
    for e in _newest_trace_events(log_dir):
        if e.get("ph") != "X" or "dur" not in e:
            continue
        entry = agg.setdefault(e.get("name", "?"), [0, 0.0])
        entry[0] += 1
        entry[1] += float(e["dur"])
    return [
        {"op": name, "category": "trace", "occurrences": n,
         "total_us": dur, "self_us": dur}
        for name, (n, dur) in agg.items()
    ]


def _union_us(intervals) -> float:
    """Total length of the union of ``(start, end)`` intervals."""
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def device_host_split(log_dir: str) -> Tuple[float, float]:
    """(device_us, host_us): BUSY microseconds, the union of the
    complete-event intervals of each plane (overlapping and nested events
    count once), summed over device planes and over host planes.  The
    chrome trace names every pid via ``ph=="M"``/``process_name``
    metadata; device planes are the ``/device:...`` ones (TPU/GPU
    streams), everything else (python threads, runtime) is host.  A
    device plane is read from its "XLA Ops" line when it has one (the
    "Steps" and "XLA Modules" lines span the gaps between ops)."""
    device_pids, ops_tids = set(), {}
    events = _newest_trace_events(log_dir)
    for e in events:
        if e.get("ph") != "M":
            continue
        name = str((e.get("args") or {}).get("name", ""))
        if e.get("name") == "process_name" and name.startswith("/device:"):
            device_pids.add(e.get("pid"))
        elif e.get("name") == "thread_name" and name == "XLA Ops":
            ops_tids[e.get("pid")] = e.get("tid")
    per_plane: Dict[Any, list] = {}
    for e in events:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        pid = e.get("pid")
        if pid in ops_tids and e.get("tid") != ops_tids[pid]:
            continue  # a device plane's other lines
        t = float(e.get("ts", 0.0))
        per_plane.setdefault(pid, []).append((t, t + float(e["dur"])))
    device_us = sum(_union_us(v) for k, v in per_plane.items() if k in device_pids)
    host_us = sum(_union_us(v) for k, v in per_plane.items() if k not in device_pids)
    return device_us, host_us


def op_summary_rows(log_dir: str, hlo_fn=None, trace_fn=None) -> Tuple[List[Dict[str, Any]], str]:
    """(rows sorted by self time desc, source label): hlo_stats when the
    xprof toolchain can parse the trace, chrome-trace events otherwise.
    ``hlo_fn``/``trace_fn`` override the row sources (ProfilerHook passes
    its bound methods so tests can stub a toolchain failure)."""
    try:
        rows = (hlo_fn or (lambda: hlo_stats_rows(log_dir)))()
        source = "hlo_stats"
    except Exception as e:  # noqa: BLE001 — xprof missing / schema drift
        logger.warning(f"profiler: hlo_stats unavailable ({e!r}); using trace events")
        rows = []
    if not rows:
        rows = (trace_fn or (lambda: trace_event_rows(log_dir)))()
        source = "trace events (backend emits no per-HLO device stats)"
    rows.sort(key=lambda r: -r["self_us"])
    return rows, source


def start_trace(log_dir: str, python_tracer: bool = True) -> Dict[str, int]:
    """``jax.profiler.start_trace`` plus the two clock stamps taken at the
    start, so timelines on the monotonic clock (``/debug/traces``, the
    time ledgers) can be laid over the device trace afterwards.
    ``python_tracer=False`` keeps the profiler's Python call tracer off:
    the host plane then holds the runtime's own events and the program's
    ``pfx.*`` spans only, and the capture costs the traced threads far
    less (the Python tracer writes an event per call)."""
    os.makedirs(log_dir, exist_ok=True)
    options = None
    if not python_tracer:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
    clocks = {"monotonic_ns": time.monotonic_ns(), "time_ns": time.time_ns()}
    jax.profiler.start_trace(log_dir, profiler_options=options)
    return clocks


def _trace_done(trace_s: float) -> None:
    from paddlefleetx_tpu.utils.telemetry import get_registry

    reg = get_registry()
    reg.counter("pfx_profiler_traces_total").inc()
    reg.gauge("pfx_profiler_trace_seconds").set(round(trace_s, 3))


def capture_profile(seconds: float, log_dir: str, top: int = 20,
                    summary: bool = True,
                    python_tracer: bool = True,
                    probe=None) -> Dict[str, Any]:
    """Capture a ``jax.profiler`` trace of the LIVE process for ``seconds``
    and answer with the parsed summary — the whole ``POST /admin/profile``
    body in one call.  Raises ``ValueError`` on a bad/over-cap duration
    (-> 400) and ``ProfileBusy`` when a capture is already running
    (-> 409).  The capture adds no device sync: the profiler observes the
    running dispatch loop, it never drives it.

    ``summary=False`` skips the parse (the xprof/TF import, the op table,
    the device/host split) and answers with ``trace_dir``, ``seconds`` and
    the start clocks only: for a caller that reduces the trace in another
    process and must not stall this one.  ``python_tracer=False``: see
    :func:`start_trace`.  ``probe`` (a callable -> dict of counters) is read
    once the trace has started and again just before it stops; the two
    readings come back as ``counters_at_start`` / ``counters_at_stop``, so
    that work and device time of one stretch can be divided."""
    cap = profile_max_seconds()
    try:
        seconds = float(seconds)
    except (TypeError, ValueError):
        raise ValueError(f"profile seconds must be a number, got {seconds!r}") from None
    if not seconds > 0:
        raise ValueError(f"profile seconds must be > 0, got {seconds}")
    if seconds > cap:
        raise ValueError(
            f"profile seconds={seconds} exceeds PFX_PROFILE_MAX_SECONDS={cap} "
            f"(raise the cap explicitly if you really want a longer trace)"
        )
    if not _CAPTURE_LOCK.acquire(blocking=False):
        raise ProfileBusy(
            "a profile capture is already active in this process; "
            "retry after it finishes"
        )
    try:
        t0 = time.monotonic()
        clocks = start_trace(log_dir, python_tracer=python_tracer)
        counters = []
        try:
            if probe is not None:
                counters.append(probe())
            time.sleep(seconds)
            if probe is not None:
                counters.append(probe())
        finally:
            jax.profiler.stop_trace()
        trace_s = time.monotonic() - t0
        _trace_done(trace_s)
        out = {
            "seconds": round(trace_s, 3),
            "trace_dir": log_dir,
            "started_monotonic_ns": clocks["monotonic_ns"],
            "started_time_ns": clocks["time_ns"],
            "python_tracer": bool(python_tracer),
        }
        if len(counters) == 2:
            out["counters_at_start"], out["counters_at_stop"] = counters
        if not summary:
            return out
        rows, source = op_summary_rows(log_dir)
        try:
            device_us, host_us = device_host_split(log_dir)
        except Exception as e:  # noqa: BLE001 — split is best-effort
            logger.warning(f"profiler: device/host split unavailable ({e!r})")
            device_us = host_us = 0.0
        total_self = sum(r["self_us"] for r in rows) or 1.0
        top_ops = [
            {**r, "self_frac": round(r["self_us"] / total_self, 4)}
            for r in rows[: max(0, int(top))]
        ]
        out.update({
            "source": source,
            "device_us": round(device_us, 1),
            "host_us": round(host_us, 1),
            "op_count": len(rows),
            "top_ops": top_ops,
        })
        return out
    finally:
        _CAPTURE_LOCK.release()


class ProfilerHook:
    """Start/stop a ``jax.profiler`` trace around a window of training
    steps.  The window comes from the config block (``enable`` +
    ``scheduler``: the hook arms itself at construction) or, at run time,
    from :meth:`arm`.  An unarmed hook costs one comparison per step."""

    def __init__(self, cfg: Optional[Dict[str, Any]]):
        cfg = cfg or {}
        self.enabled = bool(cfg.get("enable", False))
        sched = cfg.get("scheduler") or [3, 8]
        try:
            ok = len(sched) == 2 and int(sched[0]) < int(sched[1])
        except (TypeError, ValueError):
            ok = False
        if not ok:
            if not self.enabled:
                # a malformed window must not abort runs that never profile
                sched = [3, 8]
            else:
                raise ValueError(
                    f"Profiler.scheduler must be [start_step, stop_step] with "
                    f"start < stop, got {sched}"
                )
        self.start_step, self.stop_step = int(sched[0]), int(sched[1])
        self.log_dir = os.path.abspath(cfg.get("log_dir", "./profiler_log"))
        self.summary = bool(cfg.get("summary", True))
        self.summary_top = int(cfg.get("summary_top", 20))
        self.python_tracer = True
        self.traces = 0  # windows completed by this hook
        self._active = False
        self._pending_summary = False
        self._trace_t0 = 0.0
        # the armed window: None, (start, stop) in step numbers, or
        # (None, n) = "n steps from the next step boundary"
        self._window: Optional[Tuple[Optional[int], int]] = None
        if self.enabled:
            self._window = (self.start_step, self.stop_step)

    @property
    def active(self) -> bool:
        """True from the step that started a trace to the one that stops it."""
        return self._active

    def arm(self, log_dir: Optional[str] = None, steps: int = 1, *,
            python_tracer: bool = True,
            summary: Optional[bool] = None) -> None:
        """Trace the next ``steps`` training steps of the running fit: the
        trace starts at the next step boundary (the next ``step()`` call)
        and stops ``steps`` calls later.  Call it from the thread that
        runs the fit loop (a loader wrapper's ``__next__``, a callback);
        a hook can be armed again once its window has closed.
        ``summary`` (default: the config block's) writes the op/memory
        summaries at ``close()``; ``python_tracer``: see
        :func:`start_trace`."""
        if self._active or self._window is not None:
            raise ProfileBusy("a profiler window is already armed or tracing")
        if int(steps) < 1:
            raise ValueError(f"steps must be >= 1, got {steps}")
        if log_dir is not None:
            self.log_dir = os.path.abspath(log_dir)
        if summary is not None:
            self.summary = bool(summary)
        self.python_tracer = bool(python_tracer)
        self._window = (None, int(steps))

    def step(self, step: int) -> None:
        """Call once per training step with the 1-based step counter."""
        if self._window is None:
            return
        from paddlefleetx_tpu.utils.telemetry import get_flight_recorder

        start, stop = self._window
        if start is None:  # armed at run time: the window starts here
            start, stop = step, step + stop
            self._window = (start, stop)
        if not self._active and start <= step < stop:
            clocks = start_trace(self.log_dir, python_tracer=self.python_tracer)
            self._active = True
            self._trace_t0 = time.monotonic()
            get_flight_recorder().record(
                {"event": "profiler_trace_start", "step": step,
                 "log_dir": self.log_dir, **clocks}
            )
            logger.info(f"profiler: trace started (steps {start}-{stop}) -> {self.log_dir}")
        elif step >= stop:
            self._window = None
            if not self._active:
                return  # the run resumed past a config window: nothing to trace
            jax.profiler.stop_trace()
            self._active = False
            self.traces += 1
            # summaries lazily import the xprof/TF toolchain and parse the
            # whole trace — deferred to close() so the remaining training
            # steps (whose throughput is being measured) are not stalled
            self._pending_summary = True
            trace_s = time.monotonic() - self._trace_t0
            _trace_done(trace_s)
            get_flight_recorder().record(
                {"event": "profiler_trace_stop", "step": step,
                 "trace_s": round(trace_s, 3)}
            )
            logger.info(f"profiler: trace written to {self.log_dir} (view with TensorBoard)")

    def close(self) -> None:
        self._window = None
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
            self._pending_summary = True
        if self._pending_summary:
            self._pending_summary = False
            self._write_summary()

    # -- summary views (reference eager_engine.py:866-925) -----------------
    # thin instance seams over the module-level parsers: the on-demand
    # serving capture shares them, and tests stub toolchain failures here

    def _newest_run_dir(self) -> str:
        return newest_run_dir(self.log_dir)

    def _hlo_stats_rows(self):
        return hlo_stats_rows(self.log_dir)

    def _trace_event_rows(self):
        return trace_event_rows(self.log_dir)

    def _write_summary(self) -> None:
        if not self.summary:
            return
        try:
            self._write_op_summary()
        except Exception as e:  # noqa: BLE001 — summaries must never kill a run
            logger.warning(f"profiler: op summary unavailable ({e!r})")
        try:
            self._write_memory_summary()
        except Exception as e:  # noqa: BLE001
            logger.warning(f"profiler: memory summary unavailable ({e!r})")

    def _write_op_summary(self) -> None:
        rows, source = op_summary_rows(
            self.log_dir,
            hlo_fn=self._hlo_stats_rows,
            trace_fn=self._trace_event_rows,
        )
        total_self = sum(r["self_us"] for r in rows) or 1.0

        lines = [
            f"{'op':<56} {'category':<18} {'#':>6} "
            f"{'total us':>12} {'self us':>12} {'self %':>7}"
        ]
        for r in rows[: self.summary_top]:
            lines.append(
                f"{str(r['op'])[:56]:<56} {str(r['category'])[:18]:<18} "
                f"{r['occurrences']:>6} {r['total_us']:>12.1f} "
                f"{r['self_us']:>12.1f} {100.0 * r['self_us'] / total_self:>7.2f}"
            )
        report = "\n".join(lines)
        path = os.path.join(self.log_dir, "summary_ops.txt")
        with open(path, "w") as f:
            f.write(f"source: {source}\n" + report + "\n")
        logger.info(
            f"profiler: op summary (top {min(self.summary_top, len(rows))} of "
            f"{len(rows)} by self time, {source}) -> {path}\n{report}"
        )

    def _write_memory_summary(self) -> None:
        lines = []
        for dev in jax.local_devices():
            stats = dev.memory_stats()
            if not stats:
                continue
            lines.append(f"{dev}:")
            for key in sorted(stats):
                lines.append(f"  {key:<32} {stats[key]}")
        path = os.path.join(self.log_dir, "summary_memory.txt")
        with open(path, "w") as f:
            if lines:
                f.write("\n".join(lines) + "\n")
            else:
                f.write("backend exposes no memory_stats(); see the trace's "
                        "memory_profile tool instead\n")
        logger.info(f"profiler: memory summary -> {path}")
