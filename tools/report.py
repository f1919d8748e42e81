"""Offline run-report renderer: one self-contained HTML (or markdown)
page from a training run's artifacts — no server, no deps beyond stdlib,
no jax import, so it runs on CI artifacts and laptops alike.

Inputs (any subset; missing ones get a loud note in the report):

  - the engine's metrics JSONL (``Engine.metrics_file``) — step records
    + structured events (rollback / preempt_save / data_skip /
    eval_empty);
  - a flight-recorder dump (``<output_dir>/flight_recorder.jsonl`` or
    ``<PFX_FLIGHT_DIR>/flight_recorder.jsonl``) — for a CRASHED run this
    is usually the only artifact, and its ring carries the step records
    the metrics stream never flushed, plus compile events (retrace
    attribution) and the dump reason;
  - a Chrome-trace export (``<PFX_FLIGHT_DIR>/trace.json``).

Rendered: loss / lr / MFU / data-wait curves (rollback, preempt and
compile markers overlaid), the per-layer-group norm heatmap from the
observatory's ``model_stats`` records, a memory-watermark timeline, and
an annotated event table.  Usage::

    python tools/report.py --metrics m.jsonl --flight out/flight_recorder.jsonl \
        --trace artifacts/trace.json -o report.html
    python tools/report.py --run-dir out/ --format md -o report.md

``--run-dir`` scans for the conventional file names.  Exit is nonzero
only when NO input artifact could be read.

Fleet mode (``--fleet [fleet_metrics.jsonl]``, docs/observability.md
"Fleet metrics federation"): renders the FLEET view from the router's
own append-only artifact (`core/router.FleetLog` — per-replica samples
every poll cadence + controller scale events), with the same
crash-tolerance contract: per-replica TTFT/latency/occupancy/depth
curves with scale events as markers, the handoff byte/time breakdown by
transport, and a last-known per-replica state table.  With no path the
conventional locations are scanned (``--run-dir``, then
``$PFX_FLIGHT_DIR``/``./artifacts``)::

    python tools/report.py --fleet artifacts/fleet_metrics.jsonl -o fleet.html
"""

import argparse
import html
import json
import math
import os
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

STEP_EVENT_KINDS = ("rollback", "preempt_save", "data_skip", "eval_empty")


# ---------------------------------------------------------------------------
# artifact loading
# ---------------------------------------------------------------------------


def load_jsonl(path: str) -> List[Dict[str, Any]]:
    rows = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rows.append(json.loads(line))
            except json.JSONDecodeError:
                rows.append({"event": "unparseable", "raw": line[:200]})
    return rows


class RunData:
    """Everything the renderer needs, merged from whichever artifacts
    exist.  Step records from the metrics stream win over flight-ring
    copies of the same step (the stream is the durable writer); a
    crashed run with no metrics file still gets records from the ring."""

    def __init__(self) -> None:
        self.sources: List[str] = []
        self.notes: List[str] = []
        self.records: Dict[int, Dict[str, Any]] = {}
        self.events: List[Dict[str, Any]] = []
        self.compiles: List[Dict[str, Any]] = []
        self.flight_header: Optional[Dict[str, Any]] = None
        self.trace_summary: Optional[Dict[str, Any]] = None
        self.profile: Optional[Dict[str, Any]] = None

    def add_profile(self, path: str) -> None:
        with open(path) as f:
            doc = json.load(f)
        if not isinstance(doc, dict):
            raise ValueError(f"profile summary {path} is not an object")
        self.profile = doc
        self.sources.append(f"profile: {path}")

    def _ingest_row(self, row: Dict[str, Any], prefer: bool) -> None:
        kind = row.get("event", "step" if "loss" in row else None)
        if kind == "step" and isinstance(row.get("step"), (int, float)):
            step = int(row["step"])
            if prefer or step not in self.records:
                self.records[step] = row
            elif "ts" in row:
                # a metrics-stream record won, but only the flight-ring
                # copy carries a wall-clock ts — backfill it so compile
                # events (ts-only) can be mapped onto the step axis
                self.records[step].setdefault("ts", row["ts"])
        elif kind == "compile":
            self.compiles.append(row)
        elif kind == "flight_recorder_dump":
            self.flight_header = row
        elif kind in STEP_EVENT_KINDS:
            self.events.append(row)
        elif kind in ("crash", "span", "unparseable"):
            self.events.append(row)

    def add_metrics(self, path: str) -> None:
        for row in load_jsonl(path):
            self._ingest_row(row, prefer=True)
        self.sources.append(f"metrics: {path}")

    def add_flight(self, path: str) -> None:
        seen = {
            (e.get("event"), e.get("step"), e.get("reason"))
            for e in self.events
        }
        for row in load_jsonl(path):
            kind = row.get("event", "step" if "loss" in row else None)
            if kind in STEP_EVENT_KINDS:
                key = (kind, row.get("step"), row.get("reason"))
                if key in seen:
                    continue  # already ingested from the metrics stream
            self._ingest_row(row, prefer=False)
        self.sources.append(f"flight: {path}")

    def add_trace(self, path: str) -> None:
        with open(path) as f:
            doc = json.load(f)
        # both Chrome-trace containers are valid: the object form
        # ({"traceEvents": [...]}) our exporter writes, and the bare
        # JSON-array form many Perfetto tools emit
        evs = doc if isinstance(doc, list) else doc.get("traceEvents", [])
        evs = [e for e in evs if isinstance(e, dict)]
        dur = sum(e.get("dur", 0) for e in evs if e.get("ph") == "X")
        self.trace_summary = {
            "path": path,
            "events": len(evs),
            "lanes": len({(e.get("pid"), e.get("tid")) for e in evs}),
            "span_seconds": round(dur / 1e6, 3),
        }
        self.sources.append(f"trace: {path}")

    # -- derived views --------------------------------------------------
    def steps(self) -> List[int]:
        return sorted(self.records)

    def series(self, key: str, sub: Optional[str] = None) -> List[Tuple[int, float]]:
        out = []
        for s in self.steps():
            rec = self.records[s]
            v = rec.get(key)
            if sub is not None and isinstance(v, dict):
                v = v.get(sub)
            if isinstance(v, (int, float)) and math.isfinite(v):
                out.append((s, float(v)))
        return out

    def model_stats_rows(self) -> List[Dict[str, Any]]:
        return [
            self.records[s]["model_stats"] for s in self.steps()
            if isinstance(self.records[s].get("model_stats"), dict)
        ]

    def status(self) -> str:
        preempts = [e for e in self.events if e.get("event") == "preempt_save"]
        crashes = [e for e in self.events if e.get("event") == "crash"]
        if crashes:
            return f"CRASHED: {crashes[-1].get('error', '?')}"
        if preempts:
            return f"preempted at step {preempts[-1].get('step', '?')} ({preempts[-1].get('cause', '?')})"
        if self.flight_header and self.flight_header.get("reason"):
            return f"flight dump: {self.flight_header['reason']}"
        return "completed (no crash/preempt markers)"


def find_artifacts(args) -> RunData:
    data = RunData()
    metrics, flight, trace = args.metrics, args.flight, args.trace
    if args.run_dir:
        d = args.run_dir
        metrics = metrics or _first_existing(
            os.path.join(d, "metrics.jsonl"), os.path.join(d, "m.jsonl")
        )
        flight = flight or _first_existing(
            os.path.join(d, "flight_recorder.jsonl"),
            os.path.join(d, "artifacts", "flight_recorder.jsonl"),
        )
        trace = trace or _first_existing(
            os.path.join(d, "trace.json"),
            os.path.join(d, "artifacts", "trace.json"),
        )
    for path, add, label in (
        (metrics, data.add_metrics, "metrics JSONL"),
        (flight, data.add_flight, "flight-recorder dump"),
        (trace, data.add_trace, "trace export"),
    ):
        if not path:
            data.notes.append(f"no {label} given — section skipped")
            continue
        try:
            add(path)
        except (OSError, ValueError, TypeError, AttributeError, KeyError) as e:
            # the contract: an unreadable/foreign artifact is a loud
            # note and the rest of the report still renders — never a
            # traceback on a crashed run's half-written files
            data.notes.append(f"could not read {label} {path}: {e!r}")
    return data


def _first_existing(*paths: str) -> Optional[str]:
    for p in paths:
        if os.path.exists(p):
            return p
    return None


def find_profile_summary(args) -> Optional[str]:
    """Resolve an on-demand profile capture's ``profile_summary.json``
    (tools/serve.py POST /admin/profile): an explicit ``--profile PATH``
    wins, then the NEWEST capture under the conventional
    ``<dir>/profiles/<ts>/`` layout in --run-dir / $PFX_FLIGHT_DIR /
    ./artifacts."""
    import glob

    prof = getattr(args, "profile", None)
    if prof and prof != "auto":
        return prof
    roots = []
    if getattr(args, "run_dir", None):
        roots += [args.run_dir, os.path.join(args.run_dir, "artifacts")]
    roots.append(os.environ.get("PFX_FLIGHT_DIR") or "artifacts")
    for root in roots:
        hits = sorted(glob.glob(
            os.path.join(root, "profiles", "*", "profile_summary.json")
        ))
        if hits:
            return hits[-1]
    return None


# ---------------------------------------------------------------------------
# fleet artifact (core/router.FleetLog JSONL)
# ---------------------------------------------------------------------------


class FleetData:
    """The router's fleet_metrics.jsonl, parsed: per-replica sample rows
    (time-ordered), router self-samples, and controller scale events —
    whatever subset a crashed router managed to append (torn tail lines
    land as ``unparseable`` and are skipped loudly in the notes)."""

    def __init__(self) -> None:
        self.sources: List[str] = []
        self.notes: List[str] = []
        self.samples: Dict[str, List[Dict[str, Any]]] = {}  # replica -> rows
        self.router_rows: List[Dict[str, Any]] = []
        self.scale_events: List[Dict[str, Any]] = []
        self.t0: Optional[float] = None
        self.profile: Optional[Dict[str, Any]] = None

    def add_profile(self, path: str) -> None:
        with open(path) as f:
            doc = json.load(f)
        if not isinstance(doc, dict):
            raise ValueError(f"profile summary {path} is not an object")
        self.profile = doc
        self.sources.append(f"profile: {path}")

    def add(self, path: str) -> None:
        bad = 0
        for row in load_jsonl(path):
            kind = row.get("event")
            ts = row.get("ts")
            if kind == "unparseable" or not isinstance(ts, (int, float)):
                bad += 1
                continue
            if self.t0 is None or ts < self.t0:
                self.t0 = ts
            if kind == "replica_sample" and row.get("replica"):
                self.samples.setdefault(str(row["replica"]), []).append(row)
            elif kind == "router_sample":
                self.router_rows.append(row)
            elif kind == "scale":
                self.scale_events.append(row)
        for rows in self.samples.values():
            rows.sort(key=lambda r: r["ts"])
        self.router_rows.sort(key=lambda r: r["ts"])
        if bad:
            self.notes.append(
                f"{bad} unparseable/partial line(s) skipped in {path} "
                "(a crashed run's torn tail is expected)"
            )
        self.sources.append(f"fleet: {path}")

    def rel(self, ts: float) -> float:
        return round(ts - (self.t0 or 0.0), 1)

    def series(self, replica: str, key: str) -> List[Tuple[float, float]]:
        out = []
        for r in self.samples.get(replica, []):
            v = r.get(key)
            if isinstance(v, (int, float)) and math.isfinite(v):
                out.append((self.rel(r["ts"]), float(v)))
        return out

    def last(self, replica: str) -> Dict[str, Any]:
        rows = self.samples.get(replica, [])
        return rows[-1] if rows else {}

    def replicas(self) -> List[str]:
        return sorted(self.samples)

    def markers(self) -> List[Tuple[float, str, str]]:
        """Scale events as ``(x, color, label)`` chart markers (x =
        relative seconds; a LIST — two pools scaling in the same tick
        must both render, a time-keyed dict would keep only one)."""
        out: List[Tuple[float, str, str]] = []
        for e in self.scale_events:
            color = "#dc2626" if e.get("action") == "scale_down" else "#059669"
            out.append((
                self.rel(e["ts"]), color,
                f"{e.get('pool', 'fleet')} {e.get('action', '?')}: "
                f"{e.get('reason', '')}",
            ))
        return out


# ---------------------------------------------------------------------------
# SVG primitives (hand-rolled: self-contained, no chart deps)
# ---------------------------------------------------------------------------

W, H, PAD = 640, 180, 36


def _scale(vals: Sequence[float], lo_px: float, hi_px: float):
    lo, hi = min(vals), max(vals)
    if hi == lo:
        hi = lo + 1.0
    span = hi - lo

    def f(v: float) -> float:
        return lo_px + (v - lo) / span * (hi_px - lo_px)

    return f, lo, hi


def _fmt(v: float) -> str:
    return f"{v:.4g}"


def svg_line(
    title: str,
    series: Sequence[Tuple[int, float]],
    color: str = "#2563eb",
    markers: Optional[Dict[int, Tuple[str, str]]] = None,
) -> str:
    """One line chart; ``markers`` maps step -> (color, label) vertical
    annotation lines (rollback / preempt / compile)."""
    if not series:
        return (
            f'<div class="chart"><h3>{html.escape(title)}</h3>'
            "<p class='note'>no data</p></div>"
        )
    xs = [s for s, _ in series]
    ys = [v for _, v in series]
    fx, xlo, xhi = _scale(xs, PAD, W - 8)
    fy, ylo, yhi = _scale(ys, H - 20, 12)  # y grows downward in SVG
    pts = " ".join(f"{fx(x):.1f},{fy(y):.1f}" for x, y in series)
    parts = [
        f'<svg viewBox="0 0 {W} {H}" role="img" aria-label="{html.escape(title)}">',
        f'<rect x="0" y="0" width="{W}" height="{H}" fill="#fafafa"/>',
        f'<line x1="{PAD}" y1="{H - 20}" x2="{W - 8}" y2="{H - 20}" stroke="#999"/>',
        f'<line x1="{PAD}" y1="12" x2="{PAD}" y2="{H - 20}" stroke="#999"/>',
    ]
    for step, (mcolor, label) in sorted((markers or {}).items()):
        if xlo <= step <= xhi:
            x = fx(step)
            parts.append(
                f'<line x1="{x:.1f}" y1="12" x2="{x:.1f}" y2="{H - 20}" '
                f'stroke="{mcolor}" stroke-dasharray="3,2">'
                f"<title>{html.escape(label)} @ step {step}</title></line>"
            )
    parts.append(
        f'<polyline fill="none" stroke="{color}" stroke-width="1.5" points="{pts}"/>'
    )
    parts += [
        f'<text x="{PAD}" y="{H - 6}" class="ax">{_fmt(xlo)}</text>',
        f'<text x="{W - 8}" y="{H - 6}" text-anchor="end" class="ax">{_fmt(xhi)}</text>',
        f'<text x="{PAD - 4}" y="{H - 20}" text-anchor="end" class="ax">{_fmt(ylo)}</text>',
        f'<text x="{PAD - 4}" y="16" text-anchor="end" class="ax">{_fmt(yhi)}</text>',
        "</svg>",
    ]
    return (
        f'<div class="chart"><h3>{html.escape(title)}</h3>' + "".join(parts) + "</div>"
    )


_SERIES_PALETTE = (
    "#2563eb", "#d97706", "#059669", "#dc2626", "#7c3aed",
    "#0891b2", "#be123c", "#4d7c0f",
)


def svg_multi_line(
    title: str,
    series_by_label: Dict[str, Sequence[Tuple[float, float]]],
    markers: Optional[Sequence[Tuple[float, str, str]]] = None,
) -> str:
    """One chart, one polyline per labeled series (per-replica fleet
    curves), shared axes, inline legend; ``markers`` is a list of
    ``(x, color, label)`` vertical annotation lines (a list, not a
    dict keyed by x — coincident events must all render)."""
    series_by_label = {k: list(v) for k, v in series_by_label.items() if v}
    if not series_by_label:
        return (
            f'<div class="chart"><h3>{html.escape(title)}</h3>'
            "<p class='note'>no data</p></div>"
        )
    xs = [x for s in series_by_label.values() for x, _ in s]
    ys = [y for s in series_by_label.values() for _, y in s]
    fx, xlo, xhi = _scale(xs, PAD, W - 8)
    fy, ylo, yhi = _scale(ys, H - 20, 12)
    parts = [
        f'<svg viewBox="0 0 {W} {H}" role="img" aria-label="{html.escape(title)}">',
        f'<rect x="0" y="0" width="{W}" height="{H}" fill="#fafafa"/>',
        f'<line x1="{PAD}" y1="{H - 20}" x2="{W - 8}" y2="{H - 20}" stroke="#999"/>',
        f'<line x1="{PAD}" y1="12" x2="{PAD}" y2="{H - 20}" stroke="#999"/>',
    ]
    for x, mcolor, label in sorted(markers or []):
        if xlo <= x <= xhi:
            parts.append(
                f'<line x1="{fx(x):.1f}" y1="12" x2="{fx(x):.1f}" '
                f'y2="{H - 20}" stroke="{mcolor}" stroke-dasharray="3,2">'
                f"<title>{html.escape(label)} @ {x:g}s</title></line>"
            )
    legend = []
    for i, (label, series) in enumerate(sorted(series_by_label.items())):
        color = _SERIES_PALETTE[i % len(_SERIES_PALETTE)]
        pts = " ".join(f"{fx(x):.1f},{fy(y):.1f}" for x, y in series)
        parts.append(
            f'<polyline fill="none" stroke="{color}" stroke-width="1.5" '
            f'points="{pts}"><title>{html.escape(label)}</title></polyline>'
        )
        lx = PAD + 6 + 90 * i
        legend.append(
            f'<rect x="{lx}" y="2" width="8" height="8" fill="{color}"/>'
            f'<text x="{lx + 11}" y="10" class="ax">{html.escape(label)}</text>'
        )
    parts += legend
    parts += [
        f'<text x="{PAD}" y="{H - 6}" class="ax">{_fmt(xlo)}s</text>',
        f'<text x="{W - 8}" y="{H - 6}" text-anchor="end" class="ax">{_fmt(xhi)}s</text>',
        f'<text x="{PAD - 4}" y="{H - 20}" text-anchor="end" class="ax">{_fmt(ylo)}</text>',
        f'<text x="{PAD - 4}" y="16" text-anchor="end" class="ax">{_fmt(yhi)}</text>',
        "</svg>",
    ]
    return (
        f'<div class="chart"><h3>{html.escape(title)}</h3>' + "".join(parts) + "</div>"
    )


def _heat_color(t: float) -> str:
    """0..1 -> light blue .. deep red ramp."""
    t = min(1.0, max(0.0, t))
    r = int(40 + 215 * t)
    g = int(90 + 60 * (1 - t) - 60 * t)
    b = int(220 * (1 - t) + 40 * t)
    return f"rgb({r},{max(0, g)},{b})"


def svg_heatmap(title: str, groups: List[str], steps: List[int],
                matrix: List[List[Optional[float]]], log_scale: bool = True) -> str:
    """groups x steps heatmap (matrix[g][s]); log10 color scale by
    default (norms span decades), non-finite cells black."""
    if not groups or not steps:
        return (
            f'<div class="chart"><h3>{html.escape(title)}</h3>'
            "<p class='note'>no model_stats records</p></div>"
        )
    label_w = 8 + max(len(g) for g in groups) * 7
    cw = max(4, min(28, (W - label_w - 8) // max(1, len(steps))))
    ch = 16
    width = label_w + cw * len(steps) + 8
    height = 24 + ch * len(groups) + 18
    flat = [
        v for row in matrix for v in row
        if v is not None and math.isfinite(v) and (not log_scale or v > 0)
    ]
    if log_scale:
        flat = [math.log10(v) for v in flat]
    lo, hi = (min(flat), max(flat)) if flat else (0.0, 1.0)
    if hi == lo:
        hi = lo + 1.0
    parts = [
        f'<svg viewBox="0 0 {width} {height}" role="img" aria-label="{html.escape(title)}">'
    ]
    for gi, g in enumerate(groups):
        y = 20 + gi * ch
        parts.append(
            f'<text x="{label_w - 6}" y="{y + ch - 4}" text-anchor="end" '
            f'class="ax">{html.escape(g)}</text>'
        )
        for si, step in enumerate(steps):
            v = matrix[gi][si]
            if v is None or not math.isfinite(v) or (log_scale and v <= 0):
                fill = "#111"
                tip = f"{g} @ step {step}: non-finite/none"
            else:
                t = ((math.log10(v) if log_scale else v) - lo) / (hi - lo)
                fill = _heat_color(t)
                tip = f"{g} @ step {step}: {_fmt(v)}"
            parts.append(
                f'<rect x="{label_w + si * cw}" y="{y}" width="{cw - 1}" '
                f'height="{ch - 1}" fill="{fill}"><title>{html.escape(tip)}</title></rect>'
            )
    parts.append(
        f'<text x="{label_w}" y="{height - 4}" class="ax">steps '
        f"{steps[0]}..{steps[-1]}; color = log10 scale {_fmt(lo)}..{_fmt(hi)}</text>"
    )
    parts.append("</svg>")
    return (
        f'<div class="chart"><h3>{html.escape(title)}</h3>' + "".join(parts) + "</div>"
    )


# ---------------------------------------------------------------------------
# report assembly
# ---------------------------------------------------------------------------


def summarize(data: RunData) -> List[Tuple[str, Any]]:
    steps = data.steps()
    loss = data.series("loss")
    mfu = data.series("mfu")
    dw = data.series("data_wait_s")
    rollbacks = [e for e in data.events if e.get("event") == "rollback"]
    preempts = [e for e in data.events if e.get("event") == "preempt_save"]
    skips = [e for e in data.events if e.get("event") == "data_skip"]
    nonfinite = [
        s for s in steps
        if data.records[s].get("found_inf")
        or (isinstance(data.records[s].get("loss"), float)
            and math.isnan(data.records[s]["loss"]))
    ]
    mem_peak = max(
        (r.get("mem", {}).get("fit_peak_bytes", 0) for r in data.records.values()),
        default=0,
    )
    rows: List[Tuple[str, Any]] = [
        ("status", data.status()),
        ("steps logged", f"{steps[0]}..{steps[-1]} ({len(steps)} records)"
         if steps else "none"),
        ("final loss", _fmt(loss[-1][1]) if loss else "n/a"),
        ("best loss", _fmt(min(v for _, v in loss)) if loss else "n/a"),
        ("mean MFU", _fmt(sum(v for _, v in mfu) / len(mfu)) if mfu else "n/a"),
        ("total data wait", f"{dw[-1][1]:.2f}s" if dw else "n/a"),
        ("non-finite steps", f"{len(nonfinite)} ({nonfinite[:8]})"
         if nonfinite else "0"),
        ("rollbacks", len(rollbacks)),
        ("preempt saves", len(preempts)),
        ("data skips", len(skips)),
        ("compiles observed",
         f"{len(data.compiles)} ({sum(c.get('elapsed_s', 0) for c in data.compiles):.1f}s total)"
         if data.compiles else "0"),
        ("peak memory watermark", _bytes(mem_peak) if mem_peak else "n/a"),
    ]
    if data.trace_summary:
        ts = data.trace_summary
        rows.append((
            "trace export",
            f"{ts['events']} events / {ts['lanes']} lanes / "
            f"{ts['span_seconds']}s total span",
        ))
    return rows


def _bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:.1f} {unit}"
        n /= 1024
    return f"{n:.1f} TiB"


def annotation_markers(data: RunData) -> Dict[int, Tuple[str, str]]:
    markers: Dict[int, Tuple[str, str]] = {}
    for e in data.events:
        step = e.get("step")
        if not isinstance(step, (int, float)):
            continue
        kind = e.get("event")
        if kind == "rollback":
            markers[int(step)] = ("#dc2626", f"rollback ({e.get('reason', '')})")
        elif kind == "preempt_save":
            markers[int(step)] = ("#d97706", f"preempt ({e.get('cause', '')})")
        elif kind == "eval_empty":
            markers.setdefault(int(step), ("#7c3aed", "eval_empty"))
    # compile events: flight rows carry wall-clock ts; map each onto the
    # nearest step record that has a ts (flight step copies do)
    step_ts = [
        (data.records[s]["ts"], s) for s in data.steps()
        if isinstance(data.records[s].get("ts"), (int, float))
    ]
    if step_ts:
        step_ts.sort()
        for c in data.compiles:
            ts = c.get("ts")
            if not isinstance(ts, (int, float)):
                continue
            nearest = min(step_ts, key=lambda p: abs(p[0] - ts))[1]
            markers.setdefault(
                nearest,
                ("#059669",
                 f"compile {c.get('fn', '?')} {c.get('elapsed_s', 0)}s"),
            )
    return markers


def event_rows(data: RunData) -> List[List[str]]:
    rows = []
    for e in data.events:
        kind = e.get("event", "?")
        detail = {
            k: v for k, v in e.items()
            if k not in ("event", "seq", "ts") and v is not None
        }
        rows.append([str(kind), str(e.get("step", "")),
                     json.dumps(detail, default=str)[:240]])
    for c in data.compiles:
        rows.append([
            "compile", "",
            f"{c.get('fn', '?')}: {c.get('elapsed_s', '?')}s, "
            f"{c.get('diff', '')}"
            + (" [persistent-cache hit]" if c.get("cache_hit") else ""),
        ])
    return rows


def heatmap_inputs(data: RunData, key: str):
    ms_rows = data.model_stats_rows()
    if not ms_rows:
        return [], [], []
    groups = ms_rows[0].get("groups", [])
    steps = [int(r.get("step", i)) for i, r in enumerate(ms_rows)]
    matrix: List[List[Optional[float]]] = []
    for gi in range(len(groups)):
        row = []
        for r in ms_rows:
            vals = r.get(key) or []
            v = vals[gi] if gi < len(vals) else None
            row.append(float(v) if isinstance(v, (int, float)) else None)
        matrix.append(row)
    return groups, steps, matrix


CSS = """
body { font: 14px/1.45 system-ui, sans-serif; margin: 24px auto; max-width: 900px; color: #1f2937; }
h1 { font-size: 20px; } h2 { font-size: 16px; margin-top: 28px; border-bottom: 1px solid #e5e7eb; }
h3 { font-size: 13px; margin: 8px 0 2px; color: #374151; }
table { border-collapse: collapse; width: 100%; font-size: 13px; }
td, th { border: 1px solid #e5e7eb; padding: 3px 8px; text-align: left; vertical-align: top; }
th { background: #f3f4f6; }
.note { color: #92400e; background: #fef3c7; padding: 2px 8px; display: inline-block; }
.ax { font-size: 9px; fill: #6b7280; }
svg { width: 100%; height: auto; }
.chart { margin-bottom: 10px; }
code { background: #f3f4f6; padding: 0 3px; }
"""


def render_html(data: RunData, title: str) -> str:
    markers = annotation_markers(data)
    out = [
        "<!doctype html>",
        '<html lang="en"><head><meta charset="utf-8">',
        f"<title>{html.escape(title)}</title>",
        f"<style>{CSS}</style></head><body>",
        f"<h1>{html.escape(title)}</h1>",
        "<p>" + " · ".join(html.escape(s) for s in data.sources) + "</p>",
    ]
    for n in data.notes:
        out.append(f'<p class="note">{html.escape(n)}</p>')

    out.append("<h2>Summary</h2><table>")
    for k, v in summarize(data):
        out.append(
            f"<tr><th>{html.escape(str(k))}</th><td>{html.escape(str(v))}</td></tr>"
        )
    out.append("</table>")

    gp = train_goodput_rows(data)
    if gp:
        out.append("<h2>Goodput ledger</h2>")
        out.append(_html_table(_GOODPUT_TRAIN_COLS, gp))
    if data.profile:
        out.append("<h2>On-demand profile</h2>")
        out.append(f"<p>{html.escape(profile_caption(data.profile))}</p>")
        out.append(_html_table(_PROFILE_COLS, profile_rows(data.profile)))

    out.append("<h2>Curves</h2>")
    out.append(svg_line("loss", data.series("loss"), "#2563eb", markers))
    out.append(svg_line("learning rate", data.series("lr"), "#7c3aed", markers))
    out.append(svg_line("MFU", data.series("mfu"), "#059669", markers))
    out.append(svg_line(
        "data wait (cumulative s)", data.series("data_wait_s"), "#d97706", markers
    ))
    out.append(svg_line(
        "tokens/s", data.series("tokens_per_sec"), "#0891b2", markers
    ))

    out.append("<h2>Per-layer-group statistics</h2>")
    for key, label in (
        ("grad_norm", "grad norm by layer group"),
        ("update_ratio", "update/param ratio by layer group"),
    ):
        groups, steps, matrix = heatmap_inputs(data, key)
        out.append(svg_heatmap(label, groups, steps, matrix))

    out.append("<h2>Memory watermarks</h2>")
    out.append(svg_line(
        "host RSS (bytes)", data.series("mem", "host_rss_bytes"), "#be123c", markers
    ))
    dev = data.series("mem", "device_peak_bytes")
    if dev:
        out.append(svg_line("device peak (bytes)", dev, "#9d174d", markers))

    out.append("<h2>Events &amp; compiles</h2>")
    rows = event_rows(data)
    if rows:
        out.append("<table><tr><th>event</th><th>step</th><th>detail</th></tr>")
        for r in rows:
            out.append(
                "<tr>" + "".join(f"<td>{html.escape(c)}</td>" for c in r) + "</tr>"
            )
        out.append("</table>")
    else:
        out.append("<p>no events recorded</p>")
    out.append("</body></html>")
    return "\n".join(out) + "\n"


def fleet_summary(data: FleetData) -> List[Tuple[str, Any]]:
    reps = data.replicas()
    span = 0.0
    all_ts = [r["ts"] for rows in data.samples.values() for r in rows]
    all_ts += [r["ts"] for r in data.router_rows]
    if all_ts:
        span = max(all_ts) - min(all_ts)
    pools = sorted({data.last(r).get("pool", "?") for r in reps})
    proxied = max(
        (r.get("handoff_bytes_proxied", 0) or 0 for r in data.router_rows),
        default=0,
    )
    direct = sum(
        data.last(r).get("handoff_bytes_direct", 0) or 0 for r in reps
    )
    ups = sum(1 for e in data.scale_events if e.get("action") == "scale_up")
    downs = sum(
        1 for e in data.scale_events if e.get("action") == "scale_down"
    )
    mig_sent = sum(
        int(data.last(r).get("migrate_sent_total", 0) or 0) for r in reps
    )
    mig_adopted = sum(
        int(data.last(r).get("migrate_adopted_total", 0) or 0) for r in reps
    )
    mig_failed = sum(
        int(data.last(r).get("migrate_failed_total", 0) or 0) for r in reps
    )
    return [
        ("replicas seen", f"{len(reps)} ({', '.join(reps)})" if reps else "0"),
        ("pools", ", ".join(pools) if pools else "n/a"),
        ("window", f"{span:.1f}s of samples"),
        ("scale events", f"{ups} up / {downs} down"),
        ("handoff bytes", f"{_bytes(direct)} direct / "
                          f"{_bytes(proxied)} proxied via router"),
        ("prefix migrations", f"{mig_sent} sent / {mig_adopted} blocks "
                              f"adopted / {mig_failed} failed"),
        ("router samples", len(data.router_rows)),
    ]


def tenant_rows(data: FleetData) -> List[List[str]]:
    """Per-tenant front-door rows off the LAST router sample.  The
    router folds tenant labels through its top-k cardinality cap before
    logging (docs/serving.md "Multi-tenant isolation"), so this table is
    bounded no matter how many tenant names traffic invents; a None
    quota knob renders as unlimited."""
    if not data.router_rows:
        return []
    tenants = data.router_rows[-1].get("tenants") or {}
    rows = []
    for name in sorted(tenants):
        t = tenants[name] or {}

        def knob(k):
            v = t.get(k)
            return "unlimited" if v is None else str(v)

        rows.append([
            str(name), str(t.get("weight", "")), knob("rps"),
            knob("max_inflight"), str(int(t.get("in_flight", 0) or 0)),
        ])
    return rows


_TENANT_COLS = ("tenant", "weight", "rps", "max in-flight", "in flight")


# ---------------------------------------------------------------------------
# goodput ledger + on-demand profile views (docs/observability.md
# "Goodput ledger" / "On-demand profiling")
# ---------------------------------------------------------------------------


def _html_table(cols, rows) -> str:
    out = ["<table><tr>" + "".join(
        f"<th>{html.escape(str(c))}</th>" for c in cols) + "</tr>"]
    for r in rows:
        out.append("<tr>" + "".join(
            f"<td>{html.escape(str(c))}</td>" for c in r) + "</tr>")
    out.append("</table>")
    return "\n".join(out)


def _md_table(cols, rows) -> List[str]:
    lines = ["| " + " | ".join(str(c) for c in cols) + " |",
             "|" + "---|" * len(cols)]
    for r in rows:
        lines.append("| " + " | ".join(
            str(c).replace("|", "\\|") for c in r) + " |")
    return lines


def train_goodput_rows(data: RunData) -> List[List[str]]:
    """Stacked time-ledger breakdown off the LAST step record carrying
    one (core/engine.py ``time_ledger``: the fit's cumulative wall
    seconds per bucket, exhaustive by construction)."""
    for s in reversed(data.steps()):
        led = data.records[s].get("time_ledger")
        if isinstance(led, dict) and led:
            total = sum(float(v) for v in led.values()) or 1.0
            return [
                [k, f"{float(v):.3f}", f"{100.0 * float(v) / total:.1f}%"]
                for k, v in sorted(
                    led.items(), key=lambda kv: -float(kv[1])
                )
            ]
    return []


_GOODPUT_TRAIN_COLS = ("bucket", "seconds", "share")


def fleet_goodput_rows(data: FleetData) -> List[List[str]]:
    """Per-replica serving goodput off each replica's LAST fleet-log
    sample (the federated scheduler time ledger): goodput_frac =
    device-COVERED seconds / non-idle wall, where covered = non-idle
    wall minus host_gap_s (host time the device sat starved waiting for
    its next dispatch — the scheduler's ``host_gap_s``).  device_util = the same numerator over TOTAL wall including
    idle."""
    rows = []
    for r in data.replicas():
        last = data.last(r)
        wall = float(last.get("sched_wall_s", 0) or 0)
        if wall <= 0:
            continue
        dd = float(last.get("sched_device_decode_s", 0) or 0)
        dp = float(last.get("sched_device_prefill_s", 0) or 0)
        rb = float(last.get("sched_readback_s", 0) or 0)
        idle = float(last.get("sched_idle_s", 0) or 0)
        gap = float(last.get("sched_host_gap_s", 0) or 0)
        busy = max(wall - idle, 1e-9)
        covered = max(busy - gap, 0.0)
        rows.append([
            r, f"{covered / busy:.3f}", f"{covered / wall:.3f}",
            f"{dd:.2f}", f"{dp:.2f}",
            f"{float(last.get('sched_host_sched_s', 0) or 0):.2f}",
            f"{rb:.2f}",
            f"{float(last.get('sched_stream_flush_s', 0) or 0):.2f}",
            f"{gap:.3f}", f"{idle:.2f}", f"{wall:.2f}",
        ])
    return rows


_FLEET_GOODPUT_COLS = (
    "replica", "goodput_frac", "device_util", "decode_s", "prefill_s",
    "host_s", "readback_s", "stream_s", "gap_s", "idle_s", "wall_s",
)


def fleet_token_rows(data: FleetData) -> List[List[str]]:
    """Per-replica token-ledger dispositions off the last sample, with
    the closure remainder made explicit: admitted minus the terminal
    dispositions is exactly the tokens still in live decode slots."""
    rows = []
    for r in data.replicas():
        last = data.last(r)
        adm = last.get("tok_admitted")
        if adm is None:
            continue
        adm = int(adm)
        dlv = int(last.get("tok_delivered", 0) or 0)
        ev = int(last.get("tok_evicted_lost", 0) or 0)
        pr = int(last.get("tok_preempt_refunded", 0) or 0)
        sh = int(last.get("tok_shed_after_admit", 0) or 0)
        rem = adm - (dlv + ev + pr + sh)
        rows.append([
            r, str(adm), str(dlv), str(ev), str(pr), str(sh),
            "closed" if rem == 0 else f"{rem} in flight",
        ])
    return rows


_FLEET_TOKEN_COLS = (
    "replica", "admitted", "delivered", "evicted_lost",
    "preempt_refunded", "shed_after_admit", "books",
)


def profile_rows(profile: Dict[str, Any]) -> List[List[str]]:
    rows = []
    for op in (profile.get("top_ops") or [])[:20]:
        rows.append([
            str(op.get("op", "?"))[:60],
            str(op.get("category", "")),
            str(int(op.get("occurrences", 0) or 0)),
            f"{float(op.get('total_us', 0) or 0):.1f}",
            f"{float(op.get('self_us', 0) or 0):.1f}",
            f"{100.0 * float(op.get('self_frac', 0) or 0):.1f}%",
        ])
    return rows


_PROFILE_COLS = ("op", "category", "#", "total us", "self us", "self %")


def profile_caption(profile: Dict[str, Any]) -> str:
    # busy microseconds (the union of each plane's events, summed over
    # planes: utils/profiler.device_host_split), shown beside each other
    dev = float(profile.get("device_us", 0) or 0)
    host = float(profile.get("host_us", 0) or 0)
    tot = (dev + host) or 1.0
    who = profile.get("replica_id") or (
        f"{profile.get('captured', '?')}/{profile.get('requested', '?')} "
        "replicas" if "captured" in profile else "?"
    )
    return (
        f"{profile.get('seconds', '?')}s capture on {who}, "
        f"source: {profile.get('source', 'fleet aggregate')}; "
        f"device busy {dev / 1e6:.3f}s ({100 * dev / tot:.1f}%) / "
        f"host busy {host / 1e6:.3f}s ({100 * host / tot:.1f}%)"
    )


_FLEET_CURVES = (
    ("ttft_p99_s", "TTFT p99 (s) per replica"),
    ("itl_p99_s", "ITL p99 (s) per replica"),
    ("latency_p99_s", "latency p99 (s) per replica"),
    ("occupancy", "continuous-batch occupancy per replica"),
    ("depth", "reported queue depth per replica"),
    ("kv_blocks_used", "KV arena blocks used per replica"),
    # cache-survival view (docs/serving.md "KV lifecycle"): published
    # prefix blocks per replica across drains/migrations — a survivor
    # adopting a drained peer's prefixes shows as a step UP here while
    # the drained replica's curve ends — plus the spill tier's traffic
    ("prefix_cached_blocks", "prefix-cache survival: published prefix "
                             "blocks per replica"),
    ("prefix_spill_entries", "host-RAM spill tier entries per replica"),
    ("prefix_readmits_total", "spill readmits (cumulative) per replica"),
)

_FLEET_STATE_COLS = (
    "pool", "state", "depth", "occupancy", "ttft_p99_s", "itl_p99_s",
    "latency_p99_s",
    "kv_blocks_used", "kv_blocks_available", "tokens_out_total",
    "handoff_exports_total", "handoff_adopts_total",
    "prefix_cached_blocks", "prefix_spill_entries",
    "prefix_spills_total", "prefix_readmits_total",
    "migrate_sent_total", "migrate_adopted_total", "migrate_failed_total",
)


def render_fleet_html(data: FleetData, title: str) -> str:
    markers = data.markers()
    out = [
        "<!doctype html>",
        '<html lang="en"><head><meta charset="utf-8">',
        f"<title>{html.escape(title)}</title>",
        f"<style>{CSS}</style></head><body>",
        f"<h1>{html.escape(title)}</h1>",
        "<p>" + " · ".join(html.escape(s) for s in data.sources) + "</p>",
    ]
    for n in data.notes:
        out.append(f'<p class="note">{html.escape(n)}</p>')
    out.append("<h2>Summary</h2><table>")
    for k, v in fleet_summary(data):
        out.append(
            f"<tr><th>{html.escape(str(k))}</th><td>{html.escape(str(v))}</td></tr>"
        )
    out.append("</table>")

    out.append("<h2>Per-replica curves</h2>")
    for key, label in _FLEET_CURVES:
        out.append(svg_multi_line(
            label,
            {r: data.series(r, key) for r in data.replicas()},
            markers,
        ))

    out.append("<h2>Handoff breakdown</h2>")
    out.append("<table><tr><th>replica</th><th>pool</th>"
               "<th>direct bytes</th><th>proxy bytes</th>"
               "<th>exports</th><th>adopts</th></tr>")
    for r in data.replicas():
        last = data.last(r)
        out.append(
            "<tr>" + "".join(
                f"<td>{html.escape(str(c))}</td>" for c in (
                    r, last.get("pool", "?"),
                    _bytes(last.get("handoff_bytes_direct", 0) or 0),
                    _bytes(last.get("handoff_bytes_proxy", 0) or 0),
                    int(last.get("handoff_exports_total", 0) or 0),
                    int(last.get("handoff_adopts_total", 0) or 0),
                )
            ) + "</tr>"
        )
    if data.router_rows:
        rr = data.router_rows[-1]
        out.append(
            "<tr>" + "".join(
                f"<td>{html.escape(str(c))}</td>" for c in (
                    "(router)", "front door",
                    "—", _bytes(rr.get("handoff_bytes_proxied", 0) or 0),
                    f"{int(rr.get('handoff_count', 0) or 0)} chains",
                    f"{(rr.get('handoff_seconds_sum', 0) or 0):.2f}s total",
                )
            ) + "</tr>"
        )
    out.append("</table>")

    gp = fleet_goodput_rows(data)
    if gp:
        out.append("<h2>Goodput breakdown</h2>")
        out.append(_html_table(_FLEET_GOODPUT_COLS, gp))
    toks = fleet_token_rows(data)
    if toks:
        out.append("<h2>Token ledger</h2>")
        out.append(_html_table(_FLEET_TOKEN_COLS, toks))
    if data.profile:
        out.append("<h2>On-demand profile</h2>")
        out.append(f"<p>{html.escape(profile_caption(data.profile))}</p>")
        out.append(_html_table(_PROFILE_COLS, profile_rows(data.profile)))

    trs = tenant_rows(data)
    if trs:
        out.append("<h2>Tenants (front door)</h2>")
        out.append("<table><tr>" + "".join(
            f"<th>{c}</th>" for c in _TENANT_COLS) + "</tr>")
        for tr in trs:
            out.append("<tr>" + "".join(
                f"<td>{html.escape(c)}</td>" for c in tr) + "</tr>")
        out.append("</table>")

    out.append("<h2>Last known per-replica state</h2>")
    out.append("<table><tr><th>replica</th>" + "".join(
        f"<th>{c}</th>" for c in _FLEET_STATE_COLS) + "</tr>")
    for r in data.replicas():
        last = data.last(r)
        out.append("<tr><td>" + html.escape(r) + "</td>" + "".join(
            f"<td>{html.escape(str(last.get(c, '')))}</td>"
            for c in _FLEET_STATE_COLS
        ) + "</tr>")
    out.append("</table>")

    if data.scale_events:
        out.append("<h2>Scale events</h2>")
        out.append("<table><tr><th>t (s)</th><th>pool</th><th>action</th>"
                   "<th>target</th><th>reason</th></tr>")
        for e in data.scale_events:
            out.append("<tr>" + "".join(
                f"<td>{html.escape(str(c))}</td>" for c in (
                    f"{data.rel(e['ts']):g}", e.get("pool", "fleet"),
                    e.get("action", "?"), e.get("target", ""),
                    str(e.get("reason", ""))[:160],
                )
            ) + "</tr>")
        out.append("</table>")
    out.append("</body></html>")
    return "\n".join(out) + "\n"


def render_fleet_markdown(data: FleetData, title: str) -> str:
    lines = [f"# {title}", "", "sources: " + "; ".join(data.sources), ""]
    for n in data.notes:
        lines.append(f"> NOTE: {n}")
    lines += ["", "## Summary", "", "| key | value |", "|---|---|"]
    for k, v in fleet_summary(data):
        lines.append(f"| {k} | {v} |")
    gp = fleet_goodput_rows(data)
    if gp:
        lines += ["", "## Goodput breakdown", ""]
        lines += _md_table(_FLEET_GOODPUT_COLS, gp)
    toks = fleet_token_rows(data)
    if toks:
        lines += ["", "## Token ledger", ""]
        lines += _md_table(_FLEET_TOKEN_COLS, toks)
    if data.profile:
        lines += ["", "## On-demand profile", "",
                  profile_caption(data.profile), ""]
        lines += _md_table(_PROFILE_COLS, profile_rows(data.profile))
    trs = tenant_rows(data)
    if trs:
        lines += ["", "## Tenants (front door)", "",
                  "| " + " | ".join(_TENANT_COLS) + " |",
                  "|" + "---|" * len(_TENANT_COLS)]
        for tr in trs:
            lines.append("| " + " | ".join(tr) + " |")
    lines += ["", "## Last known per-replica state", "",
              "| replica | " + " | ".join(_FLEET_STATE_COLS) + " |",
              "|" + "---|" * (len(_FLEET_STATE_COLS) + 1)]
    for r in data.replicas():
        last = data.last(r)
        lines.append("| " + " | ".join(
            [r] + [str(last.get(c, "")) for c in _FLEET_STATE_COLS]
        ) + " |")
    if data.scale_events:
        lines += ["", "## Scale events", "",
                  "| t (s) | pool | action | reason |", "|---|---|---|---|"]
        for e in data.scale_events:
            lines.append(
                f"| {data.rel(e['ts']):g} | {e.get('pool', 'fleet')} | "
                f"{e.get('action', '?')} | "
                f"{str(e.get('reason', ''))[:120]} |"
            )
    return "\n".join(lines) + "\n"


def find_fleet_artifact(args) -> Optional[str]:
    """Resolve the fleet JSONL: an explicit ``--fleet PATH`` wins, then
    the conventional names under ``--run-dir``, ``$PFX_FLIGHT_DIR``, and
    ``./artifacts``."""
    if args.fleet and args.fleet != "auto":
        return args.fleet
    candidates = []
    if args.run_dir:
        candidates += [
            os.path.join(args.run_dir, "fleet_metrics.jsonl"),
            os.path.join(args.run_dir, "artifacts", "fleet_metrics.jsonl"),
        ]
    candidates.append(os.path.join(
        os.environ.get("PFX_FLIGHT_DIR") or "artifacts",
        "fleet_metrics.jsonl",
    ))
    return _first_existing(*candidates)


def render_markdown(data: RunData, title: str) -> str:
    lines = [f"# {title}", "", "sources: " + "; ".join(data.sources), ""]
    for n in data.notes:
        lines.append(f"> NOTE: {n}")
    lines += ["", "## Summary", "", "| key | value |", "|---|---|"]
    for k, v in summarize(data):
        lines.append(f"| {k} | {v} |")
    gp = train_goodput_rows(data)
    if gp:
        lines += ["", "## Goodput ledger", ""]
        lines += _md_table(_GOODPUT_TRAIN_COLS, gp)
    if data.profile:
        lines += ["", "## On-demand profile", "",
                  profile_caption(data.profile), ""]
        lines += _md_table(_PROFILE_COLS, profile_rows(data.profile))
    loss = data.series("loss")
    if loss:
        lines += ["", "## Loss", "", "| step | loss |", "|---|---|"]
        stride = max(1, len(loss) // 40)
        for s, v in loss[::stride]:
            lines.append(f"| {s} | {_fmt(v)} |")
    ms = data.model_stats_rows()
    if ms:
        last = ms[-1]
        lines += ["", f"## Layer groups (step {last.get('step', '?')})", "",
                  "| group | grad_norm | param_norm | update_ratio | nonfinite_frac |",
                  "|---|---|---|---|---|"]
        for i, g in enumerate(last.get("groups", [])):
            cells = [
                _fmt(last[k][i]) if i < len(last.get(k) or []) else ""
                for k in ("grad_norm", "param_norm", "update_ratio",
                          "nonfinite_frac")
            ]
            lines.append("| " + " | ".join([g] + cells) + " |")
    rows = event_rows(data)
    if rows:
        lines += ["", "## Events", "", "| event | step | detail |", "|---|---|---|"]
        for r in rows:
            lines.append("| " + " | ".join(c.replace("|", "\\|") for c in r) + " |")
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--metrics", help="engine metrics JSONL")
    ap.add_argument("--flight", help="flight_recorder.jsonl dump")
    ap.add_argument("--trace", help="Chrome-trace JSON export")
    ap.add_argument("--run-dir", help="directory to scan for the conventional names")
    ap.add_argument("--profile", nargs="?", const="auto", default=None,
                    help="inline an on-demand profile capture's "
                    "profile_summary.json (optional path; default scans "
                    "--run-dir / $PFX_FLIGHT_DIR profiles/)")
    ap.add_argument("--fleet", nargs="?", const="auto", default=None,
                    help="render the FLEET report from the router's "
                    "fleet_metrics.jsonl instead of a training run "
                    "(optional path; default scans --run-dir / "
                    "$PFX_FLIGHT_DIR / ./artifacts)")
    ap.add_argument("-o", "--out", default="report.html",
                    help="output path ('-' = stdout)")
    ap.add_argument("--format", choices=("html", "md"), default=None,
                    help="default: by --out extension (html unless .md)")
    ap.add_argument("--title", default="PaddleFleetX-TPU run report")
    args = ap.parse_args(argv)

    fmt = args.format or ("md" if args.out.endswith(".md") else "html")
    if args.fleet is not None:
        path = find_fleet_artifact(args)
        data = FleetData()
        if path:
            try:
                data.add(path)
            except OSError as e:
                data.notes.append(f"could not read fleet artifact {path}: {e!r}")
        if not data.sources:
            print("report.py: no readable fleet artifact (give --fleet "
                  "PATH or point --run-dir/$PFX_FLIGHT_DIR at the "
                  "router's artifacts)", file=sys.stderr)
            return 2
        if args.title == "PaddleFleetX-TPU run report":
            args.title = "PaddleFleetX-TPU fleet report"
        ppath = find_profile_summary(args)
        if ppath:
            try:
                data.add_profile(ppath)
            except (OSError, ValueError) as e:
                data.notes.append(
                    f"could not read profile summary {ppath}: {e!r}")
        doc = (render_fleet_markdown if fmt == "md"
               else render_fleet_html)(data, args.title)
        return _emit(doc, args, fmt, what=(
            f"{sum(len(v) for v in data.samples.values())} replica "
            f"samples, {len(data.scale_events)} scale events"
        ))

    data = find_artifacts(args)
    ppath = find_profile_summary(args)
    if ppath:
        try:
            data.add_profile(ppath)
        except (OSError, ValueError) as e:
            data.notes.append(f"could not read profile summary {ppath}: {e!r}")
    if not data.sources:
        print("report.py: no readable artifact (give --metrics/--flight/"
              "--trace or --run-dir)", file=sys.stderr)
        for n in data.notes:
            print(f"  {n}", file=sys.stderr)
        return 2
    doc = (render_markdown if fmt == "md" else render_html)(data, args.title)
    return _emit(doc, args, fmt, what=(
        f"{len(data.records)} step records, {len(data.events)} events, "
        f"{len(data.compiles)} compiles"
    ))


def _emit(doc: str, args, fmt: str, what: str) -> int:
    if args.out == "-":
        sys.stdout.write(doc)
    else:
        with open(args.out, "w") as f:
            f.write(doc)
        kind = "markdown" if fmt == "md" else "self-contained HTML"
        print(f"report.py: wrote {kind} report to {args.out} ({what})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
