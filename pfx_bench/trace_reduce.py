#!/usr/bin/env python3
"""From a jax.profiler trace to device busy/idle, op self times, op
categories and attributed idle gaps.  The benchmark's own reduction: the
program's ``device_host_split`` sums event durations, which counts
overlapping events twice and cannot give an idle share; this takes the
UNION of the intervals in which an operation ran on the device.

``reduce(planes)`` works on a neutral structure, so a small recorded trace
(selftest/trace_fixture.json) checks it without a profiler:

    [{"name": "/device:TPU:0",
      "lines": [{"name": "XLA Ops", "events": [[name, start_ns, dur_ns], ...]}]},
     {"name": "/host:CPU", "lines": [...]}]

``python trace_reduce.py <trace_dir>`` loads the newest ``*.xplane.pb``
under it (needs jax for ProfileData, never touches a backend) and prints
the reduction as one JSON line."""

import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
               "all-to-all", "collective-broadcast")
# On the TPU an event of the "XLA Ops" line is named by its whole HLO
# instruction: "%name = <type> opcode(operands...), attributes".  The
# opcode is the first lower-case word that opens a parenthesis after a
# space (types open theirs after ":" , ")" or a letter+digit, never a space).
_OPCODE = re.compile(r" ([a-z][a-z\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_KIND = re.compile(r"kind=(k\w+)")
_SHAPE = re.compile(r"[a-z]+\d*\[[\d,]*\]")


def describe(raw: str):
    """(label, category) of a device event name.  A Pallas kernel is a
    custom call to Mosaic (``custom_call_target="tpu_custom_call"``): XLA:TPU
    lowers every ``pallas_call`` to one, and nothing else in this program
    is.  Operands named ``%custom-call.N`` do not make their consumer one."""
    head, sep, rest = raw.partition(" = ")
    if not sep:  # already a plain name (a recorded fixture, a CPU trace)
        op = raw.split(".")[0].lstrip("%")
        cat = ("collective" if op.startswith(COLLECTIVES) else
               "pallas" if "tpu_custom_call" in raw else "other")
        return raw[:120], cat
    m = _OPCODE.search(" " + rest)
    opcode = m.group(1) if m else "?"
    label = f"{head.lstrip('%')} {opcode}"
    shape = _SHAPE.search(rest)
    if shape:
        label += f" {shape.group(0)}"
    cat = "other"
    if opcode.startswith(COLLECTIVES):
        cat = "collective"
    elif opcode == "custom-call":
        t = _TARGET.search(rest)
        target = t.group(1) if t else "?"
        label += f" {target}"
        if target == "tpu_custom_call":
            cat = "pallas"
    elif opcode == "fusion":
        k = _KIND.search(rest)
        if k:
            label += f" {k.group(1)}"
    return label[:120], cat


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _self_times(events):
    """Self time of each event on one line (children nest inside parents,
    e.g. the ops of a ``while`` body): {event index: self_ns}."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    self_ns = {i: events[i][2] for i in order}
    stack = []  # indices of open events
    for i in order:
        start, end = events[i][1], events[i][1] + events[i][2]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            parent = stack[-1]
            p_end = events[parent][1] + events[parent][2]
            self_ns[parent] -= max(0, min(end, p_end) - start)
        stack.append(i)
    return self_ns


def _attribute(gap, host):
    """The host event that best explains an idle gap: the shortest one
    that covers at least half of it (the innermost span over the gap, not
    the thread's outermost loop); failing that, the one with most overlap."""
    a, b = gap
    best, best_dur = None, None
    most, most_overlap = "unattributed", 0
    for name, start, dur in host:
        overlap = min(b, start + dur) - max(a, start)
        if overlap <= 0:
            continue
        if 2 * overlap >= b - a and (best_dur is None or dur < best_dur):
            best, best_dur = name, dur
        if overlap > most_overlap:
            most, most_overlap = name, overlap
    return best if best is not None else most


def reduce(planes, top: int = 10, gaps_considered: int = 200) -> dict:
    device = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    if not device:
        return {"device_planes": 0}
    per_plane = []
    lo, hi = None, None
    for p in device:
        lines = [ln for ln in p["lines"] if ln["name"] == OPS_LINE] or \
                [ln for ln in p["lines"] if ln["name"] not in ("Steps", "XLA Modules")]
        events = [e for ln in lines for e in ln["events"] if e[2] > 0]
        if not events:
            continue
        merged = _union([(e[1], e[1] + e[2]) for e in events])
        lo = merged[0][0] if lo is None else min(lo, merged[0][0])
        hi = merged[-1][1] if hi is None else max(hi, merged[-1][1])
        per_plane.append((p["name"], events, merged, lines))
    if not per_plane:
        return {"device_planes": len(device), "device_events": 0}
    window_ns = hi - lo
    busy = [sum(b - a for a, b in merged) for _, _, merged, _ in per_plane]
    by_name, by_cat = {}, {"collective": 0.0, "pallas": 0.0, "other": 0.0}
    for _, _, _, lines in per_plane:
        for ln in lines:
            evs = [e for e in ln["events"] if e[2] > 0]
            for i, ns in _self_times(evs).items():
                e = evs[i]
                label, cat = describe(e[0])
                by_name[label] = by_name.get(label, 0.0) + ns
                by_cat[cat] += ns
    n = len(per_plane)
    # idle gaps of the first device plane, inside the common window
    merged0 = per_plane[0][2]
    gaps = [(merged0[i][1], merged0[i + 1][0]) for i in range(len(merged0) - 1)]
    if merged0[0][0] > lo:
        gaps.append((lo, merged0[0][0]))
    if merged0[-1][1] < hi:
        gaps.append((merged0[-1][1], hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [e for p in planes if not p["name"].startswith("/device:")
            for ln in p["lines"] for e in ln["events"] if e[2] > 0]
    attributed = {}
    for g in gaps[:gaps_considered]:
        who = _attribute(g, host)
        attributed[who] = attributed.get(who, 0.0) + (g[1] - g[0])
    busy_s = sum(busy) / n / 1e9
    op_ns = sum(by_name.values()) / n
    return {
        "device_planes": n,
        "window_s": window_ns / 1e9,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / (window_ns / 1e9) if window_ns else None,
        # per chip: self time in each category over that chip's busy time
        "category_share": {k: (v / n / 1e9) / busy_s if busy_s else None
                           for k, v in by_cat.items()},
        "op_self_s_over_busy_s": op_ns / 1e9 / busy_s if busy_s else None,
        "device_ops": [[k, v / n / 1e9] for k, v in
                       sorted(by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / 1e9] for k, v in
                      sorted(attributed.items(), key=lambda kv: -kv[1])[:top]
                      if v >= 1e4],  # 10 us: less is a rounding of the clock
        "gap_count": len(gaps),
        "longest_gap_s": (gaps[0][1] - gaps[0][0]) / 1e9 if gaps else 0.0,
    }


# -- xplane loading (the one part that needs jax) ----------------------------


def newest_xplanes(trace_dir: str):
    runs = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*")))
    return sorted(glob.glob(os.path.join(runs[-1], "*.xplane.pb"))) if runs else []


def load_xplane(path: str, host_min_ns: int = 20000):
    """Host events shorter than ``host_min_ns`` are dropped: the Python
    tracer writes millions, and none can explain a gap worth listing."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        is_dev = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                dur = int(ev.duration_ns)
                if not is_dev and dur < host_min_ns:
                    continue
                events.append([ev.name, int(ev.start_ns), dur])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def structure(planes, names: int = 12):
    """What the trace holds, for a reader of the output directory."""
    return [{"plane": p["name"], "lines": [
        {"line": ln["name"], "events": len(ln["events"]),
         "sample": [[str(e[0])[:400], e[1], e[2]] for e in ln["events"][:names]]}
        for ln in p["lines"]]}
        for p in planes]


def main(argv) -> int:
    trace_dir = argv[1]
    paths = newest_xplanes(trace_dir)
    if not paths:
        print(json.dumps({"error": f"no xplane.pb under {trace_dir}"}))
        return 1
    planes = [p for path in paths for p in load_xplane(path)]
    if len(argv) > 2:
        with open(argv[2], "w") as f:
            json.dump(structure(planes), f, indent=1)
    print(json.dumps(reduce(planes)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
