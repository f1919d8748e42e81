"""Training-run observatory: per-layer-group model-internals statistics,
non-finite provenance, memory watermarks, and retrace attribution.

The engine's step records (loss / lr / global grad_norm / MFU) say *that*
a run is sick, never *where*.  Production-scale training stacks
(MegaScale, Jiang et al. 2024) treat per-layer statistics and
memory/straggler diagnostics as the thing that makes a large run
debuggable; this module is that layer for ``core/engine.py``:

  - **Group mapping** — :func:`build_group_spec` assigns EVERY parameter
    leaf of any model in the zoo to exactly one *layer group* through a
    deterministic path rule: leaves whose key path crosses a ``layers``
    stack (``models/common.stack_spec_tree`` — GPT, ERNIE, T5, ViT,
    DebertaV2 all use it) split per layer into ``block_<i>`` (prefixed
    ``encoder/``/``decoder/`` when nested); embedding-rooted leaves map
    to ``embed``; final-LN / LM-head leaves to ``head``; anything else
    keeps its (lowercased) root key.  The mapping is *total* (no leaf
    unassigned) and *stable* (pure function of the tree structure).
  - **In-graph statistics** — :func:`group_sqsum` / :func:`group_stats`
    compute per-group grad norm, param norm, update norm, update/param
    ratio and grads-fraction-non-finite as ``[G]`` vectors inside the
    jitted train step.  Sums accumulate in fp32 via the SAME per-leaf
    rule as ``optims/optimizer.global_norm_f32`` (``sqsum_f32``), so the
    engine's global grad norm is exactly ``sqrt(sum(group_sqsum))`` and
    grouping adds no second pass over the gradients.
  - **Non-finite provenance** — :func:`nonfinite_group_names` turns the
    per-group finiteness vector (free: ``isfinite`` of the group sqsums
    the norm already needs) into the ordered list of offending groups,
    carried by step records, anomaly ``rollback`` events and the flight
    recorder, so a postmortem names a culprit layer instead of
    "found_inf fired".
  - **Memory watermarks** — :func:`memory_watermarks` reads
    ``device.memory_stats()`` where the backend provides it (TPU), with
    a host-RSS fallback (``/proc/self/status``), exported as ``pfx_mem_*``
    gauges by :func:`export_memory_gauges`; the engine tracks the peak
    per fit and warns loudly when headroom drops under
    ``PFX_MEM_WARN_HEADROOM`` (default 0.05 = 5% free).
  - **Retrace attribution** — :class:`CompileWatcher` turns jax's
    compile logging into a structured compile-event log (fn name, arg
    avals diffed against the previous compile of that fn, elapsed
    seconds) feeding ``pfx_compile_events_total`` /
    ``pfx_compile_seconds_total`` and the flight ring — "why did step
    812 take 40 s" is answerable from the flight dump offline
    (``tools/report.py``).

Cadence contract (docs/observability.md): the engine computes group
stats behind ``lax.cond`` on ``Engine.logging.model_stats_every``
(default = logging cadence, ``0`` disables) and the results ride the
existing step-record device fetch — no new per-step host syncs, and at
``0`` the train step graph is byte-identical to the stats-less one
(asserted by tests/test_model_stats.py).
"""

from __future__ import annotations

import logging
import os
import re
import threading
from collections import deque
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from paddlefleetx_tpu.utils.log import logger

# ---------------------------------------------------------------------------
# group mapping
# ---------------------------------------------------------------------------

# path components marking a stacked per-layer subtree (the leading axis is
# the layer index — models/common.stack_spec_tree's contract)
STACK_KEYS = ("layers",)
# non-stacked root classification (lowercased containment / exact match)
_HEAD_ROOTS = ("final_ln", "final_layernorm", "final_layer_norm", "lm_head",
               "head", "pooler")


class GroupSpec(NamedTuple):
    """Deterministic leaf -> layer-group assignment for one param tree.

    ``names`` is the canonical group order (``embed`` first, stacked
    blocks in layer order, scalar groups, ``head`` last) — the order
    "first offending group" provenance reports in.  ``assignments`` has
    one entry per flattened leaf: ``(group_index, None)`` for a scalar
    group, ``(first_block_index, num_layers)`` for a stacked leaf whose
    leading axis spreads over ``num_layers`` consecutive block groups.
    ``sizes`` counts float elements per group (the non-finite-fraction
    denominator); non-inexact leaves are assigned but carry zero size
    and are skipped by every statistic."""

    names: Tuple[str, ...]
    assignments: Tuple[Tuple[int, Optional[int]], ...]
    sizes: Any  # np.ndarray [G] float
    treedef: Any

    @property
    def num_groups(self) -> int:
        return len(self.names)


def _key_name(k: Any) -> str:
    for attr in ("key", "name", "idx"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def _is_inexact(leaf: Any) -> bool:
    dt = getattr(leaf, "dtype", None)
    return dt is not None and np.issubdtype(np.dtype(dt), np.inexact)


def _scalar_group(comps: Sequence[str]) -> str:
    root = comps[0].lower()
    if "embed" in root:
        return "embed"
    if root in _HEAD_ROOTS or "head" in root or root.startswith("final"):
        return "head"
    return root


def build_group_spec(params: Any) -> GroupSpec:
    """Map every leaf of ``params`` (arrays or ShapeDtypeStructs) to a
    layer group.  Total over any pytree — a leaf that matches no rule
    keeps its root key as its group — and a pure function of the tree
    structure, so two calls on the same model agree exactly."""
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    # pass 1: discover groups.  stacked[] = (base, L); scalar[] = name
    leaf_plan: List[Tuple[str, Any]] = []  # ("stacked", (base, L, layer_sz)) | ("scalar", name)
    stack_layers: Dict[str, int] = {}
    for kp, leaf in flat:
        comps = [_key_name(k) for k in kp] or ["params"]
        stack_at = next(
            (i for i, c in enumerate(comps) if c.lower() in STACK_KEYS), None
        )
        shape = tuple(getattr(leaf, "shape", ()) or ())
        if stack_at is not None and len(shape) >= 1 and shape[0] >= 1:
            base = "/".join(c.lower() for c in comps[:stack_at])
            length = int(shape[0])
            prev = stack_layers.setdefault(base, length)
            if prev != length:
                # inconsistent stack under one prefix: refuse to guess a
                # per-layer split, fall back to one scalar group — the
                # mapping stays total either way
                leaf_plan.append(("scalar", _scalar_group(comps)))
                continue
            leaf_plan.append(("stacked", base))
        else:
            leaf_plan.append(("scalar", _scalar_group(comps)))

    # canonical order: embed, blocks (bases sorted, layers ascending),
    # other scalar groups sorted, head last
    scalar_names = {name for kind, name in leaf_plan if kind == "scalar"}
    ordered: List[str] = []
    if "embed" in scalar_names:
        ordered.append("embed")
    block_base_index: Dict[str, int] = {}
    for base in sorted(stack_layers):
        block_base_index[base] = len(ordered)
        prefix = f"{base}/" if base else ""
        ordered.extend(
            f"{prefix}block_{i}" for i in range(stack_layers[base])
        )
    for name in sorted(scalar_names - {"embed", "head"}):
        ordered.append(name)
    if "head" in scalar_names:
        ordered.append("head")
    index = {n: i for i, n in enumerate(ordered)}

    sizes = np.zeros((len(ordered),), np.float64)
    assignments: List[Tuple[int, Optional[int]]] = []
    for (kp, leaf), (kind, ref) in zip(flat, leaf_plan):
        n_el = float(np.prod(getattr(leaf, "shape", ()) or (), dtype=np.float64))
        if kind == "stacked":
            first = block_base_index[ref]
            length = stack_layers[ref]
            assignments.append((first, length))
            if _is_inexact(leaf):
                sizes[first:first + length] += n_el / length
        else:
            g = index[ref]
            assignments.append((g, None))
            if _is_inexact(leaf):
                sizes[g] += n_el
    return GroupSpec(tuple(ordered), tuple(assignments), sizes, treedef)


def group_labels(spec: GroupSpec) -> List[str]:
    """The group names in canonical (provenance) order."""
    return list(spec.names)


# ---------------------------------------------------------------------------
# in-graph statistics
# ---------------------------------------------------------------------------


def _flat_leaves(spec: GroupSpec, tree: Any) -> List[Any]:
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if treedef != spec.treedef:
        raise ValueError(
            f"tree structure does not match the GroupSpec ({treedef} vs "
            f"{spec.treedef}) — build the spec from the same param tree"
        )
    return leaves


def _accumulate(spec: GroupSpec, tree: Any, leaf_fn) -> Any:
    """Sum ``leaf_fn(leaf) -> per-layer [L] or scalar`` into a [G] f32
    vector following the spec's assignments; non-float leaves skip."""
    import jax.numpy as jnp

    out = jnp.zeros((spec.num_groups,), jnp.float32)
    for leaf, (g0, length) in zip(_flat_leaves(spec, tree), spec.assignments):
        if leaf is None or not _is_inexact(leaf):
            continue
        if length is not None:
            axes = tuple(range(1, leaf.ndim))
            out = out.at[g0:g0 + length].add(leaf_fn(leaf, axes))
        else:
            out = out.at[g0].add(leaf_fn(leaf, None))
    return out


def group_sqsum(spec: GroupSpec, tree: Any) -> Any:
    """Per-group sum of squares, fp32-accumulated (the one rule behind
    ``optims/optimizer.global_norm_f32`` — ``sqrt(sum(group_sqsum))`` IS
    the global norm, so the engine computes the grouped and global grad
    norms in a single pass)."""
    import jax.numpy as jnp

    from paddlefleetx_tpu.optims.optimizer import sqsum_f32

    def leaf_fn(x, axes):
        if axes is None:
            return sqsum_f32(x)
        return jnp.sum(jnp.square(x.astype(jnp.float32)), axis=axes)

    return _accumulate(spec, tree, leaf_fn)


def group_nonfinite_count(spec: GroupSpec, tree: Any) -> Any:
    """Per-group count of non-finite elements, [G] f32."""
    import jax.numpy as jnp

    def leaf_fn(x, axes):
        bad = (~jnp.isfinite(x)).astype(jnp.float32)
        return jnp.sum(bad) if axes is None else jnp.sum(bad, axis=axes)

    return _accumulate(spec, tree, leaf_fn)


def group_stats(
    spec: GroupSpec,
    *,
    grad_sqsum: Any,
    params: Any,
    updates: Any,
    grads: Any,
) -> Dict[str, Any]:
    """The full per-group statistic set, each a [G] f32 vector:
    ``grad_norm`` / ``param_norm`` / ``update_norm`` / ``update_ratio``
    (update/param — the LR-health signal that drifts for hundreds of
    steps before a spike) / ``nonfinite_frac`` (fraction of grad
    ELEMENTS non-finite).  Called inside the train step's stats branch;
    ``grad_sqsum`` is passed in because the caller already computed it
    for the global norm."""
    import jax.numpy as jnp

    eps = jnp.float32(1e-12)
    param_norm = jnp.sqrt(group_sqsum(spec, params))
    update_norm = jnp.sqrt(group_sqsum(spec, updates))
    sizes = jnp.asarray(np.maximum(spec.sizes, 1.0), jnp.float32)
    return {
        "grad_norm": jnp.sqrt(grad_sqsum),
        "param_norm": param_norm,
        "update_norm": update_norm,
        "update_ratio": update_norm / (param_norm + eps),
        "nonfinite_frac": group_nonfinite_count(spec, grads) / sizes,
    }


def nonfinite_group_names(
    spec: GroupSpec, flags: Any, limit: Optional[int] = None
) -> List[str]:
    """Offending group names from a per-group non-finite indicator vector
    (host side, canonical order — the FIRST entry is the first offending
    group a postmortem should name)."""
    flat = np.asarray(flags).reshape(-1)
    names = [n for n, f in zip(spec.names, flat) if float(f) > 0]
    return names if limit is None else names[:limit]


# ---------------------------------------------------------------------------
# memory watermarks
# ---------------------------------------------------------------------------


def _host_rss_bytes() -> Optional[int]:
    """Resident-set size of this process: /proc (linux, current RSS)
    with a resource-module fallback (``ru_maxrss`` — a lifetime PEAK,
    in KiB on Linux/BSD but already bytes on macOS; still an honest
    watermark, just never decreasing); None when neither works."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        pass
    try:
        import resource
        import sys as _sys

        peak = int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)
        return peak if _sys.platform == "darwin" else peak * 1024
    except Exception:  # noqa: BLE001 — no RSS source is a valid state
        return None


def memory_watermarks() -> Dict[str, Any]:
    """One memory snapshot: per-device ``bytes_in_use`` / ``peak_bytes``
    / ``bytes_limit`` where the backend exposes ``memory_stats()`` (TPU
    does; CPU returns None and contributes nothing), plus host RSS.
    ``headroom_frac`` is the WORST device's free fraction (None when no
    device reports a limit).  Pure host-side accounting — never a device
    sync."""
    devices: List[Dict[str, Any]] = []
    headroom: Optional[float] = None
    try:
        import jax

        for d in jax.local_devices():
            try:
                ms = d.memory_stats()
            except Exception:  # noqa: BLE001 — backend-optional API
                ms = None
            if not ms:
                continue
            in_use = ms.get("bytes_in_use")
            limit = ms.get("bytes_limit")
            row = {
                "id": int(getattr(d, "id", len(devices))),
                "bytes_in_use": in_use,
                "peak_bytes": ms.get("peak_bytes_in_use"),
                "bytes_limit": limit,
            }
            devices.append(row)
            if in_use is not None and limit:
                free = max(0.0, 1.0 - float(in_use) / float(limit))
                headroom = free if headroom is None else min(headroom, free)
    except Exception:  # noqa: BLE001 — no backend is a valid state here
        pass
    return {
        "devices": devices,
        "host_rss_bytes": _host_rss_bytes(),
        "device_peak_bytes": max(
            (d["peak_bytes"] for d in devices if d.get("peak_bytes")), default=None
        ),
        "device_in_use_bytes": max(
            (d["bytes_in_use"] for d in devices if d.get("bytes_in_use")),
            default=None,
        ),
        "headroom_frac": headroom,
    }


def export_memory_gauges(registry, wm: Dict[str, Any]) -> None:
    """Mirror a watermark snapshot onto ``pfx_mem_*`` gauges."""
    if wm.get("host_rss_bytes") is not None:
        registry.gauge("pfx_mem_host_rss_bytes").set(wm["host_rss_bytes"])
    for d in wm.get("devices", ()):
        lab = {"device": str(d["id"])}
        if d.get("bytes_in_use") is not None:
            registry.gauge("pfx_mem_device_bytes_in_use", **lab).set(
                d["bytes_in_use"]
            )
        if d.get("peak_bytes") is not None:
            registry.gauge("pfx_mem_device_peak_bytes", **lab).set(
                d["peak_bytes"]
            )
        if d.get("bytes_limit") is not None:
            registry.gauge("pfx_mem_device_limit_bytes", **lab).set(
                d["bytes_limit"]
            )
    if wm.get("headroom_frac") is not None:
        registry.gauge("pfx_mem_headroom_frac").set(
            round(wm["headroom_frac"], 4)
        )


def warn_headroom(wm: Dict[str, Any], threshold: Optional[float] = None) -> bool:
    """Loud warning when the worst device's free-HBM fraction drops
    under the threshold (``PFX_MEM_WARN_HEADROOM``, default 0.05).
    Returns True when it warned — callers rate-limit (the engine warns
    once per fit)."""
    from paddlefleetx_tpu.utils.telemetry import _env_float

    threshold = (
        threshold if threshold is not None
        else _env_float("PFX_MEM_WARN_HEADROOM", 0.05)
    )
    head = wm.get("headroom_frac")
    if head is None or head >= threshold:
        return False
    # worst by free FRACTION — the same quantity headroom_frac (and the
    # breach decision) is computed from, so the named device is the one
    # that tripped the warning even on heterogeneous fleets
    worst = min(
        (d for d in wm.get("devices", ()) if d.get("bytes_limit")),
        key=lambda d: 1.0 - (d["bytes_in_use"] or 0) / d["bytes_limit"],
        default=None,
    )
    detail = (
        f" (device {worst['id']}: {worst['bytes_in_use']}/"
        f"{worst['bytes_limit']} bytes in use)" if worst else ""
    )
    logger.warning(
        f"HBM headroom low: {head:.1%} free < {threshold:.1%} threshold"
        f"{detail} — the next allocation spike (eval, checkpoint "
        "snapshot, retrace) may OOM; shrink the batch/model or raise "
        "PFX_MEM_WARN_HEADROOM to silence"
    )
    return True


# ---------------------------------------------------------------------------
# retrace attribution: the compile-event log
# ---------------------------------------------------------------------------

# jax 0.9: "Compiling jit(train_step) with global shapes and types
# (ShapedArray(...), ...). Argument mapping: (...)." — the avals print as a
# tuple and the module name carries its jit(...) wrapper
_COMPILING_RE = re.compile(
    r"Compiling (?:jit\()?([^\s)]+)\)? with global shapes and types "
    r"(\(.*?\))\. Argument mapping",
    re.DOTALL,
)
_CACHE_HIT_RE = re.compile(r"Persistent compilation cache hit")
# the per-compile chatter jax_log_compiles turns on (suppressed from run
# logs once the watcher owns those loggers); anything NOT matching —
# e.g. jax._src.compiler's "Unable to generate cache key" errors — is
# forwarded to the repo logger so real problems stay visible
_COMPILE_CHATTER_RE = re.compile(
    r"Compiling |Finished tracing|Finished jaxpr|Finished XLA compilation|"
    r"compilation cache hit|persistent compilation cache|"
    r"compile_requests|get_compile_options|cache_key"
)


def _split_avals(avals: str) -> List[str]:
    """Split jax's ``(ShapedArray(f32[4]), ...)`` listing into per-arg
    strings (best-effort: balanced-paren split, robust to nested
    parentheses inside an aval)."""
    body = avals.strip()
    if body.startswith("("):
        body = body[1:]
    if body.endswith(")"):
        body = body[:-1]
    out, depth, cur = [], 0, []
    for ch in body:
        if ch == "," and depth == 0:
            if "".join(cur).strip():
                out.append("".join(cur).strip())
            cur = []
            continue
        depth += ch in "([{"
        depth -= ch in ")]}"
        cur.append(ch)
    if "".join(cur).strip():
        out.append("".join(cur).strip())
    return out


def diff_avals(prev: Optional[List[str]], cur: List[str], cap: int = 3) -> str:
    """Human-readable diff of two compile keys' aval lists: what changed
    since the previous compile of this fn (the retrace attribution)."""
    if prev is None:
        return "first compile"
    if len(prev) != len(cur):
        return f"arg count {len(prev)} -> {len(cur)}"
    changed = [
        f"arg{i}: {p} -> {c}" for i, (p, c) in enumerate(zip(prev, cur))
        if p != c
    ]
    if not changed:
        return "same avals (sharding/donation/compiler-option change)"
    extra = f" (+{len(changed) - cap} more)" if len(changed) > cap else ""
    return "; ".join(changed[:cap])[:400] + extra


class CompileWatcher:
    """Structured compile-event log fed from jax's own compile logging.

    ``install()`` flips ``jax_log_compiles`` on and attaches a logging
    handler to jax's pxla logger, whose "Compiling <fn> with global
    shapes and types [...]" line carries the fn name + the full abstract
    arg list; a ``jax.monitoring`` duration listener then stamps the
    backend-compile elapsed seconds onto the pending event (the two fire
    on the same thread, in order).  Each finished event lands in:

      - the bounded ``events`` ring (``PFX_COMPILE_LOG_CAP``, default
        256) — served offline by ``tools/report.py``;
      - the flight recorder ring (``event: "compile"``) so a crash dump
        explains late retraces;
      - ``pfx_compile_events_total`` / ``pfx_compile_seconds_total``.

    The jax loggers it taps get ``propagate = False`` while installed so
    per-compile chatter does not spam run logs; records that are NOT
    compile chatter (a broken persistent cache logs errors through the
    same ``jax._src.compiler`` logger) are re-emitted through the repo
    logger at their original level, so owning the loggers never hides a
    real problem (uninstall restores propagation).  Gate:
    ``PFX_COMPILE_LOG=0`` disables installation entirely."""

    _TAPPED_LOGGERS = (
        "jax._src.interpreters.pxla",
        "jax._src.dispatch",
        "jax._src.compiler",  # persistent-cache-hit lines (also silenced)
    )

    def __init__(self, capacity: Optional[int] = None) -> None:
        from paddlefleetx_tpu.utils.telemetry import _env_int

        cap = capacity if capacity is not None else _env_int(
            "PFX_COMPILE_LOG_CAP", 256
        )
        self.events: deque = deque(maxlen=cap)
        self._lock = threading.Lock()
        self._fn_counts: Dict[str, int] = {}
        self._prev_avals: Dict[str, List[str]] = {}
        self._pending = threading.local()
        self._installed = False
        self._handler: Optional[logging.Handler] = None
        self._was_propagating: Dict[str, bool] = {}

    # -- ingestion ------------------------------------------------------
    def observe_compile_start(self, fn: str, avals_str: str) -> None:
        self._pending.value = (fn, _split_avals(avals_str), False)

    def observe_cache_hit(self) -> None:
        pending = getattr(self._pending, "value", None)
        if pending is not None:
            self._pending.value = (pending[0], pending[1], True)

    def observe_compile_done(self, elapsed_s: float) -> None:
        pending = getattr(self._pending, "value", None)
        self._pending.value = None
        if pending is None:
            return
        fn, avals, cache_hit = pending
        with self._lock:
            prev = self._prev_avals.get(fn)
            diff = diff_avals(prev, avals)
            self._prev_avals[fn] = avals
            n = self._fn_counts[fn] = self._fn_counts.get(fn, 0) + 1
            event = {
                "event": "compile",
                "fn": fn,
                "elapsed_s": round(float(elapsed_s), 4),
                "n_args": len(avals),
                "diff": diff,
                "nth_for_fn": n,
            }
            if cache_hit:
                # the retrace happened (a new compile key) but the
                # executable came from the persistent cache — the step
                # paid trace time, not XLA time
                event["cache_hit"] = True
            self.events.append(event)
        try:
            from paddlefleetx_tpu.utils.telemetry import (
                count_compile_event,
                get_flight_recorder,
                get_registry,
            )

            count_compile_event()  # a slow iteration reads it (StallWatch)
            get_flight_recorder().record(dict(event))
            reg = get_registry()
            reg.counter("pfx_compile_events_total").inc()
            reg.counter("pfx_compile_seconds_total").inc(float(elapsed_s))
        except Exception as e:  # noqa: BLE001 — observability must not
            # take down a compile (e.g. a test-scoped registry reset race)
            logger.warning(f"compile-event export failed: {e}")

    def snapshot(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self.events)

    # -- wiring ---------------------------------------------------------
    def install(self) -> "CompileWatcher":
        if self._installed:
            return self
        import jax
        from jax._src import monitoring

        watcher = self

        class _Handler(logging.Handler):
            def emit(self, record: logging.LogRecord) -> None:
                try:
                    msg = record.getMessage()
                    m = _COMPILING_RE.search(msg)
                    if m:
                        watcher.observe_compile_start(m.group(1), m.group(2))
                        return
                    if _CACHE_HIT_RE.search(msg):
                        watcher.observe_cache_hit()
                        return
                    if (
                        record.levelno >= logging.WARNING
                        and not _COMPILE_CHATTER_RE.search(msg)
                    ):
                        # not per-compile chatter: this logger's
                        # propagation is off, so re-emit through the repo
                        # logger — a broken persistent cache (ERROR via
                        # jax._src.compiler) must stay visible
                        logger.log(
                            record.levelno, f"[{record.name}] {msg}"
                        )
                except Exception:  # noqa: BLE001 — never raise from logging
                    pass

        self._handler = _Handler(level=logging.DEBUG)
        for name in self._TAPPED_LOGGERS:
            lg = logging.getLogger(name)
            self._was_propagating[name] = lg.propagate
            lg.addHandler(self._handler)
            # jax's per-compile lines log at WARNING once jax_log_compiles
            # is on; without this they would spam every run's stderr
            lg.propagate = False

        def _on_duration(name: str, secs: float, **_kw) -> None:
            if name == "/jax/core/compile/backend_compile_duration":
                watcher.observe_compile_done(secs)

        monitoring.register_event_duration_secs_listener(_on_duration)
        jax.config.update("jax_log_compiles", True)
        self._installed = True
        return self

    def uninstall(self) -> None:
        """Detach the logging taps (test isolation; the monitoring
        listener stays registered — jax offers no unregister — but goes
        quiet once ``_installed`` is cleared via the pending gate)."""
        if not self._installed:
            return
        import jax

        for name in self._TAPPED_LOGGERS:
            lg = logging.getLogger(name)
            if self._handler is not None:
                lg.removeHandler(self._handler)
            lg.propagate = self._was_propagating.get(name, True)
        jax.config.update("jax_log_compiles", False)
        self._installed = False


_watcher: Optional[CompileWatcher] = None


def get_compile_watcher() -> CompileWatcher:
    """The process-wide compile watcher (not yet installed)."""
    global _watcher
    if _watcher is None:
        _watcher = CompileWatcher()
    return _watcher


def install_compile_watcher() -> Optional[CompileWatcher]:
    """Install the process-wide watcher unless ``PFX_COMPILE_LOG=0``.
    Idempotent — the engine and the serve CLI both call this."""
    raw = (os.environ.get("PFX_COMPILE_LOG") or "").strip()
    if raw and raw not in ("1", "true", "on"):
        if raw in ("0", "false", "off"):
            return None
        raise ValueError(
            f"PFX_COMPILE_LOG={raw!r}: use 0/1 (loud-parse: unset it or "
            "pass a valid value)"
        )
    return get_compile_watcher().install()
