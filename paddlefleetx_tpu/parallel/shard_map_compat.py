"""``shard_map`` adapter: one entry point for the repo's manual regions.

The parallel schedules (``parallel/pipeline.py`` 1F1B/GPipe,
``parallel/ring_attention.py``) and the Pallas kernels under a mesh
(``parallel/sharding.shard_kernel``) are *partially manual*: manual over
their own axes (``stages`` / ``sep`` / the batch and heads axes) with
every other mesh axis left to GSPMD — ``jax.shard_map(axis_names=,
check_vma=False)``.  Specs may only name the manual axes.

The adapter adds one thing jax does not give: it records the body's manual
axis set in a thread-local while the body traces, so code deep inside a
mapped region (sharding constraints, a nested kernel map) can ask
:func:`current_manual_axes` instead of guessing from jax internals.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, FrozenSet, Iterable

import jax

__all__ = [
    "shard_map",
    "current_manual_axes",
    "in_manual_region",
]

_TLS = threading.local()


def current_manual_axes() -> FrozenSet[str]:
    """Mesh axes that are Manual in the shard_map bodies currently being
    traced on this thread (empty outside any mapped region); nested maps
    accumulate."""
    return getattr(_TLS, "axes", frozenset())


def in_manual_region() -> bool:
    return bool(current_manual_axes())


def _with_manual_axes(body: Callable, axes: FrozenSet[str]) -> Callable:
    """Wrap ``body`` so the thread-local manual set grows by ``axes`` while
    it traces (restored on exit)."""

    def wrapped(*args):
        prev = getattr(_TLS, "axes", frozenset())
        _TLS.axes = prev | frozenset(axes)
        try:
            return body(*args)
        finally:
            _TLS.axes = prev

    return wrapped


def shard_map(
    body: Callable,
    mesh: Any,
    in_specs: Any,
    out_specs: Any,
    manual_axes: Iterable[str],
) -> Callable:
    """Map ``body`` over ``mesh`` manually along ``manual_axes``; every
    other axis stays GSPMD-auto inside.  ``in_specs``/``out_specs`` name
    only ``manual_axes``.  Returns the mapped callable."""
    manual = frozenset(manual_axes)
    missing = manual - set(mesh.axis_names)
    if missing:
        raise ValueError(
            f"manual axes {sorted(missing)} not in mesh axes {mesh.axis_names}"
        )
    return jax.shard_map(
        _with_manual_axes(body, manual),
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        axis_names=set(manual),
        check_vma=False,
    )
