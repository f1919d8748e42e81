"""Device selection / synchronization (reference utils/device.py:19-62,
which maps gpu/xpu/rocm/npu/mlu/intel_gpu/cpu and exposes synchronize()).

The device is never implicit.  Every entry point calls
:func:`apply_platform_env` before the first backend touch; it resolves the
platform ONCE (:func:`resolve_platform`): an explicit CPU pin gives the
CPU, anything else pins ``tpu`` — so a machine without a chip fails at
start with JAX's own "Unable to initialize backend 'tpu'" instead of
quietly training on the host.  The same call places the persistent
compile cache (:func:`compile_cache_dir`).
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional

import jax

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def resolve_platform() -> str:
    """'cpu' | 'tpu' — the platform this process is pinned to.

    ``PFX_PLATFORM`` (the repo's own pin: tests, CPU rehearsals, the chip
    smoke's children) decides when set, and takes only those two names: the
    kernels know how to compile for one and interpret on the other.
    Otherwise ``JAX_PLATFORMS=cpu`` — exactly that — is the CPU pin, and
    anything else means ``tpu``: a priority list such as ``tpu,cpu`` (the
    chip machine exports it) would let jax fall through to the host when
    the chip does not come up, which is the one thing this must not do."""
    pin = os.environ.get("PFX_PLATFORM", "").strip().lower()
    if pin:
        if pin not in ("cpu", "tpu"):
            raise ValueError(f"PFX_PLATFORM={pin!r}; valid: cpu, tpu")
        return pin
    jax_pin = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    return "cpu" if jax_pin == "cpu" else "tpu"


def compile_cache_dir() -> Optional[str]:
    """Where this checkout keeps its persistent compile cache when nobody
    placed it from outside: ``<checkout>/.jax_cache`` (fixed and
    git-ignored — the path is part of the cache key, so it never derives
    from a pid, a time or a temp dir).  ``None`` when
    ``JAX_COMPILATION_CACHE_DIR`` is set: jax reads that variable itself
    and code sets nothing on top of it."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return os.path.join(_REPO_ROOT, ".jax_cache")


def apply_platform_env() -> str:
    """Pin the resolved platform and place the compile cache, before any
    backend init.  Call at the top of every CLI entry point; returns the
    platform."""
    plat = resolve_platform()
    jax.config.update("jax_platforms", plat)
    cache = compile_cache_dir()
    if cache is not None:
        jax.config.update("jax_compilation_cache_dir", cache)
    return plat


def pallas_interpret() -> bool:
    """THE interpret-mode switch of every Pallas kernel in ``ops/``:
    interpreted on the CPU (tests, rehearsals), compiled by Mosaic on a
    TPU, and an error anywhere else — a kernel that silently interprets
    on an accelerator it was not written for is a benchmark of the
    interpreter."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels here target 'tpu' (compiled) or 'cpu' "
        f"(interpreted); jax.default_backend() is {backend!r}"
    )


def device_identity() -> Dict[str, object]:
    """``platform`` / ``device_kind`` / ``device_count`` as JAX reports
    them — the three fields both entry points log at start and
    ``serve.py``'s ``/healthz`` carries."""
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
    }


def get_device_type() -> str:
    """'tpu' | 'cpu' (``jax.default_backend()``)."""
    return jax.default_backend()


def get_devices() -> List[jax.Device]:
    return list(jax.devices())


def device_count() -> int:
    return jax.device_count()


def local_device_count() -> int:
    return jax.local_device_count()


def synchronize() -> None:
    """Block until all in-flight device work completes (reference
    paddle.device.synchronize equivalent)."""
    for d in jax.local_devices():
        jax.device_put(0.0, d).block_until_ready()


def memory_stats() -> dict:
    """Per-device memory stats where the backend reports them."""
    out = {}
    for d in jax.local_devices():
        try:
            out[str(d)] = d.memory_stats()
        except Exception:
            out[str(d)] = {}
    return out
