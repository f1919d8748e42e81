"""Test harness: run everything on a virtual 8-device CPU mesh.

This is the mock-multinode capability the reference lacks (SURVEY.md §4):
every parallel layout (dp/tp/pp/sp/ep) runs as a multi-device unit test on
one host, numerics asserted against single-device references.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

# The suite is CPU-only by design.  Export the pin (not just jax.config) so
# entry-point modules imported in-process and every subprocess a test
# spawns resolve the same platform through utils/device.resolve_platform.
os.environ["PFX_PLATFORM"] = "cpu"
# Every hit of the persistent compile cache makes XLA:CPU log two ~3 KB
# error lines (cpu_aot_loader.cc: "+prefer-no-scatter is not supported on
# the host machine"), also for entries this machine compiled.  A drill that
# keeps a server's output in a pipe it reads only at the end then blocks the
# server at 64 KB, before /healthz: on a warm cache a replica prints 80 KB
# while it boots.  Silence the native logs for this process and its children.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)

# Persistent compilation cache: XLA-CPU compiles dominate suite wall-clock
# (a resnet18 engine test spends >70s compiling on one core); cached repeat
# runs skip them.  Placed the way every entry point places it
# (utils/device.compile_cache_dir): where JAX_COMPILATION_CACHE_DIR says if
# it came set from outside — jax read the variable at import and nothing is
# set in code — else tests/.jax_cache, set in-process (a pytest plugin has
# imported jax by now, so the environment alone is too late) and exported
# so subprocess tests (golden-doc walkthroughs, config launches,
# distributed workers) share it.  The 0.1s persist threshold also banks the
# long tail of 0.1-1s compiles scattered across ~600 small tests.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _cache_dir = os.path.join(os.path.dirname(__file__), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", _cache_dir)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache_dir
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.1")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")


@pytest.fixture
def devices8():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs[:8]
