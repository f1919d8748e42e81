"""The device is never implicit (utils/device.py): platform resolution,
the one Pallas interpret switch, and the compile-cache placement."""

import os
import subprocess
import sys

import jax
import pytest

from paddlefleetx_tpu.utils import device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def clean_env(monkeypatch):
    for name in ("PFX_PLATFORM", "JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR"):
        monkeypatch.delenv(name, raising=False)
    return monkeypatch


@pytest.mark.parametrize("var", ["PFX_PLATFORM", "JAX_PLATFORMS"])
def test_cpu_pin_is_honoured_under_both_variables(clean_env, var):
    clean_env.setenv(var, "cpu")
    assert device.resolve_platform() == "cpu"


def test_no_pin_requests_the_tpu(clean_env):
    assert device.resolve_platform() == "tpu"


def test_repo_pin_wins_over_jax_platforms(clean_env):
    """The chip smoke's children carry PFX_PLATFORM=tpu so that a machine
    which exports JAX_PLATFORMS=cpu (this sandbox does) still fails for
    want of a chip instead of training on the host."""
    clean_env.setenv("JAX_PLATFORMS", "cpu")
    clean_env.setenv("PFX_PLATFORM", "tpu")
    assert device.resolve_platform() == "tpu"


def test_unknown_platform_pin_is_refused(clean_env):
    clean_env.setenv("PFX_PLATFORM", "gpu")
    with pytest.raises(ValueError, match="valid: cpu, tpu"):
        device.resolve_platform()


def test_a_priority_list_is_not_a_cpu_pin(clean_env):
    """The chip machine exports JAX_PLATFORMS=tpu,cpu: jax would fall
    through to the host if the chip did not come up.  Pinned to tpu only."""
    clean_env.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert device.resolve_platform() == "tpu"


@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False)])
def test_pallas_interpret_follows_the_backend(monkeypatch, backend, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert device.pallas_interpret() is want


def test_pallas_interpret_raises_on_an_unknown_platform(monkeypatch):
    """Neither compiled for nor tested on: a kernel must not quietly
    interpret there and be timed as if it had run."""
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        device.pallas_interpret()


def test_one_interpret_switch():
    """ops/ holds no second copy of the backend test."""
    ops = os.path.join(REPO, "paddlefleetx_tpu", "ops")
    for name in sorted(os.listdir(ops)):
        if name.endswith(".py"):
            with open(os.path.join(ops, name)) as f:
                text = f.read()
            assert "default_backend" not in text, name
            assert "def _interpret" not in text, name


def test_compile_cache_outside_variable_means_nothing_set_in_code(clean_env):
    clean_env.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert device.compile_cache_dir() is None


def test_compile_cache_default_is_the_fixed_checkout_path(clean_env):
    assert device.compile_cache_dir() == os.path.join(REPO, ".jax_cache")


_APPLY = (
    "import jax; from paddlefleetx_tpu.utils.device import apply_platform_env; "
    "print(apply_platform_env(), jax.config.jax_platforms, "
    "jax.config.jax_compilation_cache_dir)"
)


def _apply_in_child(env_extra, drop=()):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PFX_PLATFORM", "JAX_PLATFORMS",
                        "JAX_COMPILATION_CACHE_DIR") + tuple(drop)}
    env.update(env_extra)
    out = subprocess.run(
        [sys.executable, "-c", _APPLY], capture_output=True, text=True,
        cwd=REPO, env=env, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_apply_platform_env_in_a_fresh_process():
    """What an entry point does first: pin the platform (no pin -> tpu),
    place the cache (outside variable -> jax's own reading of it, untouched;
    unset -> <checkout>/.jax_cache).  No backend is initialized, so the
    tpu case runs here without a chip."""
    plat, pinned, cache = _apply_in_child({})
    assert (plat, pinned) == ("tpu", "tpu")
    assert cache == os.path.join(REPO, ".jax_cache")

    plat, pinned, cache = _apply_in_child(
        {"JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": "/tmp/pfx_outside"}
    )
    assert (plat, pinned) == ("cpu", "cpu")
    assert cache == "/tmp/pfx_outside"


def test_entry_point_without_a_chip_fails_at_start(tmp_path):
    """No silent CPU: tools/train.py with no CPU pin on a machine without
    a chip dies with JAX's own backend error before any model is built."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("PFX_PLATFORM", "JAX_PLATFORMS")}
    env["TPU_LOG_DIR"] = "disabled"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "train.py"), "-c",
         os.path.join(REPO, "configs/gpt/pretrain_gpt_345M_single.yaml"),
         "-o", f"Engine.save_load.output_dir={tmp_path}"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=300,
    )
    assert out.returncode != 0
    log = out.stdout + out.stderr
    assert "Unable to initialize backend 'tpu'" in log, log[-2000:]
    assert "init:" not in log and "step 1/" not in log


def test_loader_workers_import_no_backend():
    """data/batch_sampler.WorkerLoader spawns workers that unpickle a
    dataset: nothing they import for that may initialize a jax backend — a
    worker that did would take the chip from the trainer that owns it.
    (Importing jax is harmless; touching a backend is what claims it.)"""
    code = (
        "import importlib, pkgutil, sys\n"
        "import paddlefleetx_tpu.data as d\n"
        "for m in pkgutil.walk_packages(d.__path__, d.__name__ + '.'):\n"
        "    importlib.import_module(m.name)\n"
        "from jax._src import xla_bridge\n"
        "print(sorted(xla_bridge._backends))\n"
    )
    env = {k: v for k, v in os.environ.items()
           if k not in ("PFX_PLATFORM", "JAX_PLATFORMS")}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=REPO, env=env, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "[]"  # no backend was initialized


def test_device_and_version_utils():
    from paddlefleetx_tpu.utils import device, version

    assert device.get_device_type() == "cpu"  # the suite's pin
    assert device.device_count() >= 1
    device.synchronize()  # must not raise
    assert isinstance(device.memory_stats(), dict)
    assert "paddlefleetx-tpu" in version.show()
