"""Unit tests for the shared bench helpers in bench.py.

host_fence is the single timing fence for every benchmark: a one-element
device->host fetch cannot return before the computation it depends on.
"""

import sys
import pytest
import os

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import host_fence, model_flops_per_token  # noqa: E402


def test_host_fence_returns_one_element():
    out = jax.jit(lambda x: x * 2)(jnp.arange(12.0).reshape(3, 4))
    got = host_fence(out)
    assert isinstance(got, np.ndarray)
    assert got.size == 1
    assert got[0] == 0.0


def test_host_fence_pytree():
    # benches fence jit outputs that are dicts/tuples of arrays; the fence
    # fetches from the first leaf regardless of structure
    out = jax.jit(lambda x: {"loss": x.sum(), "ids": x.astype(jnp.int32)})(
        jnp.ones((2, 3))
    )
    got = host_fence(out)
    assert got.size == 1


def test_host_fence_completes_computation():
    # assert on the fence's OWN return value: it must have fetched the
    # computed buffer (a no-op fence cannot produce the right number)
    x = jnp.full((64, 64), 3.0)
    out = jax.jit(lambda a: a @ a)(x)
    np.testing.assert_allclose(host_fence(out)[0], 3.0 * 3.0 * 64)


def test_model_flops_per_token_scales_with_depth():
    one = model_flops_per_token(1024, 24, 50304, 1024)
    two = model_flops_per_token(1024, 48, 50304, 1024)
    # doubling layers should roughly double per-token FLOPs (the embedding
    # head term is shared, so strictly less than 2x)
    assert one < two < 2 * one


def test_bench_child_without_chip_or_pin_fails():
    """No silent CPU: with no chip and no CPU pin the benchmark child
    dies at its first backend touch — no metric row under any name, no
    4-layer model quietly re-run on the host."""
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k not in ("PFX_PLATFORM", "JAX_PLATFORMS")}
    env["TPU_LOG_DIR"] = "disabled"
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py"), "--child"],
        capture_output=True, text=True, cwd=repo, env=env, timeout=300,
    )
    assert out.returncode != 0
    assert "Unable to initialize backend 'tpu'" in out.stderr, out.stderr[-2000:]
    assert '"metric"' not in out.stdout


def test_bench_has_no_fallback_and_parent_stays_off_jax():
    """The probe / wait / fall-back harness is gone, and importing the
    parent half of bench.py pulls in no jax: a parent that touched jax
    would hold the chip its child needs."""
    import subprocess

    import bench

    for name in ("ensure_backend_or_fallback", "wait_for_backend",
                 "_backend_alive", "CPU_FALLBACK_SHAPE",
                 "RING_CPU_FALLBACK_SHAPE"):
        assert not hasattr(bench, name), name
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, bench; print('jax' in sys.modules)"],
        capture_output=True, text=True, cwd=repo, timeout=60,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "False"


def test_ring_row_contract():
    """The long-context ring case's row contract: null vs_baseline (no
    published CP reference) and honest zero rows that parse."""
    import bench

    row = bench._honest_ring_row("some reason")
    assert row["metric"] == bench.RING_METRIC
    assert row["value"] == 0.0
    assert row["vs_baseline"] is None
    assert "some reason" in row["unit"]


@pytest.mark.slow  # ~60s: full seq-4096 ring fwd+bwd on a forced 4-device
# CPU mesh in a fresh subprocess; the row-shape contract stays tier-1 via
# test_ring_row_contract
def test_ring_bench_cpu_smoke_emits_platform_labeled_row():
    import json as _json
    import subprocess

    env = dict(os.environ)
    env["PFX_PLATFORM"] = "cpu"
    env.pop("JAX_PLATFORMS", None)
    env["XLA_FLAGS"] = ""  # the child forces its own 4-device host
    env.update({"BENCH_RING_STEPS": "1", "BENCH_RING_HEADS": "2",
                "BENCH_RING_DIM": "16"})
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py"), "--child-ring"],
        capture_output=True, text=True, cwd=repo, env=env, timeout=540,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    row = _json.loads(out.stdout.strip().splitlines()[-1])
    import bench

    assert row["metric"] == bench.RING_METRIC
    assert row["platform"] == "cpu"
    assert row["seq"] >= 4096
    assert row["ring"] >= 2
    assert row["value"] > 0.0
    assert "cpu" in row["unit"]  # labeled, never reads as chip evidence
