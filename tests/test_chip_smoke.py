"""chip_smoke.py's contract, as far as a machine without a chip can hold it:
no chip -> a non-zero exit in seconds, ``"ok": false`` on the last line and
no model built; a parent that never touches jax; and (slow) the whole CPU
dress rehearsal."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(argv, cwd=REPO, timeout=300, env=None):
    return subprocess.run(
        [sys.executable, *argv], capture_output=True, text=True, cwd=cwd,
        timeout=timeout, env=env,
    )


def _last_json(stdout):
    return json.loads(stdout.strip().splitlines()[-1])


def test_no_chip_fails_in_seconds_and_builds_no_model():
    """Run as the driver runs it — no arguments — with the sandbox's own
    JAX_PLATFORMS=cpu in the environment: the children are pinned to tpu
    regardless, so the run dies for want of a chip instead of passing on
    the host."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PFX_PLATFORM="cpu")
    t0 = time.time()
    out = _run([SMOKE], env=env)
    took = time.time() - t0
    assert out.returncode != 0
    assert took < 60, took
    last = _last_json(out.stdout)
    assert last["ok"] is False and last["device"] is None
    assert "no accelerator" in out.stdout
    # nothing was built: no parameters initialised, no step, no server
    for built in ("init:", "step 1/", "healthz", "Traceback"):
        assert built not in out.stdout, built


def test_alone_in_a_directory_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    out = _run([str(tmp_path / "chip_smoke.py")], cwd=str(tmp_path))
    assert out.returncode != 0
    assert _last_json(out.stdout)["ok"] is False


def test_parent_is_stdlib_only():
    """One process per chip: the parent that starts the phases imports no
    jax (a parent that had touched it would hold the chip)."""
    out = _run(["-c", "import sys, chip_smoke; "
                "print('jax' in sys.modules, 'numpy' in sys.modules)"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["False", "False"]


def test_request_plan_and_metric_parsing():
    sys.path.insert(0, REPO)
    import chip_smoke

    shape = chip_smoke.shape_of(rehearse=False)
    plan = chip_smoke.request_plan(7, shape)
    assert plan == chip_smoke.request_plan(7, shape)  # from the seed
    assert plan != chip_smoke.request_plan(8, shape)
    # mixed prompt lengths across both warmed buckets, never past max_dec_len
    assert len({len(r["prompt_ids"]) > 64 for r in plan}) == 2
    assert all(r["max_tokens"] <= 32 for r in plan)
    text = (
        '# HELP pfx_token_ledger_total x\n'
        'pfx_token_ledger_total{disposition="admitted"} 36.0\n'
        'pfx_token_ledger_total{disposition="delivered"} 36.0\n'
        'pfx_token_ledger_in_flight 0.0\n'
        'pfx_compile_events_total 30\n'
    )
    led = chip_smoke.metric_values(text, "pfx_token_ledger_total")
    assert led == {'{disposition="admitted"}': 36.0,
                   '{disposition="delivered"}': 36.0}
    assert chip_smoke.metric_values(text, "pfx_compile_events_total") == {"": 30.0}
    assert chip_smoke.metric_values(text, "pfx_token_ledger_in_flight") == {"": 0.0}


@pytest.mark.slow  # ~45 s: every phase end to end on the CPU at toy sizes
def test_cpu_rehearsal_passes_every_phase():
    out = _run([SMOKE, "--rehearse", "--steps", "3"], timeout=900)
    assert out.returncode == 0, out.stdout[-3000:]
    last = _last_json(out.stdout)
    assert last["ok"] is True and last["rehearsal"] is True
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert last["serve"]["continuous"]["compiles_after_warmup"] == 0
    assert last["serve"]["continuous"]["token_ledger"]["in_flight"] == 0
