"""The benchmark's own quick checks under tier-1.

``pfx_bench/selftest/run.py`` is run by hand; every PR is judged through
the code it checks (the contract of ``BENCHMARK.json`` and its data files,
the trace reduction on its recorded fixture, the FLOP arithmetic, the load
generator's seeding, the refusal to run without a chip).  This file calls
those checks and edits nothing under ``pfx_bench/``.
"""

import importlib.util
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NO_CHIP_LIMIT_S = 120


@pytest.fixture(scope="module")
def selftest():
    path = os.path.join(REPO, "pfx_bench", "selftest", "run.py")  # noqa: E10 — a directory, not a metric
    spec = importlib.util.spec_from_file_location("bench_selftest_run", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize(
    "name", ["check_contract", "check_trace", "check_math", "check_loadgen", "check_no_chip"]
)
def test_selftest_check(selftest, name, monkeypatch):
    del selftest.FAILS[:]
    if name == "check_no_chip":
        # as tier-1 itself runs: no chip, the CPU pinned; the run must
        # refuse within seconds and print no result line
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        run_cmd = selftest.run_cmd
        monkeypatch.setattr(
            selftest, "run_cmd",
            lambda argv, timeout: run_cmd(argv, min(timeout, NO_CHIP_LIMIT_S)))
        cells = sorted(
            f[:-5] for f in os.listdir(os.path.join(selftest.BENCH, "workloads")))
        selftest.check_no_chip(cells)
    else:
        getattr(selftest, name)()
    assert selftest.FAILS == []
