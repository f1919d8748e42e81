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


@pytest.mark.parametrize("mix,cell,pad,buckets,of_knee", [
    ("think-long-answers", "serve-dsv3-1of32-think", 512, [1024, 1536, 2048, 2560, 3072], 0.8),
    ("chat-short-answers", "serve-nemotron3-nano-1of8-chat", 256, [256, 512, 768, 1024], 0.8),
    ("chat-brief-turns", "serve-falcon-h1-34b-6of72-chat", 256, [256], 0.8),  # ONE bucket, one stall size
    ("context-chat-answers", "serve-xing4-29b-6of40-rag", 2048, [2048], 0.8),  # ONE bucket again
])
def test_a_serving_cell_s_data_files(selftest, mix, cell, pad, buckets, of_knee):
    """A cell added after the self-test's own list of mixes: its traffic
    through the generator (the same seed the same plan, another seed the
    same sizes in another order, lengths inside the mix's bounds, the
    prompt buckets the server warms), and its workload's readers and
    runner found by name."""
    common, loadgen = selftest.common, selftest.loadgen
    t = common.load_traffic(mix)
    vocab = common.load_cell(cell)["config_data"]["model"]["vocab_size"]
    a = loadgen.build_plan(t, 3, 51.0, vocab)
    b = loadgen.build_plan(t, 3_000_000_007, 51.0, vocab)
    assert a == loadgen.build_plan(t, 3, 51.0, vocab)
    win = [r for r in a["requests"] if r["phase"] == "window"]
    assert len(win) == round(t["rate_rps"] * 51.0)
    assert sorted(len(r["prompt_ids"]) for r in a["requests"]) == sorted(
        len(r["prompt_ids"]) for r in b["requests"])
    assert [r["prompt_ids"] for r in a["requests"]] != [r["prompt_ids"] for r in b["requests"]]
    lo, hi = t["prompt_len"]["min"], t["prompt_len"]["max"]
    assert all(lo <= len(r["prompt_ids"]) <= hi and 1 <= min(r["prompt_ids"])
               and max(r["prompt_ids"]) < vocab for r in a["requests"])
    assert all(t["max_tokens"]["min"] <= r["max_tokens"] <= t["max_tokens"]["max"]
               for r in a["requests"])
    assert loadgen.prompt_buckets(t, pad) == buckets
    assert abs(t["rate_rps"] - of_knee * t["knee_rps"]) < 1e-9 and t["knee_note"]
    c = common.load_cell(cell)
    assert os.path.isfile(os.path.join(selftest.BENCH, "runners", c["runner"] + ".py"))
    assert os.path.isfile(os.path.join(selftest.BENCH, "runners", c["runner"] + "_child.py"))
    assert os.path.isfile(os.path.join(selftest.BENCH, "runners", c["runner"] + ".md"))
    for name in c["per_layer"]:
        reader = common.load_layer_metric(name)["reader"]
        assert os.path.isfile(os.path.join(selftest.BENCH, "readers", reader + ".py")), name
    conf = c["config_data"]
    for key in ("reference", "math", "yaml"):
        assert os.path.isfile(os.path.join(selftest.ROOT, conf[key])), key
    ctx = max(hi + t["max_tokens"]["max"], 0)
    assert ctx <= conf["model"]["max_position_embeddings"]  # no request outgrows the context


@pytest.mark.parametrize("kind", ["command", "configs", "workloads", "end_to_end", "per_layer"])
def test_a_benchmark_entry_s_lines_fit(kind):
    """The contract's limit on every free line of ``BENCHMARK.json`` (a
    ``why``, a ``layer``, a configuration's ``source``, a word of
    ``command``): 1 to 200 printable characters on one line.  The self-test
    holds only a cell's ``why`` to it; PR 55 was refused once over a
    configuration's."""
    import json
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    if kind == "command":
        lines = {"command": bench["command"]}
    else:
        keys = ("why", "layer") + (("source",) if kind == "configs" else ())
        lines = {e["name"]: [e[k] for k in keys if k in e] for e in bench[kind]}
    bad = {n: len(t) for n, ts in lines.items() for t in ts
           if not (1 <= len(t) <= 200 and t.isprintable())}
    assert not bad
