"""Five serving cells through the benchmark's own rehearsal at
``--trace 2``, with the token gap's books read beside the run (and, since
PR 53, the two train cells with the engine's shares listed).

PR 35's books were refused on one ``--trace 2`` run of
``serve-dsv3-1of32-think`` that came back not ``correct``, and no tier-1
test had the shape of that run.  These drive ``pfx_bench/run.py``'s
``main`` (through ``tools/gap_books_run.py``, which edits nothing under
``pfx_bench/``) at toy widths on the CPU: the real server process, the
open loop, the window's two scrapes, the drain, the reference check, the
traced stretch with its ``POST /admin/profile``, and ``judge``.  Each
cell must come back ``correct`` with no ``check failed:`` line, carry the
new series in its window's scrape, and close its books against the token
ledger AND against the frames the clients counted."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HELD = ("decode", "admission", "flush")
BOOK_METRICS = ("sched.gap_admission_share", "sched.gap_flush_share",
                "sched.admit_host_share", "sched.stall_share")
TRAIN_METRICS = ("engine.stall_share", "engine.host_gap_share")


@pytest.mark.parametrize("cell,seed", [
    ("serve-dsv3-1of32-think", 3_700_000_011),
    ("serve-nemotron3-nano-1of8-chat", 3_700_000_029),
    ("serve-1.3b-docs", 3_700_000_047),
    ("serve-mellum2-12b-1of4-code", 4_200_000_061),  # two classes of pages (PR 42)
    ("serve-falcon-h1-34b-6of72-chat", 4_400_000_017),  # the most admissions a second (PR 44)
])
def test_a_serving_cell_rehearses_correct_with_its_books_closed(cell, seed):
    win = _rehearse(cell, seed)
    # the server's mean gap is the client's, one SSE flush later (toy widths
    # on a shared CPU: loose; the chip's agreement is in PERF.md).  Under six
    # workers the driver's run of PR 41 read 3.08 against 2.17 ms in the dsv3
    # case, the one failure of that run, and every case passes alone: five
    # other test processes move the window's two scrapes and the client's
    # clock apart.  A busy host misses now and then, a wrong clock or a
    # dropped class every time: so one more rehearsal, held to all of the
    # above again, before the same bound decides
    if win["server_gap_mean_ms"] != pytest.approx(win["client_gap_mean_in_window_ms"], rel=0.25):
        win = _rehearse(cell, seed)
    assert win["server_gap_mean_ms"] == pytest.approx(
        win["client_gap_mean_in_window_ms"], rel=0.25)


@pytest.mark.parametrize("cell,seed", [
    ("train-345m-1chip", 5_300_000_011),
    ("train-trinity-mini-1of8", 5_300_000_023),
])
def test_a_train_cell_rehearses_with_the_engine_shares_listed(cell, seed):
    """A train cell through the same tool (PR 53): its line carries the two
    shares of the step records whose files wait for a ``benchmark`` issue,
    and there are no books to print."""
    line, lines = _run(cell, seed)
    assert "train_tokens_per_s" in line["metrics"]
    assert "engine.data_wait_share" in line["metrics"]
    for name in TRAIN_METRICS:
        # 0.0 where no step of the window ran far past the others; on a
        # CPU that six workers share one may
        assert 0.0 <= line["metrics"][name]["value"] <= 100.0, name
    assert lines[-1].startswith('{"correct"')
    assert not any(ln.startswith("gap_books:") for ln in lines)


def _run(cell: str, seed: int):
    """One rehearsal of ``cell`` -> its result line, and every line."""
    env = dict(os.environ)
    env.pop("PFX_FAULT", None)
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "gap_books_run.py"),
         "--rehearse", "--workload", cell, "--seed", str(seed),
         "--seconds", "6", "--trace", "2"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    out = p.stdout
    assert p.returncode == 0, out[-3000:] + p.stderr[-2000:]
    assert "check failed:" not in out, out[-3000:]
    lines = out.strip().splitlines()
    line = json.loads(next(ln for ln in reversed(lines) if ln.startswith('{"correct"')))
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    return line, lines


def _rehearse(cell: str, seed: int) -> dict:
    """One rehearsal of ``cell``, held to everything but the two clocks'
    agreement -> its window's books."""
    line, lines = _run(cell, seed)
    # the traced run's line carries the accepted metrics, the books' three
    # and the slow iterations' share (0.0 in a sound window)
    assert "itl_mean_ms" in line["metrics"] and "sched.prefill_share" in line["metrics"]
    for name in BOOK_METRICS:
        assert 0.0 <= line["metrics"][name]["value"] <= 100.0, name
    books = json.loads(lines[-1].split("gap_books: ", 1)[1])
    assert books["series_present"] and books["errors"] == 0
    win, boot = books["window"], books["since_boot"]
    assert set(win["gaps"]) == set(HELD) and sum(win["gaps"].values()) > 0
    assert all(win["seconds"][h] >= 0.0 for h in HELD)
    assert (win["seconds"]["flush"] > 0) == (win["gaps"]["flush"] > 0)
    # the books close, exactly: the server's gaps since boot are the frames
    # less the first frames by the token ledger and by the clients' own count
    assert boot["gaps"] == boot["ledger_frames_less_rows"] == boot["client_frames_less_rows"]
    assert books["closed"] is True
    # over the window the ledger's side may be off by the rows seated but
    # not yet framed when a scrape landed, and by one commit's rows (four
    # slots here) where the commit landed between the two families' reads
    assert abs(sum(win["gaps"].values()) - win["ledger_frames_less_rows"]) <= 8
    # every admission of an expert model went through pfx_grouped_matmul:
    # its calls a prefill are a constant of the program (none for the dense block)
    assert win["prefill_admits"] > 0
    # each under one of the three paths (which, follows the arrivals: an
    # admission into a live batch goes behind the step in flight)
    assert sum(win["admissions"].values()) == win["prefill_admits"]
    assert win["moe_grouped_calls"] == _grouped_products(cell) * win["prefill_admits"]
    return win


def _grouped_products(cell: str) -> int:
    from paddlefleetx_tpu.models.gpt.config import GPTConfig

    bench = os.path.join(REPO, "pfx_bench")  # noqa: E10 — a directory, not a metric
    with open(os.path.join(bench, "workloads", f"{cell}.json")) as f:
        config = json.load(f)["config"]
    with open(os.path.join(bench, "configs", f"{config}.json")) as f:
        spec = json.load(f)
    model = dict(spec["model"], **spec.get("rehearse_model", {}))
    products = GPTConfig(**model).sorted_pair_products
    dense = ("serve-1.3b-docs", "serve-falcon-h1-34b-6of72-chat")  # no expert layer
    assert (products > 0) == (cell not in dense)
    return products
