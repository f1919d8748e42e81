"""The token gap's books (`core/continuous_batching.TokenGapBooks`,
docs/observability.md "Goodput ledger").

Every gap between two frames of a row is booked once, at the commit
that delivers the later frame, under what the interval between the two
commits held: ``decode``, ``admission`` or ``flush``.  The tests keep a
second account of each run beside the books, from what a client and a
trace would see (the stream sink's frames with the commit they came
from, every donating dispatch, every flush that committed a step in
flight), and hold the books to it:

  close        over adversarial runs (admissions landing under a step in
               flight, rows finishing inside a chained dispatch, a
               chunked prefill, a preemption, a deadline eviction, an
               ArenaReset, speculation) every frame after a row's first
               is in exactly one class, and the sum of the gaps is the
               frames less the first frames
  classes      a stretch without admissions books ``decode`` only, an
               admission into a live batch books ``admission`` for
               exactly the live rows, a full batch with an entry waiting
               books ``flush`` (where there is a step in flight to flush)
  behind       (PR 44) an admission queued behind a step in flight marks
               the interval that begins at that step's commit, where the
               prefill's device time falls, not the one its dispatch call
               happened in; its host seconds book 0 (the device had the
               step queued); ``pfx_sched_admissions_total{path=}`` counts
               each admission under one of three paths
  seconds      with an injected clock the seconds are the stamps'
               differences, and the stamps are the readback span's
  fault        a fault inside the books leaves every request answered
               and counts in ``pfx_sched_gap_books_errors_total``
  warm-up      rows without an entry (warm-up, a direct driver) book
               nothing
"""

import os
import sys
import time
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_continuous_batching import PROMPTS, TINY  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HELD = ("decode", "admission", "flush")
AHEAD = pytest.mark.parametrize("ahead", [True, False], ids=["ahead", "sync"])


@pytest.fixture(scope="module")
def server():
    import jax

    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.core.serving import GenerationServer
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import AttrDict, process_configs

    cfg = AttrDict.from_nested(TINY)
    cfg = process_configs(cfg, num_devices=jax.device_count())
    mesh = init_dist_env(cfg)
    return GenerationServer(cfg, mesh, build_module(cfg))


def _sched(server, ahead, *, depth=32, preempt_min_tokens=2, **engine_kw):
    from paddlefleetx_tpu.core.continuous_batching import (
        ContinuousScheduler,
        PagedDecodeEngine,
    )

    engine_kw.setdefault("max_batch", 4)
    eng = PagedDecodeEngine(server, **engine_kw)
    return ContinuousScheduler(eng, max_depth=depth, dispatch_ahead=ahead,
                               preempt_min_tokens=preempt_min_tokens)


class Account:
    """The second account: which commit framed which request, which
    interval held a donating dispatch or a flush of a step in flight,
    and the stamp of every commit.  Commits are numbered by the engine's
    own ``stats["steps"]``; ``marks[k]`` is what the interval after
    commit ``k`` held."""

    def __init__(self, sched):
        self.sched, self.eng = sched, sched.engine
        eng = self.eng
        self.frames, self.marks, self.stamps = [], {}, {}
        donate, flush, commit = (eng._dispatch_donating, sched._flush_engine,
                                 eng.gap_books.commit)

        def donating(*a, **k):
            if not eng._warmup:
                # queued behind a step in flight, the device runs it after
                # that step: in the interval that begins at its commit
                self.marks[eng.stats["steps"] + eng.has_inflight] = "admission"
            return donate(*a, **k)

        def flushing():
            before = eng.stats["steps"] if eng.has_inflight else None
            n = flush()
            if before is not None and eng.stats["steps"] == before + 1:
                self.marks.setdefault(eng.stats["steps"], "flush")
            return n

        def committing(t, framed):
            self.stamps[eng.stats["steps"]] = t
            return commit(t, framed)

        eng._dispatch_donating = donating
        sched._flush_engine = flushing
        eng.gap_books.commit = committing

    def sink(self, rid):
        def stream(row_idx, start, tokens):
            self.frames.append((self.eng.stats["steps"], rid, len(tokens)))
        return stream

    def submit(self, rid, prompt, max_new, **kw):
        kw.setdefault("deadline_s", 120)
        return self.sched.submit([prompt], max_new, stream=self.sink(rid), **kw)

    def expected(self):
        """(gaps, seconds) by class, and (frames, first frames)."""
        gaps = dict.fromkeys(HELD, 0)
        secs = dict.fromkeys(HELD, 0.0)
        last, firsts = {}, 0
        for k, rid, _ in sorted(self.frames):
            if last.get(rid) == k - 1:
                held = self.marks.get(k - 1, "decode")
                gaps[held] += 1
                secs[held] += self.stamps[k] - self.stamps[k - 1]
            else:
                firsts += 1
            last[rid] = k
        return gaps, secs, len(self.frames), firsts

    def hold(self):
        """The books against this account."""
        books = self.eng.gap_books
        gaps, secs, frames, firsts = self.expected()
        assert books.errors == 0
        assert books.gaps == gaps, (books.gaps, gaps)
        assert sum(books.gaps.values()) == frames - firsts
        for h in HELD:
            assert books.seconds[h] == pytest.approx(secs[h], abs=1e-9), h
        assert all(v >= 0.0 for v in books.seconds.values())
        return gaps


def _run(sched, futs, limit=400):
    """Drive the scheduler by hand until every future is done."""
    for _ in range(limit):
        if all(f.done() for f in futs):
            break
        sched._iterate()
    else:
        raise AssertionError("the requests never finished")
    for _ in range(3):  # commit what is still in flight
        sched._iterate()


def _outcome(f):
    try:
        return f.result(timeout=10)[0]
    except Exception as exc:  # noqa: BLE001 — the scenario failed it on purpose
        return exc


# -- the scenarios of `close` ------------------------------------------------


def _admissions_in_flight(server, ahead, monkeypatch):
    """Requests of unequal length arrive while steps are in flight; rows
    finish inside a chained dispatch while others go on."""
    sched = _sched(server, ahead)
    acc = Account(sched)
    futs = [acc.submit(0, PROMPTS[0], 9), acc.submit(1, PROMPTS[1], 3)]
    for i in range(2, 7):
        for _ in range(2):
            sched._iterate()
        futs.append(acc.submit(i, PROMPTS[i % 4], 2 + 2 * (i % 3)))
    _run(sched, futs)
    assert all(isinstance(_outcome(f), list) for f in futs)
    return acc, {"admission"}


def _chunked_prefill(server, ahead, monkeypatch):
    """A long prompt streams in by chunks beside two decoding rows: each
    chunk is a donating dispatch, the row frames only once it is done."""
    sched = _sched(server, ahead, prefill_chunk=16)
    acc = Account(sched)
    futs = [acc.submit(0, PROMPTS[0], 12), acc.submit(1, PROMPTS[1], 12)]
    for _ in range(3):
        sched._iterate()
    futs.append(acc.submit(2, [1 + (7 * j) % 90 for j in range(70)], 6))
    _run(sched, futs)
    assert sched.engine.stats["prefill_chunks"] >= 4
    return acc, {"admission", "decode"}


def _preemption(server, ahead, monkeypatch):
    """preempt_storm evicts a decoding row, which resumes as a re-prefill
    continuation: the commit it sat out books nothing for it."""
    from paddlefleetx_tpu.utils import resilience

    sched = _sched(server, ahead)
    acc = Account(sched)
    futs = [acc.submit(i, PROMPTS[i], 14) for i in range(3)]
    monkeypatch.setenv("PFX_FAULT", f"preempt_storm:{sched._iter_counter + 5}")
    resilience.reset_fault_state()
    try:
        _run(sched, futs)
    finally:
        monkeypatch.delenv("PFX_FAULT")
        resilience.reset_fault_state()
    assert sched.stats["preemptions"] == 1
    assert all(isinstance(_outcome(f), list) for f in futs)
    return acc, {"admission", "decode"}


def _deadline_eviction(server, ahead, monkeypatch):
    """A row's deadline passes in mid-decode: its frames stop, the other
    rows' gaps go on (a flush that seats nobody, where a step is in
    flight)."""
    from paddlefleetx_tpu.core.request_queue import DeadlineExceeded

    sched = _sched(server, ahead)
    acc = Account(sched)
    futs = [acc.submit(i, PROMPTS[i], 16) for i in range(3)]
    for _ in range(5):
        sched._iterate()
    doomed = next(r for r in sched.engine.slots
                  if r is not None and r.entry.future is futs[1])
    doomed.entry.deadline = time.monotonic() - 1.0
    _run(sched, futs)
    assert sched.stats["evictions"] == 1
    assert isinstance(_outcome(futs[1]), DeadlineExceeded)
    return acc, {"decode", "flush"} if ahead else {"decode"}


def _arena_reset(server, ahead, monkeypatch):
    """A commit's readback dies: the arena is rebuilt, the live rows
    fail, and the next requests are served from fresh books' intervals."""
    from paddlefleetx_tpu.core.continuous_batching import ArenaReset
    from paddlefleetx_tpu.utils import resilience

    sched = _sched(server, ahead)
    acc = Account(sched)
    futs = [acc.submit(i, PROMPTS[i], 16) for i in range(2)]
    for _ in range(4):
        sched._iterate()
    monkeypatch.setenv(
        "PFX_FAULT", f"cb_commit_crash:{sched.engine.stats['steps'] + 2}")
    resilience.reset_fault_state()
    try:
        _run(sched, futs)
    finally:
        monkeypatch.delenv("PFX_FAULT")
        resilience.reset_fault_state()
    assert all(isinstance(_outcome(f), ArenaReset) for f in futs)
    after = [acc.submit(2 + i, PROMPTS[i], 6) for i in range(2)]
    _run(sched, after)
    assert all(isinstance(_outcome(f), list) for f in after)
    return acc, {"decode"}


def _speculation(server, ahead, monkeypatch):
    """A verify step commits several tokens a row: the client sees one
    frame, so the books count one gap."""
    from paddlefleetx_tpu.ops.speculative import SpecConfig

    sched = _sched(server, ahead, spec=SpecConfig(draft_k=3))
    acc = Account(sched)
    futs = [acc.submit(0, [5, 6] * 8, 16), acc.submit(1, PROMPTS[1], 10)]
    _run(sched, futs)
    outs = [_outcome(f) for f in futs]
    assert all(isinstance(o, list) for o in outs)
    assert sum(n for _, _, n in acc.frames) == sum(len(o) for o in outs)
    return acc, {"decode"}


SCENARIOS = {f.__name__[1:]: f for f in (
    _admissions_in_flight, _chunked_prefill, _preemption,
    _deadline_eviction, _arena_reset, _speculation)}


@AHEAD
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_the_books_close(server, monkeypatch, scenario, ahead):
    """Every frame after a row's first is in exactly one class, the one
    the second account gives it, and the gaps sum to the frames less
    the first frames."""
    acc, must_hold = SCENARIOS[scenario](server, ahead, monkeypatch)
    gaps = acc.hold()
    for held in must_hold:
        assert gaps[held] > 0, (held, gaps)
    if not ahead:
        assert gaps["flush"] == 0  # no step in flight, nothing to flush


# -- classes -----------------------------------------------------------------


@AHEAD
def test_a_stretch_without_admissions_books_decode_only(server, ahead):
    """Two rows seated in one iteration and run to their end: their first
    frames are no gaps, every later one is ``decode``, and the books
    close against the token ledger and the admissions counter."""
    sched = _sched(server, ahead)
    acc = Account(sched)
    futs = [acc.submit(i, PROMPTS[i], 8) for i in range(2)]
    _run(sched, futs)
    outs = [_outcome(f) for f in futs]
    gaps = acc.hold()
    assert gaps["admission"] == gaps["flush"] == 0
    assert gaps["decode"] == sum(len(o) for o in outs) - 2
    ledger = sched.token_ledger()
    assert sum(gaps.values()) == ledger["admitted"] - sched.stats["prefill_admits"]
    assert sched.engine.gap_books.admit_host_s > 0.0


@AHEAD
def test_an_admission_into_a_live_batch_books_the_live_rows(server, ahead):
    """A third request joins two decoding rows: the interval that holds
    its prefill books ``admission`` once for each of the two, and the
    newcomer's first frame is no gap."""
    sched = _sched(server, ahead)
    acc = Account(sched)
    futs = [acc.submit(i, PROMPTS[i], 12) for i in range(2)]
    for _ in range(4):
        sched._iterate()
    before = dict(sched.engine.gap_books.gaps)
    assert before["admission"] == 0 and before["decode"] > 0
    futs.append(acc.submit(2, PROMPTS[2], 4))
    _run(sched, futs)
    gaps = acc.hold()
    assert gaps["admission"] == 2
    assert gaps["flush"] == 0


@AHEAD
def test_an_admission_behind_a_step_books_where_its_prefill_runs(server, ahead):
    """Two rows decode, a third request is seated behind the step in
    flight N: the interval that ends at N's commit (the one the dispatch
    call happened in) is ``decode``, the next one, which holds the
    prefill's device time, is ``admission`` for the two live rows, and the
    admission's host seconds book nothing.  A fourth request finds the
    pool short and is seated after a flush; the counter has each path.
    Without dispatch-ahead nothing is ever in flight: every admission is
    ``idle`` and marks the interval its dispatch happened in."""
    sched = _sched(server, ahead, num_blocks=4)  # three rows of one block
    acc = Account(sched)
    eng, books = sched.engine, sched.engine.gap_books
    futs = [acc.submit(i, PROMPTS[i], 10) for i in range(2)]
    for _ in range(4):
        sched._iterate()
    assert eng.has_inflight == ahead
    g0, host0 = dict(books.gaps), books.admit_host_s
    assert host0 > 0.0 and g0["admission"] == 0
    futs.append(acc.submit(2, PROMPTS[2], 4))
    sched._iterate()  # the prefill is dispatched; ahead: N commits after it
    g1 = dict(books.gaps)
    sched._iterate()  # ahead: N+1 commits, the prefill ran before it
    g2 = dict(books.gaps)
    sched._iterate()
    g3 = dict(books.gaps)
    if ahead:
        assert int(sched.stats["admits_behind_step"]) == 1
        assert books.admit_host_s == host0  # the device had N queued
        assert (g1["decode"], g1["admission"]) == (g0["decode"] + 2, 0)
        assert (g2["decode"], g2["admission"]) == (g1["decode"], 2)
    else:
        assert int(sched.stats["admits_idle"]) == 3
        assert books.admit_host_s > host0
        assert (g1["decode"], g1["admission"]) == (g0["decode"], 2)
        assert (g2["decode"], g2["admission"]) == (g1["decode"] + 3, 2)
    # the newcomer's first frame was no gap; from here all three decode
    assert (g3["decode"], g3["admission"]) == (g2["decode"] + 3, 2)
    host1 = books.admit_host_s
    futs.append(acc.submit(3, PROMPTS[3], 4))  # no block left: it waits
    sched._iterate()
    assert sched.depth() == 1
    _run(sched, futs)
    acc.hold()
    assert books.admit_host_s > host1  # seated with nothing queued
    mets = {lab["path"]: v for n, lab, v in sched.collect()
            if n == "pfx_sched_admissions_total"}
    want = ({"behind_step": 1.0, "after_flush": 1.0, "idle": 2.0} if ahead
            else {"behind_step": 0.0, "after_flush": 0.0, "idle": 4.0})
    assert mets == want
    assert sum(mets.values()) == sched.stats["prefill_admits"]
    assert all(isinstance(_outcome(f), list) for f in futs)


@AHEAD
def test_a_full_batch_with_an_entry_waiting_books_flush(server, ahead):
    """Both slots taken and a third request waiting: the scheduler commits
    the step in flight on every iteration to look for room and seats
    nobody, so every step runs after the host: ``flush``.  Without
    dispatch-ahead there is no step in flight and the class is empty."""
    sched = _sched(server, ahead, max_batch=2)
    acc = Account(sched)
    cap = sched.engine.capacity  # a multiple of the data-parallel world
    futs = [acc.submit(i, PROMPTS[i % 4], 10) for i in range(cap)]
    for _ in range(3):
        sched._iterate()
    base = dict(sched.engine.gap_books.gaps)
    futs.append(acc.submit(cap, PROMPTS[2], 4))
    for _ in range(4):
        sched._iterate()
    assert sched.depth() == 1  # still waiting
    mid = dict(sched.engine.gap_books.gaps)
    _run(sched, futs)
    gaps = acc.hold()
    assert mid["admission"] == base["admission"] == 0
    if ahead:
        # the first of the four iterations commits a step that was not flushed
        assert mid["flush"] >= 3 * cap and mid["decode"] - base["decode"] <= cap
    else:
        assert gaps["flush"] == 0 and mid["decode"] - base["decode"] >= 3 * cap
    assert all(isinstance(_outcome(f), list) for f in futs)


# -- seconds -----------------------------------------------------------------


@AHEAD
def test_seconds_are_the_stamps_differences(server, ahead, monkeypatch):
    """With a clock that ticks a quarter of a second a reading under every
    ledger span, the commits' stamps are that clock's (the readback
    span's end, no reading of the books' own) and the seconds are rows x
    the stamps' differences, to the bit."""
    from paddlefleetx_tpu.utils import telemetry

    ticks = iter(range(1, 10 ** 6))
    fake = types.SimpleNamespace(**{k: getattr(time, k) for k in dir(time)
                                    if not k.startswith("_")})
    fake.monotonic = lambda: 0.25 * next(ticks)
    monkeypatch.setattr(telemetry, "time", fake)
    sched = _sched(server, ahead)
    acc = Account(sched)
    futs = [acc.submit(i, PROMPTS[i], 6) for i in range(3)]
    for _ in range(3):
        sched._iterate()
    futs.append(acc.submit(3, PROMPTS[3], 5))
    _run(sched, futs)
    books = sched.engine.gap_books
    gaps, secs, _, _ = acc.expected()
    assert books.gaps == gaps and gaps["admission"] > 0
    assert books.seconds == secs  # quarters add exactly
    stamps = [acc.stamps[k] for k in sorted(acc.stamps)]
    assert all(t * 4 == int(t * 4) and t < 10 ** 6 for t in stamps)
    assert stamps == sorted(stamps)


def test_the_books_alone():
    """The object by itself, on stamps given by hand."""
    from paddlefleetx_tpu.core.continuous_batching import TokenGapBooks

    b = TokenGapBooks()
    b.commit(10.0, [1, 2])            # first frames: no gap
    b.commit(10.5, [1, 2])            # two gaps of 0.5, decode
    b.note("flush")
    b.note("admission")               # an admission beats a flush ...
    b.note("flush")                   # ... whichever came first
    b.commit(12.0, [1, 2, 3])         # two gaps of 1.5, admission; 3 is new
    b.note("flush")
    b.commit(12.25, [2, 3])           # two gaps of 0.25, flush
    b.commit(13.25, [3])
    b.commit(14.25, [])               # nobody framed
    b.commit(15.25, [3])              # sat one commit out: no gap
    assert b.gaps == {"decode": 3, "admission": 2, "flush": 2}
    assert b.seconds == {"decode": 2.0, "admission": 3.0, "flush": 0.5}
    b.admit_host(0.125)
    b.admit_host(-1.0)                # a clock that went back books nothing
    assert b.admit_host_s == 0.125 and b.errors == 0


# -- fault -------------------------------------------------------------------


@AHEAD
def test_a_fault_in_the_books_fails_no_request(server, ahead):
    """The books broken twice in mid-run: both faults are counted, the
    interval they hit is dropped, every request is answered in full and
    the scrape still carries every series."""
    sched = _sched(server, ahead)
    books = sched.engine.gap_books
    sched.start()
    try:
        futs = [sched.submit([p], 12, deadline_s=120,
                             stream=lambda r, s, t: None) for p in PROMPTS]
        for _ in range(2):
            deadline = time.monotonic() + 60
            seen = books.errors
            books._prev = None  # the next commit's intersection raises
            while books.errors == seen and time.monotonic() < deadline:
                time.sleep(0.001)
                if all(f.done() for f in futs):
                    futs.append(sched.submit([PROMPTS[0]], 12, deadline_s=120))
        outs = [f.result(timeout=300)[0] for f in futs]
    finally:
        assert sched.shutdown(timeout=60)
    assert all(len(o) >= 1 for o in outs)
    assert books.errors == 2
    mets = {(n, tuple(sorted(lab.items()))): v for n, lab, v in sched.collect()}
    assert mets[("pfx_sched_gap_books_errors_total", ())] == 2.0
    for held in HELD:
        assert ("pfx_sched_token_gaps_total", (("held", held),)) in mets
        assert ("pfx_sched_token_gap_seconds_total", (("held", held),)) in mets
    assert mets[("pfx_sched_admit_host_seconds_total", ())] > 0.0
    assert sum(books.gaps.values()) > 0  # and went on booking afterwards
    ledger = sched.token_ledger()
    assert ledger["in_flight"] == 0 and ledger["admitted"] == ledger["delivered"]


# -- warm-up -----------------------------------------------------------------


@AHEAD
def test_warm_up_and_rows_without_an_entry_book_nothing(server, ahead):
    """The warm-up's rows, and rows a direct driver seats (the
    benchmark's state probe after its window), belong to no request:
    not one gap, not one second, no admission mark left behind."""
    sched = _sched(server, ahead)
    eng, books = sched.engine, sched.engine.gap_books
    sched.warmup([4])
    eng.dispatch_ahead = False
    slot = eng.admit(PROMPTS[0], 6)
    for _ in range(8):
        eng.step()
    if eng.slots[slot] is not None:
        eng.release(slot)
    assert books.gaps == dict.fromkeys(HELD, 0)
    assert books.seconds == dict.fromkeys(HELD, 0.0)
    assert books.admit_host_s == 0.0 and books.errors == 0
    assert "gap_steps" not in eng.stats
    assert "gap_steps" not in sched._engine_debug_view()["overlap"]


# -- the benchmark's three readings -------------------------------------------


@pytest.mark.parametrize("metric,want", [
    ("sched.gap_admission_share", 25.0),
    ("sched.gap_flush_share", 5.0),
    ("sched.admit_host_share", 2.0),
])
def test_the_layer_metric_reads_its_counter(metric, want):
    """Each of the three metric files through the reader it names, on a
    scrape delta written by hand; on a delta without the families (a
    program from before the books) the reader returns nothing."""
    bench = os.path.join(REPO, "pfx_bench")  # noqa: E10 — a directory, not a metric
    sys.path.insert(0, bench)
    try:
        import common
        import run  # the benchmark's own loader of a reader by name

        d = common.load_layer_metric(metric)
        reader = run.load_module("readers", d["reader"])
    finally:
        sys.path.remove(bench)
    assert (d["name"], d["layer"], d["moves"], d["better"], d["source"], d["unit"]) == (
        metric, "serving scheduler", "itl_mean_ms", "lower", "program_counter", "%")
    delta = {
        'pfx_sched_token_gap_seconds_total{held="decode"}': 7.0,
        'pfx_sched_token_gap_seconds_total{held="admission"}': 2.5,
        'pfx_sched_token_gap_seconds_total{held="flush"}': 0.5,
        'pfx_sched_token_gaps_total{held="decode"}': 1000.0,
        "pfx_sched_admit_host_seconds_total": 0.4,
        "pfx_sched_wall_seconds_total": 50.0,
        'pfx_sched_time_seconds_total{bucket="idle"}': 30.0,
        'pfx_sched_time_seconds_total{bucket="host_sched"}': 5.0,
    }
    assert reader.read({"scrape_delta": delta}, **d["args"]) == pytest.approx(want)
    old = {k: v for k, v in delta.items()
           if "token_gap" not in k and "admit_host" not in k}
    got = reader.read({"scrape_delta": old}, **d["args"])
    assert got is None or got == 0.0
