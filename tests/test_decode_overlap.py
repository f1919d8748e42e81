"""Dispatch-ahead decode overlap acceptance (`make test-decode-overlap`).

  replay equality   the SAME seeded traffic (admissions, a pre-expired
                    shed, speculative commits) through
                    ``dispatch_ahead=True`` vs ``False`` folds to
                    IDENTICAL `replay_decision_log` totals and
                    token-identical greedy output — the exact-replay
                    contract the commit-order decision-log landing
                    exists to keep;
  ArenaReset drill  an injected crash (PFX_FAULT=cb_commit_crash) in
                    the commit readback of an IN-FLIGHT dispatched step
                    resets cleanly: exactly the live seq_ids die, the
                    stale in-flight handle is dropped, and the rebuilt
                    arena decodes token-identically;
  behind the step   (PR 44) an admission the scheduler's own view can
                    seat is dispatched with the step still in flight,
                    the step after it is dispatched from the host
                    mirrors after that step's commit, and the outputs
                    of a seeded mix of staggered admissions are
                    token-identical to ``dispatch_ahead=False``; a full
                    batch or a short pool flushes first and seats in the
                    same iteration; speculation, a chunked or prefix-hit
                    admission, an adoption and a resume flush first; a
                    commit crash of the step an admission was queued
                    behind kills the admitted row with the others;
  streamed drill    (slow) POST /generate?stream=1 through the REAL
                    router + replica CLIs yields >= 2 SSE token flushes
                    with per-row monotone token indices, ITL
                    percentiles on the replica's /metrics, and an
                    intact stitched trace at the router.
"""

import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_continuous_batching import PROMPTS, TINY  # noqa: E402
# a scheduler driven by hand (``_iterate``), never started, and its drain
from test_gap_books import _run, _sched as _hand_sched  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# repetitive prompt: the n-gram self-draft's best case, so the
# speculative side actually ACCEPTS drafts and the replay-equality
# assertion covers a non-zero pfx_spec_accepted_total
REP = [5, 6] * 8


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
    module = build_module(cfg)
    return GenerationServer(cfg, mesh, module)


def _run_seeded_traffic(server, ahead: bool):
    """One deterministic traffic mix through a fresh engine+scheduler:
    4 plain admissions, 1 speculative-friendly repetitive prompt, and
    1 pre-expired request (shed before admission on both sides)."""
    from paddlefleetx_tpu.core.continuous_batching import (
        ContinuousScheduler,
        PagedDecodeEngine,
    )
    from paddlefleetx_tpu.core.request_queue import DeadlineExceeded
    from paddlefleetx_tpu.ops.speculative import SpecConfig
    from paddlefleetx_tpu.utils.tracing import replay_decision_log

    eng = PagedDecodeEngine(server, max_batch=4,
                            spec=SpecConfig(draft_k=3))
    sched = ContinuousScheduler(eng, max_depth=16, dispatch_ahead=ahead)
    doomed = sched.submit([PROMPTS[0]], 6, deadline_s=0.01)
    time.sleep(0.05)  # expired BEFORE the scheduler thread starts
    sched.start()
    futs = [sched.submit([p], 6, deadline_s=120)
            for p in PROMPTS + [REP]]
    outs = [f.result(timeout=300)[0] for f in futs]
    with pytest.raises(DeadlineExceeded):
        doomed.result(timeout=60)
    assert sched.shutdown(timeout=30)
    replay = replay_decision_log(sched.decision_log)
    return outs, replay, dict(sched.stats)


def test_replay_equality_dispatch_ahead_on_vs_off(server):
    """THE overlap acceptance: identical seeded traffic folds to the
    same decision-log totals with dispatch-ahead on or off, and the
    greedy outputs are token-identical (f32)."""
    outs_a, replay_a, stats_a = _run_seeded_traffic(server, ahead=True)
    outs_s, replay_s, stats_s = _run_seeded_traffic(server, ahead=False)
    assert outs_a == outs_s
    # iteration COUNT is wall-clock (idle iterations append all-zero
    # rows); every event total must agree exactly
    fold_a = {k: v for k, v in replay_a.items() if k != "iterations"}
    fold_s = {k: v for k, v in replay_s.items() if k != "iterations"}
    assert fold_a == fold_s, (fold_a, fold_s)
    assert fold_a["prefill_admits"] == len(PROMPTS) + 1
    assert fold_a["shed"] == 1
    assert fold_a["evictions"] == replay_s["evictions"]
    # the repetitive prompt made speculation commit real tokens, so the
    # equality above covers the spec counters non-trivially
    assert fold_a["spec_accepted"] > 0
    for k in ("prefill_admits", "completed", "evictions", "shed_deadline"):
        assert stats_a[k] == stats_s[k], (k, stats_a[k], stats_s[k])


def test_arena_reset_mid_overlap_kills_exactly_the_live_rows(
    server, monkeypatch
):
    """An in-flight dispatched step whose commit readback crashes
    resets the arena cleanly: the ArenaReset carries exactly the live
    seq_ids, the poisoned in-flight handle is dropped, and the rebuilt
    arena decodes token-identically."""
    from paddlefleetx_tpu.core.continuous_batching import (
        ArenaReset,
        PagedDecodeEngine,
    )
    from paddlefleetx_tpu.utils import resilience

    ref = server.generate_ids([PROMPTS[0]], max_dec_len=6)[0]
    eng = PagedDecodeEngine(server, max_batch=4)
    eng.dispatch_ahead = True
    s0 = eng.admit(PROMPTS[0], 6)
    s1 = eng.admit(PROMPTS[1], 6)
    eng.step()  # dispatches step 1 and leaves it IN FLIGHT
    assert eng.has_inflight
    live = {eng.slots[s].seq_id for s in (s0, s1)}
    resilience.reset_fault_state()
    monkeypatch.setenv("PFX_FAULT", "cb_commit_crash:1")
    try:
        # chains step 2 on the in-flight handles, then commits step 1 —
        # where the injected readback crash fires
        with pytest.raises(ArenaReset) as ei:
            eng.step()
    finally:
        monkeypatch.delenv("PFX_FAULT")
        resilience.reset_fault_state()
    assert {r.seq_id for r in ei.value.dead_rows} == live
    assert not eng.has_inflight  # the chained step died with the arena
    assert not eng.active.any()
    # fresh pools: an identical request decodes token-identically
    s2 = eng.admit(PROMPTS[0], 6)
    for _ in range(96):
        eng.step()
        if not eng.active.any():
            break
    eng.flush()
    assert eng.slots[s2].tokens == ref


# ---------------------------------------------------------------------------
# an admission behind the step in flight (PR 44)
# ---------------------------------------------------------------------------


def _watch(sched):
    """What the engine was handed, in order: ``("admit" | "adopt", a step
    was in flight)``, ``("step", chained)``, ``("flush", committed)``."""
    eng = sched.engine
    events = []
    admit, adopt, dispatch, flush = (eng.admit, eng.adopt, eng._dispatch,
                                     sched._flush_engine)

    def admitting(*a, **k):
        events.append(("admit", eng.has_inflight))
        return admit(*a, **k)

    def adopting(*a, **k):
        events.append(("adopt", eng.has_inflight))
        return adopt(*a, **k)

    def dispatching(*a, overlapped, **k):
        events.append(("step", overlapped))
        return dispatch(*a, overlapped=overlapped, **k)

    def flushing():
        events.append(("flush", eng.has_inflight))
        return flush()

    eng.admit, eng.adopt, eng._dispatch = admitting, adopting, dispatching
    sched._flush_engine = flushing
    return events


def _finish(sched, futs):
    _run(sched, futs)
    return [f.result(timeout=10)[0] for f in futs]


def _paths(sched):
    return {p: int(sched.stats["admits_" + p]) for p in sched.ADMIT_PATHS}


def _two_rows_in_flight(sched):
    """Two rows decoding, a step in flight, a slot free."""
    futs = [sched.submit([PROMPTS[i]], 12, deadline_s=120) for i in range(2)]
    for _ in range(3):
        sched._iterate()
    assert sched.engine.has_inflight and sched.engine.free_slots() >= 1
    return futs


def test_a_seatable_admission_is_dispatched_behind_the_step_in_flight(server):
    """Two rows decode, a step is in flight, a slot is free: the third
    request's prefill is dispatched while ``has_inflight`` is still true
    and nothing is flushed for it; the step after it does not chain (it
    is dispatched after that step's commit, from the host mirrors); the
    one after that chains again; the outputs are the sequential path's."""
    sched = _hand_sched(server, True)
    eng = sched.engine
    events = _watch(sched)
    futs = _two_rows_in_flight(sched)
    assert _paths(sched) == {"behind_step": 0, "after_flush": 0, "idle": 2}
    del events[:]
    futs.append(sched.submit([PROMPTS[2]], 6, deadline_s=120))
    steps0 = int(eng.stats["steps"])
    sched._iterate()
    assert events == [("admit", True), ("step", False)], events
    assert int(eng.stats["steps"]) == steps0 + 1  # committed inside step()
    assert eng.has_inflight and eng.active_rows() == 3
    assert _paths(sched) == {"behind_step": 1, "after_flush": 0, "idle": 2}
    sched._iterate()
    assert events[-1] == ("step", True), events
    outs = _finish(sched, futs)
    refs = [server.generate_ids([PROMPTS[i]], max_dec_len=n)[0]
            for i, n in ((0, 12), (1, 12), (2, 6))]
    assert outs == refs
    assert ("flush", True) not in events
    assert sched.stats["prefill_admits"] == sum(_paths(sched).values()) == 3


def test_a_chained_step_that_outlived_its_rows_is_nothing_in_flight(server):
    """The batch drained inside a chained dispatch: the step still "in
    flight" carried no live row and the device is long done with it.  The
    next admission commits it first and counts as ``idle``."""
    sched = _hand_sched(server, True)
    eng = sched.engine
    events = _watch(sched)
    _finish(sched, [sched.submit([PROMPTS[i]], 5, deadline_s=120) for i in range(2)])
    assert eng.has_inflight and eng.inflight_rows == 0
    assert not eng.seats_behind_step(PROMPTS[2])
    del events[:]
    fut = sched.submit([PROMPTS[2]], 5, deadline_s=120)
    sched._iterate()
    assert events == [("flush", True), ("admit", False), ("step", False)], events
    assert _paths(sched) == {"behind_step": 0, "after_flush": 0, "idle": 3}
    assert _finish(sched, [fut])[0] == server.generate_ids(
        [PROMPTS[2]], max_dec_len=5)[0]


def _staggered_mix(server, ahead):
    """A seeded mix: sixteen requests of random prompts and budgets,
    submitted zero to three iterations apart into eight slots."""
    import random

    from paddlefleetx_tpu.utils.tracing import replay_decision_log

    rng = random.Random(44)
    sched = _hand_sched(server, ahead, max_batch=8)
    futs = []
    for _ in range(16):
        prompt = [rng.randrange(1, 90) for _ in range(rng.randrange(2, 15))]
        futs.append(sched.submit([prompt], rng.randrange(3, 13), deadline_s=120))
        for _ in range(rng.randrange(0, 4)):
            sched._iterate()
    outs = _finish(sched, futs)
    replay = replay_decision_log(sched.decision_log)
    return outs, replay, sched


def test_staggered_admissions_are_token_identical_ahead_and_sync(server):
    """The seeded mix through ``dispatch_ahead=True`` (most admissions
    queued behind a step in flight) and ``False`` (none): the same tokens
    for every request, the same decision-log totals, closed ledgers."""
    outs_a, replay_a, sched_a = _staggered_mix(server, True)
    outs_s, replay_s, sched_s = _staggered_mix(server, False)
    assert outs_a == outs_s
    fold_a = {k: v for k, v in replay_a.items() if k != "iterations"}
    fold_s = {k: v for k, v in replay_s.items() if k != "iterations"}
    assert fold_a == fold_s, (fold_a, fold_s)
    assert fold_a["prefill_admits"] == 16
    paths_a, paths_s = _paths(sched_a), _paths(sched_s)
    assert paths_a["behind_step"] >= 8, paths_a
    assert sum(paths_a.values()) == 16
    assert paths_s == {"behind_step": 0, "after_flush": 0, "idle": 16}
    for sched in (sched_a, sched_s):
        ledger = sched.token_ledger()
        assert ledger["in_flight"] == 0
        assert ledger["admitted"] == ledger["delivered"] == sum(map(len, outs_a))
        assert sched.engine.gap_books.errors == 0


@pytest.mark.parametrize("short", ["slots", "blocks"])
def test_a_view_that_cannot_seat_flushes_then_seats_in_the_same_iteration(
    server, short
):
    """Every slot taken (or the pool too short) and an entry waiting: each
    iteration commits the step in flight to look for room, as before, and
    the iteration whose flush frees the room seats the entry after it."""
    if short == "slots":
        sched = _hand_sched(server, True, max_batch=2)
        n_live = sched.engine.capacity
    else:
        # one block a row: three rows fill the pool, five slots stay free
        sched = _hand_sched(server, True, max_batch=8, num_blocks=4)
        n_live = 3
    eng = sched.engine
    events = _watch(sched)
    futs = [sched.submit([PROMPTS[i % 4]], 6 if i else 3, deadline_s=120)
            for i in range(n_live)]
    for _ in range(2):
        sched._iterate()
    assert eng.has_inflight and eng.active_rows() == n_live
    waiting = sched.submit([PROMPTS[2]], 4, deadline_s=120)
    futs.append(waiting)
    seated_in = None
    for it in range(40):
        del events[:]
        sched._iterate()
        if ("admit", False) in events or ("admit", True) in events:
            seated_in = list(events)
            break
        # nobody seated: the step in flight was committed to look
        assert events[0] == ("flush", True), events
    assert seated_in is not None
    # the flush that freed the room comes first, the admission after it
    # in the same iteration, with nothing in flight
    assert seated_in[0] == ("flush", True), seated_in
    assert ("admit", False) in seated_in and ("admit", True) not in seated_in
    assert seated_in.index(("flush", True)) < seated_in.index(("admit", False))
    paths = _paths(sched)
    assert paths["after_flush"] == 1 and paths["behind_step"] == 0, paths
    outs = _finish(sched, futs)
    assert outs[-1] == server.generate_ids([PROMPTS[2]], max_dec_len=4)[0]


def _case_speculation(server):
    from paddlefleetx_tpu.ops.speculative import SpecConfig

    sched = _hand_sched(server, True, spec=SpecConfig(draft_k=3))
    return sched, lambda: sched.submit([PROMPTS[2]], 6, deadline_s=120), "admit"


def _case_chunked(server):
    sched = _hand_sched(server, True, prefill_chunk=16)
    long = [1 + (7 * j) % 90 for j in range(40)]
    return sched, lambda: sched.submit([long], 6, deadline_s=120), "admit"


def _case_prefix_hit(server):
    # the prompt's first block was published by a finished request
    sched = _hand_sched(server, True, prefix_cache_blocks=8)
    shared = [1 + (5 * j) % 90 for j in range(20)]
    _finish(sched, [sched.submit([shared + [7]], 3, deadline_s=120)])
    assert sched.engine.cache.prefix.cached_blocks() >= 1
    return sched, lambda: sched.submit([shared + [9]], 6, deadline_s=120), "admit"


def _case_adoption(server):
    from paddlefleetx_tpu.core.continuous_batching import PagedDecodeEngine
    from paddlefleetx_tpu.core.paged_cache import pack_handoff, unpack_handoff

    sched = _hand_sched(server, True)
    exporter = PagedDecodeEngine(server, max_batch=4)
    meta, arrays = unpack_handoff(
        pack_handoff(*exporter.prefill_export(PROMPTS[2], 6)))
    return (sched, lambda: sched.submit_handoff(meta, arrays, deadline_s=120),
            "adopt")


CASES = {f.__name__[6:]: f for f in (
    _case_speculation, _case_chunked, _case_prefix_hit, _case_adoption)}


@pytest.mark.parametrize("case", sorted(CASES))
def test_what_cannot_queue_behind_a_step_still_flushes_first(server, case):
    """Speculation, a chunked prefill, a prefix hit and a handoff adoption
    with a free slot and a step in flight: the step is committed first
    and the admission finds nothing in flight, as before."""
    sched, submit, kind = CASES[case](server)
    eng = sched.engine
    events = _watch(sched)
    futs = _two_rows_in_flight(sched)
    before = _paths(sched)
    del events[:]
    futs.append(submit())
    sched._iterate()
    assert events[:2] == [("flush", True), (kind, False)], events
    paths = _paths(sched)
    assert paths["behind_step"] == before["behind_step"] == 0
    assert paths["after_flush"] == before["after_flush"] + 1
    outs = _finish(sched, futs)
    assert all(len(o) >= 1 for o in outs)
    assert eng.gap_books.errors == 0


def test_a_prefix_miss_with_the_cache_on_queues_behind_the_step(server):
    """The prefix cache on and nothing cached for the prompt: the
    monolithic prefill, so the admission goes behind the step in flight."""
    sched = _hand_sched(server, True, prefix_cache_blocks=8)
    events = _watch(sched)
    futs = _two_rows_in_flight(sched)
    del events[:]
    futs.append(sched.submit([[40 + j for j in range(20)]], 6, deadline_s=120))
    sched._iterate()
    assert events == [("admit", True), ("step", False)], events
    assert _paths(sched)["behind_step"] == 1
    _finish(sched, futs)


def test_a_resumed_row_flushes_first(server, monkeypatch):
    """A preempted row's continuation is re-seated after a flush, never
    behind the step in flight; the fresh rows around it go behind."""
    from paddlefleetx_tpu.utils import resilience

    sched = _hand_sched(server, True)
    eng = sched.engine
    events = _watch(sched)
    futs = [sched.submit([PROMPTS[i]], 14, deadline_s=120) for i in range(3)]
    for _ in range(4):
        sched._iterate()
    monkeypatch.setenv("PFX_FAULT", f"preempt_storm:{sched._iter_counter + 1}")
    resilience.reset_fault_state()
    try:
        sched._iterate()  # the storm: flush, preempt one row, step
    finally:
        monkeypatch.delenv("PFX_FAULT")
        resilience.reset_fault_state()
    assert sched.stats["preemptions"] == 1 and eng.has_inflight
    before = _paths(sched)
    del events[:]
    sched._iterate()  # the continuation is the head of the queue
    assert events[:2] == [("flush", True), ("admit", False)], events
    paths = _paths(sched)
    assert paths["after_flush"] == before["after_flush"] + 1
    assert paths["behind_step"] == before["behind_step"]
    outs = _finish(sched, futs)
    refs = [server.generate_ids([PROMPTS[i]], max_dec_len=14)[0] for i in range(3)]
    assert outs == refs


def test_counts_of_a_prefill_behind_a_step_wait_for_the_commit_after(server):
    """A prefill's expert-layer counts are device values its program
    computes: fetched at the commit of the step it was queued behind they
    would hold the host until the prefill has run.  They ride that step's
    record and are fetched with the next commit's."""
    import numpy as np

    from paddlefleetx_tpu.core.continuous_batching import PagedDecodeEngine

    eng = PagedDecodeEngine(server, max_batch=4)
    eng.dispatch_ahead = True
    eng.admit(PROMPTS[0], 8)
    eng._count_moe(np.array([4, 2, 1]), fetch=False)  # nothing in flight
    assert len(eng._moe_pending) == 1
    eng.step()  # in flight
    eng._count_moe(np.array([7, 5, 3]), fetch=False)  # queued behind it
    assert len(eng._moe_pending) == 1 and len(eng._inflight["moe_behind"]) == 1
    eng._dispatch_donating(lambda: None, "a prefill behind the step")
    eng.step()  # commit-first: that step's commit fetched neither
    assert len(eng._moe_pending) == 2 and eng.stats["moe_pairs"] == 0
    eng._count_moe(np.array([1, 1, 1]))  # as the next commit does
    assert not eng._moe_pending
    assert [eng.stats[k] for k in ("moe_pairs", "moe_held_pairs", "moe_held_max_pairs")] == [12, 8, 5]
    eng.flush()


def test_commit_crash_of_a_step_an_admission_was_queued_behind(
    server, monkeypatch
):
    """``cb_commit_crash`` on the commit of a step that a prefill was
    queued behind: the ArenaReset carries the admitted row with the rows
    that were in the step, and the rebuilt arena decodes
    token-identically."""
    from paddlefleetx_tpu.core.continuous_batching import (
        ArenaReset,
        PagedDecodeEngine,
    )
    from paddlefleetx_tpu.utils import resilience

    ref = server.generate_ids([PROMPTS[0]], max_dec_len=6)[0]
    eng = PagedDecodeEngine(server, max_batch=4)
    eng.dispatch_ahead = True
    s0 = eng.admit(PROMPTS[0], 6)
    s1 = eng.admit(PROMPTS[1], 6)
    eng.step()  # in flight
    assert eng.has_inflight and eng.seats_behind_step(PROMPTS[2])
    s2 = eng.admit(PROMPTS[2], 6)  # its prefill queues behind the step
    assert eng.has_inflight
    live = {eng.slots[s].seq_id for s in (s0, s1, s2)}
    resilience.reset_fault_state()
    monkeypatch.setenv("PFX_FAULT", "cb_commit_crash:1")
    try:
        # commit-first (no chaining behind an admission): the crash fires
        # in the commit of the step the prefill was queued behind
        with pytest.raises(ArenaReset) as ei:
            eng.step()
    finally:
        monkeypatch.delenv("PFX_FAULT")
        resilience.reset_fault_state()
    assert {r.seq_id for r in ei.value.dead_rows} == live
    assert not eng.has_inflight and not eng.active.any()
    assert eng.cache.stats()["kv_blocks_used"] == 0
    s3 = eng.admit(PROMPTS[0], 6)
    for _ in range(96):
        eng.step()
        if not eng.active.any():
            break
    eng.flush()
    assert eng.slots[s3].tokens == ref


def test_a_failed_admission_behind_a_step_commits_it_before_siblings_leave(
    server, monkeypatch
):
    """Two rows decode and a step is in flight; an entry of two rows
    arrives: its first row is seated behind that step, its second row's
    admission fails on the host (``gen_crash``).  The step is committed
    before the sibling leaves (row membership changes only after a flush),
    the rows it held decode on token-identically, and so does the next
    request."""
    from paddlefleetx_tpu.utils import resilience

    sched = _hand_sched(server, True)
    eng = sched.engine
    events = _watch(sched)
    futs = _two_rows_in_flight(sched)
    assert eng.free_slots() >= 2
    del events[:]
    pair = sched.submit([PROMPTS[1], PROMPTS[2]], 30, deadline_s=120)
    resilience.reset_fault_state()
    monkeypatch.setenv("PFX_FAULT", "gen_crash:4")  # the pair's second row
    try:
        sched._iterate()
    finally:
        monkeypatch.delenv("PFX_FAULT")
        resilience.reset_fault_state()
    # the sibling went in behind the step; the failed row's turn found the
    # step still in flight and committed it; then the batch stepped on
    # (the fault fires in front of ``eng.admit``, so no second admit event)
    assert events == [("admit", True), ("flush", True), ("step", False)], events
    with pytest.raises(RuntimeError, match="gen_crash"):
        pair.result(timeout=10)
    assert eng.active_rows() == 2  # nothing of the entry is left on the engine
    assert _finish(sched, futs) == [
        server.generate_ids([PROMPTS[i]], max_dec_len=12)[0] for i in range(2)]
    eng.flush()  # the hand-driven run stops at the last future, a step of dead rows behind it
    assert not eng.active.any()
    assert all(r is None for r in eng.slots)
    assert eng.cache.stats()["kv_blocks_used"] == 0
    again = sched.submit([PROMPTS[1]], 6, deadline_s=120)
    assert _finish(sched, [again])[0] == server.generate_ids(
        [PROMPTS[1]], max_dec_len=6)[0]
    assert eng.gap_books.errors == 0


# ---------------------------------------------------------------------------
# two-process streamed drill: real serve.py + router.py CLIs
# ---------------------------------------------------------------------------


def _parse_sse(body: str):
    """SSE body -> ordered [(event, data_obj)] pairs."""
    out = []
    for frame in body.split("\n\n"):
        event, data = None, None
        for line in frame.split("\n"):
            if line.startswith("event: "):
                event = line[len("event: "):]
            elif line.startswith("data: "):
                data = json.loads(line[len("data: "):])
        if event is not None:
            out.append((event, data))
    return out


@pytest.mark.fault
@pytest.mark.slow  # two jax boots; gated by make test-decode-overlap
def test_streamed_generate_through_router_two_process(tmp_path):
    import urllib.request

    import yaml

    from test_disagg_drills import (
        _finish,
        _free_port,
        _get,
        _metrics,
        _spawn_replica,
        _spawn_router,
        _wait_eligible,
        _wait_healthy,
        SYS,
        TINY as DRILL_TINY,
    )

    cfg_path = tmp_path / "tiny_stream.yaml"
    cfg_path.write_text(yaml.safe_dump(DRILL_TINY))
    sport, rport = _free_port(), _free_port()
    replica = _spawn_replica(
        cfg_path, sport, "--scheduler", "continuous", "--cb-batch", "4",
        "--replica-id", "s0",
    )
    router = None
    try:
        _wait_healthy([(sport, replica)])
        router = _spawn_router(rport, "--replica",
                               f"http://127.0.0.1:{sport}")
        _wait_eligible(rport, 1, proc=router)

        req = urllib.request.Request(
            f"http://127.0.0.1:{rport}/generate?stream=1",
            data=json.dumps({
                "prompt_ids": SYS + [40, 41, 42], "max_tokens": 6,
                "deadline_s": 60,
            }).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as r:
            assert r.status == 200
            assert "text/event-stream" in r.headers.get("Content-Type", "")
            trace_id = r.headers.get("X-Trace-Id")
            # incremental arrival: the close-delimited body lands in
            # multiple reads because each flush leaves the replica (and
            # transits the router) the moment its step commits
            chunks = []
            while True:
                c = r.read1(65536)
                if not c:
                    break
                chunks.append(c)
        frames = _parse_sse(b"".join(chunks).decode())
        tokens = [d for e, d in frames if e == "token"]
        summaries = [d for e, d in frames if e == "summary"]
        assert not [d for e, d in frames if e == "error"], frames
        # >= 2 flushes, each a separate wire chunk end-to-end
        assert len(tokens) >= 2, frames
        assert len(chunks) >= 2, [len(c) for c in chunks]
        # per-row monotone token indices with no gaps
        seen = {}
        for d in tokens:
            assert d["index"] == seen.get(d["row"], 0), tokens
            seen[d["row"]] = d["index"] + len(d["tokens"])
        assert summaries, frames
        total = sum(len(d["tokens"]) for d in tokens)
        assert summaries[-1]["usage"]["tokens"] == total == sum(
            seen.values()
        )
        assert summaries[-1]["flushes"] == len(tokens)

        # the streamed leg still stitches: the router timeline carries
        # its own routing events AND the replica's remote spans (which
        # rode the stream's terminal summary frame, not a header)
        assert trace_id
        tl = _get(rport, f"/debug/trace?id={trace_id}")
        names = [e["name"] for e in tl["events"]]
        assert "route" in names and "routed" in names
        remote = [e for e in tl["events"] if e.get("proc")]
        assert remote, names
        assert {e["proc"]["replica_id"] for e in remote} == {"s0"}
        assert "decode_chunk" in {e["name"] for e in remote}

        # streamed accounting: TTFT observed at first flush and ITL
        # per-gap — the replica's /metrics carries both histograms
        m = _metrics(sport)
        itl_n = m.get("pfx_request_itl_seconds_count", {}).get(
            frozenset(), 0)
        assert itl_n == len(tokens) - 1, (itl_n, len(tokens))
        assert m.get("pfx_request_ttft_seconds_count", {}).get(
            frozenset(), 0) >= 1
        # and the fleet plumb: the router's healthz poll view carries
        # the replica's itl_p99_s field
        views = _get(rport, "/replicas")["replicas"]
        assert all("itl_p99_s" in v for v in views), views
    finally:
        out_r = _finish(router)
        out_s = _finish(replica)
        assert "Traceback" not in out_s, out_s[-3000:]
        assert "Traceback" not in out_r, out_r[-3000:]
