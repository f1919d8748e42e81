"""Elastic-control-plane units (`core/controller.py`): scale policy
validation, the controller's breach/depth/occupancy decisions with
hysteresis + cooldowns + min/max bounds, the bounded decision log and
its counter-replay contract, and the replica supervisor's crash-restart
backoff + flap-budget quarantine — all against stub cores / injected
clocks / tiny real subprocesses (no jax, no model): the multi-process
chaos drills live in tests/test_elastic_drills.py.
"""

import subprocess
import sys
import time

import pytest

from paddlefleetx_tpu.core.controller import (
    ElasticController,
    ManagedReplica,
    ReplicaSupervisor,
    ScalePolicy,
    replay_controller_log,
)
from paddlefleetx_tpu.utils.telemetry import Registry


class StubCore:
    """RouterCore stand-in: mutable replica views + call recording."""

    def __init__(self):
        self.views = []
        self.added = []
        self.drained = []
        self._next = 0

    def replica_views(self):
        return [dict(v) for v in self.views]

    def add_replica(self, url, role="monolith"):
        key = f"r{self._next}"
        self._next += 1
        self.added.append((key, url, role))
        return key

    def drain(self, key):
        self.drained.append(key)
        return {"replica": key}


def _view(key, *, state="serving", depth=0, in_flight=0, occupancy=0.0,
          breach=False, draining=False):
    return {
        "key": key, "role": "monolith", "state": state, "depth": depth,
        "in_flight": in_flight, "occupancy": occupancy,
        "slo_breach": breach, "draining": draining,
    }


class FakeProc:
    """Popen stand-in with a scriptable exit code."""

    def __init__(self):
        self.rc = None
        self.pid = 4242
        self.terminated = False

    def poll(self):
        return self.rc

    def terminate(self):
        self.terminated = True
        self.rc = 0

    def wait(self, timeout=None):
        if self.rc is None:
            raise subprocess.TimeoutExpired("fake", timeout)
        return self.rc

    def kill(self):
        self.rc = -9


def _supervisor(reg, **kw):
    kw.setdefault("base_port", 9500)
    kw.setdefault("max_replicas", 4)
    kw.setdefault("spawn_fn", lambda m: FakeProc())
    kw.setdefault("registry", reg)
    return ReplicaSupervisor(
        "python serve.py --port {port} --replica-id {replica_id}", **kw
    )


def _controller(core, sup, reg, **policy_kw):
    policy_kw.setdefault("min_replicas", 1)
    policy_kw.setdefault("max_replicas", 3)
    policy_kw.setdefault("up_cooldown_s", 5.0)
    policy_kw.setdefault("down_cooldown_s", 60.0)
    policy_kw.setdefault("idle_s", 30.0)
    return ElasticController(
        core, sup, ScalePolicy(**policy_kw), registry=reg
    )


# ---------------------------------------------------------------------------
# policy validation
# ---------------------------------------------------------------------------


def test_scale_policy_validates_loudly():
    ScalePolicy().validate()
    with pytest.raises(ValueError, match="min_replicas"):
        ScalePolicy(min_replicas=0).validate()
    with pytest.raises(ValueError, match="max_replicas"):
        ScalePolicy(min_replicas=3, max_replicas=2).validate()
    with pytest.raises(ValueError, match="hysteresis"):
        ScalePolicy(low_depth=5.0, high_depth=4.0).validate()
    with pytest.raises(ValueError, match="hysteresis"):
        ScalePolicy(low_occupancy=0.95, high_occupancy=0.9).validate()
    with pytest.raises(ValueError, match="idle_s"):
        ScalePolicy(idle_s=0).validate()


def test_supervisor_template_requires_port_placeholder():
    with pytest.raises(ValueError, match="{port}"):
        ReplicaSupervisor("python serve.py", base_port=9500,
                          max_replicas=2, registry=Registry())


# ---------------------------------------------------------------------------
# scale-up: breach-driven fast path, watermarks, cooldown, max bound
# ---------------------------------------------------------------------------


def test_breach_drives_scale_up_and_registers_replica():
    reg, core = Registry(), StubCore()
    sup = _supervisor(reg)
    ctl = _controller(core, sup, reg)
    ctl._register(sup.ensure(ctl.target, now=0.0))
    assert [k for k, _, _ in core.added] == ["r0"]
    core.views = [_view("r0", breach=True)]
    row = ctl.tick(now=10.0)
    assert row["action"] == "scale_up" and "breach" in row["reason"]
    assert ctl.target == 2
    # the new slot was spawned AND registered with the router core
    assert len(core.added) == 2
    assert sup.slots[1].desired and sup.slots[1].key == core.added[1][0]


def test_depth_and_occupancy_watermarks_drive_scale_up():
    reg, core = Registry(), StubCore()
    ctl = _controller(core, _supervisor(reg), reg, high_depth=4.0)
    core.views = [_view("r0", depth=3, in_flight=2)]  # avg 5 > 4
    assert ctl.tick(now=10.0)["action"] == "scale_up"
    reg2, core2 = Registry(), StubCore()
    ctl2 = _controller(core2, _supervisor(reg2), reg2)
    core2.views = [_view("r0", occupancy=0.95)]
    row = ctl2.tick(now=10.0)
    assert row["action"] == "scale_up" and "occupancy" in row["reason"]


def test_up_cooldown_and_warming_replicas_bound_scale_rate():
    reg, core = Registry(), StubCore()
    ctl = _controller(core, _supervisor(reg), reg, up_cooldown_s=5.0)
    core.views = [_view("r0", breach=True)]
    assert ctl.tick(now=10.0)["action"] == "scale_up"
    # still breaching, but the spawned replica is warming: hold
    core.views = [_view("r0", breach=True), _view("r1", state="booting")]
    row = ctl.tick(now=10.5)
    assert row["action"] == "hold" and "warming" in row["reason"]
    # warming replica landed but the up-cooldown still gates
    core.views = [_view("r0", breach=True), _view("r1")]
    row = ctl.tick(now=12.0)
    assert row["action"] == "hold" and "cooldown" in row["reason"]
    # past the cooldown: the breach scales again
    assert ctl.tick(now=20.0)["action"] == "scale_up"
    assert ctl.target == 3


def test_max_replicas_bounds_scale_up_loudly():
    reg, core = Registry(), StubCore()
    ctl = _controller(core, _supervisor(reg), reg, max_replicas=1)
    core.views = [_view("r0", breach=True)]
    row = ctl.tick(now=10.0)
    assert row["action"] == "hold" and "max_replicas" in row["reason"]
    assert ctl.target == 1
    assert reg.value("pfx_controller_breach") == 1.0


# ---------------------------------------------------------------------------
# scale-down: idle dwell + cooldown hysteresis, min bound, remote drain
# ---------------------------------------------------------------------------


def test_idle_dwell_and_cooldown_gate_scale_down():
    reg, core = Registry(), StubCore()
    sup = _supervisor(reg)
    ctl = _controller(core, sup, reg, idle_s=30.0, down_cooldown_s=60.0)
    ctl._register(sup.ensure(2, now=0.0))
    ctl.target = 2
    core.views = [_view("r0"), _view("r1")]  # idle fleet
    assert ctl.tick(now=10.0)["action"] == "hold"   # dwell starts
    assert ctl.tick(now=35.0)["action"] == "hold"   # dwell met, but the
    # last scale action was... never: -inf, so cooldown passes; dwell is
    # measured from the FIRST idle tick (10.0): 35-10=25 < 30
    row = ctl.tick(now=41.0)  # 31s of sustained idle
    assert row["action"] == "scale_down"
    assert ctl.target == 1
    # the drain went through the core (remote authenticated transport)
    # and retired the HIGHEST slot
    assert core.drained == [sup.slots[1].key]
    assert not sup.slots[1].desired
    # min bound: still idle, but the floor holds
    core.views = [_view("r0")]
    for t in (120.0, 200.0, 300.0):
        assert ctl.tick(now=t)["action"] == "hold"
    assert ctl.target == 1


def test_scale_up_with_no_spawnable_slot_holds_and_keeps_books_honest():
    """Pressure at a fleet whose remaining slots are all quarantined
    must NOT move the target or the scale_ups counter — a scale-up that
    spawns nothing would make the decision log 'replay exactly' while
    recording spawns that never happened."""
    reg, core = Registry(), StubCore()
    # the supervisor shares the policy's ceiling (tools/router.py wires
    # both from --max-replicas)
    sup = _supervisor(reg, max_replicas=2)
    ctl = _controller(core, sup, reg, max_replicas=2, up_cooldown_s=1.0)
    ctl._register(sup.ensure(1, now=0.0))
    sup._slot(1).quarantined = True  # the only headroom slot is dead
    core.views = [_view("r0", breach=True)]
    for t in (10.0, 20.0, 30.0):
        row = ctl.tick(now=t)
        assert row["action"] == "hold", row
        assert "no spawnable slot" in row["reason"], row
    assert ctl.target == 1
    assert reg.value("pfx_controller_scale_ups_total") == 0.0
    replay = replay_controller_log(list(ctl.decision_log))
    assert replay["scale_ups"] == 0 and replay["ticks"] == 3


def test_total_outage_is_not_idle_and_never_scales_down():
    """Zero serving replicas (all crashed / restart-pending) reads as
    depth 0 and occupancy 0 — but it is an OUTAGE, not idleness: the
    controller must hold, never retire capacity mid-outage."""
    reg, core = Registry(), StubCore()
    sup = _supervisor(reg)
    ctl = _controller(core, sup, reg, idle_s=5.0, down_cooldown_s=5.0,
                      max_replicas=3)
    ctl._register(sup.ensure(2, now=0.0))
    ctl.target = 2
    core.views = [_view("r0", state="gone"), _view("r1", state="gone")]
    for t in (10.0, 20.0, 40.0, 80.0):  # far past every dwell/cooldown
        row = ctl.tick(now=t)
        assert row["action"] == "hold", row
    assert ctl.target == 2 and core.drained == []
    assert all(m.desired for m in sup.slots.values())


def test_traffic_blip_resets_idle_dwell():
    reg, core = Registry(), StubCore()
    sup = _supervisor(reg)
    ctl = _controller(core, sup, reg, idle_s=30.0)
    ctl._register(sup.ensure(2, now=0.0))
    ctl.target = 2
    core.views = [_view("r0"), _view("r1")]
    ctl.tick(now=10.0)
    # a depth blip above low_depth (but under high) resets the dwell
    core.views = [_view("r0", depth=2), _view("r1")]
    assert ctl.tick(now=25.0)["action"] == "hold"
    core.views = [_view("r0"), _view("r1")]
    assert ctl.tick(now=41.0)["action"] == "hold"  # dwell restarted at 41
    assert ctl.tick(now=72.0)["action"] == "scale_down"


# ---------------------------------------------------------------------------
# decision log: bounded, replayable to exact counter agreement
# ---------------------------------------------------------------------------


def test_decision_log_replays_to_exact_counter_agreement():
    reg, core = Registry(), StubCore()
    sup = _supervisor(reg)
    ctl = _controller(core, sup, reg, up_cooldown_s=1.0, idle_s=5.0,
                      down_cooldown_s=5.0)
    ctl._register(sup.ensure(ctl.target, now=0.0))
    t = 10.0
    core.views = [_view("r0", breach=True)]
    ctl.tick(now=t)                                   # scale_up
    core.views = [_view("r0", breach=True), _view("r1")]
    ctl.tick(now=t + 2)                               # scale_up (cooldown ok)
    core.views = [_view("r0"), _view("r1"), _view("r2")]
    for dt in (3, 4, 5, 6, 7, 8, 9):
        ctl.tick(now=t + dt)                          # holds, then downs
    replay = replay_controller_log(list(ctl.decision_log))
    assert replay["ticks"] == len(ctl.decision_log) == 9
    assert replay["scale_ups"] == 2
    assert replay["scale_downs"] >= 1
    # THE agreement contract: the untruncated log reproduces the
    # pfx_controller_* counters exactly
    assert reg.value("pfx_controller_ticks_total") == replay["ticks"]
    assert reg.value("pfx_controller_scale_ups_total") == replay["scale_ups"]
    assert (reg.value("pfx_controller_scale_downs_total")
            == replay["scale_downs"])
    assert reg.value("pfx_controller_target_replicas") == ctl.target


def test_decision_log_is_bounded(monkeypatch):
    monkeypatch.setenv("PFX_CONTROLLER_LOG_CAP", "8")
    reg, core = Registry(), StubCore()
    ctl = _controller(core, _supervisor(reg), reg)
    core.views = [_view("r0")]
    for i in range(20):
        ctl.tick(now=float(i))
    assert len(ctl.decision_log) == 8
    assert ctl.decision_log[-1]["tick"] == 20  # newest kept, oldest evicted


# ---------------------------------------------------------------------------
# supervisor: spawn, crash-restart backoff, flap quarantine, warm boot
# ---------------------------------------------------------------------------


def test_supervisor_restarts_crash_with_backoff():
    reg = Registry()
    spawned = []

    def spawn(m):
        p = FakeProc()
        spawned.append((m.slot, p))
        return p

    sup = _supervisor(reg, spawn_fn=spawn, backoff_base_s=2.0,
                      flap_budget=5)
    sup.ensure(1, now=0.0)
    assert len(spawned) == 1
    spawned[0][1].rc = 1  # crash
    sup.poll(now=10.0)
    assert len(spawned) == 1  # backoff pending, not yet respawned
    sup.poll(now=11.0)
    assert len(spawned) == 1  # 10 + 2.0 backoff not reached
    sup.poll(now=12.5)
    assert len(spawned) == 2  # respawned
    assert sup.slots[0].restarts == 1
    assert reg.value("pfx_replica_restarts_total", replica="m0") == 1.0


def test_supervisor_quarantines_crash_loop_within_flap_budget():
    reg = Registry()
    procs = []

    def spawn(m):
        p = FakeProc()
        p.rc = 23  # dies instantly: the crash-loop case
        procs.append(p)
        return p

    sup = _supervisor(reg, spawn_fn=spawn, backoff_base_s=0.01,
                      flap_budget=3, flap_window_s=60.0)
    sup.ensure(1, now=0.0)
    t = 1.0
    for _ in range(40):
        sup.poll(now=t)
        t += 1.0
        if sup.slots[0].quarantined:
            break
    m = sup.slots[0]
    assert m.quarantined, "crash-looper was never quarantined"
    # quarantine fired WITHIN the flap budget: exactly budget restarts,
    # then no more spawns ever
    assert m.restarts == 3 and len(procs) == 4
    assert reg.value("pfx_replica_quarantines_total", replica="m0") == 1.0
    for _ in range(5):
        sup.poll(now=t)
        t += 1.0
    assert len(procs) == 4  # quarantined means QUARANTINED
    # ensure() skips the quarantined slot and desires the next one
    started = sup.ensure(1, now=t)
    assert [m2.slot for m2 in started] == [1]


def test_supervisor_clean_exit_while_desired_respawns_without_flap_spend():
    """An out-of-band drain of a supervised replica (manual POST
    /admin/drain) exits 0 while the slot is still desired: the fleet
    self-heals by respawning, but a deploy is not a crash — no crash
    warning, no flap-budget spend, never a quarantine."""
    reg = Registry()
    procs = []

    def spawn(m):
        p = FakeProc()
        procs.append(p)
        return p

    sup = _supervisor(reg, spawn_fn=spawn, backoff_base_s=0.5,
                      flap_budget=3, flap_window_s=1e9)
    sup.ensure(1, now=0.0)
    t = 1.0
    for _ in range(6):  # twice the flap budget of clean exits
        procs[-1].rc = 0  # drained out from under the supervisor
        sup.poll(now=t)           # reap: schedules a flap-exempt respawn
        sup.poll(now=t + 0.6)     # past the backoff: respawn
        t += 1.0
    m = sup.slots[0]
    assert len(procs) == 7 and m.restarts == 6
    assert not m.quarantined, "clean exits spent the flap budget"
    assert m.restart_times == []  # the flap window never saw them
    assert reg.value("pfx_replica_restarts_total", replica="m0") == 6.0


def test_supervisor_expected_exit_is_not_restarted():
    reg = Registry()
    spawned = []

    def spawn(m):
        p = FakeProc()
        spawned.append(p)
        return p

    sup = _supervisor(reg, spawn_fn=spawn)
    sup.ensure(1, now=0.0)
    sup.drain_slot(0)
    spawned[0].rc = 0  # the drained replica exits 0
    for t in (1.0, 2.0, 3.0):
        sup.poll(now=t)
    assert len(spawned) == 1
    assert sup.slots[0].restarts == 0


def test_supervisor_warm_boot_hands_cache_dir_through_the_environment(tmp_path):
    sup = ReplicaSupervisor(
        "python serve.py --port {port} --replica-id {replica_id}",
        base_port=9600, max_replicas=2,
        compile_cache_dir=str(tmp_path / "cache"),
        spawn_fn=lambda m: FakeProc(), registry=Registry(),
    )
    sup.ensure(2, now=0.0)
    # jax reads the variable itself; serve.py has no flag for it
    assert sup.env["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path / "cache")
    for slot, m in sup.slots.items():
        assert "--compile-cache-dir" not in m.cmd
        assert f"--port 960{slot}" in " ".join(m.cmd)
        assert f"--replica-id m{slot}" in " ".join(m.cmd)


def test_supervisor_real_subprocess_lifecycle():
    """One real child end-to-end: spawn, SIGKILL -> crash seen ->
    restart, stop_all tears down cleanly."""
    reg = Registry()
    sup = ReplicaSupervisor(
        f"{sys.executable} -c 'import time; time.sleep({{port}})'",
        base_port=300, max_replicas=1, backoff_base_s=0.05,
        registry=reg,
    )
    try:
        sup.ensure(1)
        m = sup.slots[0]
        assert m.proc.poll() is None
        m.proc.kill()
        m.proc.wait(timeout=10)
        deadline = time.time() + 10
        while m.restarts == 0 and time.time() < deadline:
            sup.poll()
            time.sleep(0.02)
        assert m.restarts == 1 and m.proc is not None
        assert m.proc.poll() is None  # the replacement is alive
    finally:
        sup.stop_all(timeout=10)
    assert all(m.proc is None for m in sup.slots.values())


def test_managed_replica_view_shape():
    m = ManagedReplica(slot=0, port=9500, url="http://127.0.0.1:9500",
                       cmd=["x"])
    v = m.view()
    assert v["slot"] == 0 and v["pid"] is None and not v["quarantined"]


# ---------------------------------------------------------------------------
# role-aware pool supervision (docs/serving.md "Disaggregated operations")
# ---------------------------------------------------------------------------


def _pool_view(key, role, available_blocks=None, **kw):
    v = _view(key, **kw)
    v["role"] = role
    v["available_blocks"] = available_blocks
    return v


def _pool_controller(core, sup, reg, role, **policy_kw):
    policy_kw.setdefault("min_replicas", 1)
    policy_kw.setdefault("max_replicas", 3)
    policy_kw.setdefault("up_cooldown_s", 5.0)
    policy_kw.setdefault("down_cooldown_s", 60.0)
    policy_kw.setdefault("idle_s", 30.0)
    return ElasticController(
        core, sup, ScalePolicy(**policy_kw), role=role, registry=reg
    )


def test_scale_policy_validates_low_blocks():
    with pytest.raises(ValueError, match="low_blocks"):
        ScalePolicy(low_blocks=-1).validate()
    ScalePolicy(low_blocks=8, use_depth=False).validate()


def test_scale_policy_rejects_all_signals_off():
    """With every load signal disabled, 'idle' degenerates to 'no SLO
    breach' and a slammed pool would be drained mid-load — a
    self-contradictory policy is a config error, loudly."""
    with pytest.raises(ValueError, match="load signal"):
        ScalePolicy(use_depth=False, use_occupancy=False,
                    low_blocks=0).validate()
    # one signal is enough on its own
    ScalePolicy(use_depth=False, use_occupancy=False,
                low_blocks=4).validate()
    ScalePolicy(use_depth=True, use_occupancy=False).validate()


def test_decode_pool_scales_on_available_blocks_not_depth():
    """The decode pool watches arena signals: a deep queue alone never
    scales it (use_depth=False — decode queues drain at step
    boundaries), but a serving replica whose admissible blocks fall to
    the watermark is pressure."""
    reg, core = Registry(), StubCore()
    sup = _supervisor(reg, slot_prefix="d", role="decode")
    ctl = _pool_controller(core, sup, reg, "decode",
                           use_depth=False, low_blocks=4)
    # deep queue, healthy arena: hold (depth is not a decode signal)
    core.views = [_pool_view("r0", "decode", available_blocks=64,
                             depth=50)]
    assert ctl.tick(now=10.0)["action"] == "hold"
    # arena pressure: the WORST serving replica is at the watermark
    core.views = [
        _pool_view("r0", "decode", available_blocks=64),
        _pool_view("r1", "decode", available_blocks=3),
    ]
    row = ctl.tick(now=20.0)
    assert row["action"] == "scale_up", row
    assert "available blocks" in row["reason"], row
    assert row["min_blocks"] == 3 and row["pool"] == "decode"
    # occupancy stays live as a decode signal
    reg2, core2 = Registry(), StubCore()
    ctl2 = _pool_controller(core2, _supervisor(reg2), reg2, "decode",
                            use_depth=False)
    core2.views = [_pool_view("r0", "decode", occupancy=0.95)]
    assert ctl2.tick(now=10.0)["action"] == "scale_up"


def test_decode_pool_block_pressure_blocks_idle_scale_down():
    """An arena hovering just above the watermark is not 'idle': the
    scale-down needs comfortable headroom (> 2x low_blocks)."""
    reg, core = Registry(), StubCore()
    sup = _supervisor(reg, slot_prefix="d", role="decode")
    ctl = _pool_controller(core, sup, reg, "decode",
                           use_depth=False, low_blocks=4,
                           idle_s=5.0, down_cooldown_s=5.0)
    ctl._register(sup.ensure(2, now=0.0))
    ctl.target = 2
    core.views = [
        _pool_view("r0", "decode", available_blocks=7),
        _pool_view("r1", "decode", available_blocks=64),
    ]
    for t in (10.0, 20.0, 40.0):
        assert ctl.tick(now=t)["action"] == "hold"
    # headroom restored: the idle dwell may finally run down
    core.views = [
        _pool_view("r0", "decode", available_blocks=60),
        _pool_view("r1", "decode", available_blocks=64),
    ]
    ctl.tick(now=50.0)
    assert ctl.tick(now=56.0)["action"] == "scale_down"


def test_prefill_pool_ignores_occupancy_scales_on_depth_and_breach():
    """The prefill pool watches queue depth + TTFT burn; occupancy is
    meaningless there (no decode arena) and must not trip it."""
    reg, core = Registry(), StubCore()
    sup = _supervisor(reg, slot_prefix="p", role="prefill")
    ctl = _pool_controller(core, sup, reg, "prefill",
                           use_occupancy=False, high_depth=4.0)
    core.views = [_pool_view("r0", "prefill", occupancy=1.0)]
    assert ctl.tick(now=10.0)["action"] == "hold"
    core.views = [_pool_view("r0", "prefill", depth=3, in_flight=2)]
    row = ctl.tick(now=20.0)
    assert row["action"] == "scale_up" and "depth" in row["reason"]
    reg2, core2 = Registry(), StubCore()
    ctl2 = _pool_controller(core2, _supervisor(reg2), reg2, "prefill",
                            use_occupancy=False)
    core2.views = [_pool_view("r0", "prefill", breach=True)]
    assert ctl2.tick(now=10.0)["action"] == "scale_up"


def test_prefill_pool_depth_can_exclude_router_inflight():
    """count_in_flight=False (the direct-transport prefill policy):
    router-side in-flight spans the whole prefill->decode relay there,
    so only replica-REPORTED queue depth may trip the scale-up — five
    slow decodes in relay are not prefill pressure."""
    reg, core = Registry(), StubCore()
    sup = _supervisor(reg, slot_prefix="p", role="prefill")
    ctl = _pool_controller(core, sup, reg, "prefill",
                           use_occupancy=False, high_depth=4.0,
                           count_in_flight=False)
    core.views = [_pool_view("r0", "prefill", depth=0, in_flight=5)]
    assert ctl.tick(now=10.0)["action"] == "hold"
    core.views = [_pool_view("r0", "prefill", depth=5, in_flight=0)]
    assert ctl.tick(now=20.0)["action"] == "scale_up"


def test_pool_controllers_keep_labeled_counters_and_per_pool_replay():
    """Two pool controllers over one registry: each pool's rows replay
    into ITS pool-labeled pfx_controller_* counters exactly (the PR 11
    replay contract, per-pool edition), and the monolith spelling stays
    unlabeled."""
    reg, core = Registry(), StubCore()
    pre = _pool_controller(
        core, _supervisor(reg, slot_prefix="p", role="prefill"), reg,
        "prefill", use_occupancy=False, up_cooldown_s=1.0,
    )
    dec = _pool_controller(
        core, _supervisor(reg, base_port=9700, slot_prefix="d",
                          role="decode"), reg,
        "decode", use_depth=False, low_blocks=4, up_cooldown_s=1.0,
    )
    core.views = [
        _pool_view("r0", "prefill", depth=9),
        _pool_view("r1", "decode", available_blocks=2),
    ]
    pre.tick(now=10.0)   # prefill scale_up (depth)
    dec.tick(now=10.0)   # decode scale_up (blocks)
    core.views = [
        _pool_view("r0", "prefill", depth=9),
        _pool_view("r2", "prefill", state="booting"),
        _pool_view("r1", "decode", available_blocks=50),
        _pool_view("r3", "decode", state="booting"),
    ]
    pre.tick(now=11.0)   # hold: warming
    dec.tick(now=11.0)   # hold
    rows = list(pre.decision_log) + list(dec.decision_log)
    for pool, ctl in (("prefill", pre), ("decode", dec)):
        replay = replay_controller_log(rows, pool=pool)
        assert replay["ticks"] == 2
        assert replay["scale_ups"] == 1
        assert reg.value("pfx_controller_ticks_total",
                         pool=pool) == replay["ticks"]
        assert reg.value("pfx_controller_scale_ups_total",
                         pool=pool) == replay["scale_ups"]
        assert reg.value("pfx_controller_target_replicas",
                         pool=pool) == ctl.target
    # the monolith spelling stays UNLABELED (PR 11 drill contract)
    assert reg.value("pfx_controller_ticks_total") == 0.0


def test_supervisor_slot_prefix_names_pool_replicas():
    reg = Registry()
    sup = _supervisor(reg, slot_prefix="d", role="decode")
    sup.ensure(2, now=0.0)
    assert [m.rid for m in sup._snapshot()] == ["d0", "d1"]
    assert sup.views()[0]["replica_id"] == "d0"
