"""Control-plane survivability chaos drills through the real CLIs
(`make test-ha`, docs/serving.md "Control-plane recovery"): SIGKILL the
SUPERVISING tools/router.py itself and prove its death is a non-event.

  router-kill   SIGKILL the router mid-two-tenant-flood -> restart on
                the same ports + PFX_FLIGHT_DIR: every live replica is
                RE-ADOPTED into its slot (zero respawns, zero flap
                budget, pids unchanged), the flooding tenant's quota
                bucket restores from the journal (no free burst window
                — its first post-restart over-quota request still
                429s), post-recovery greedy output is token-identical,
                recovery-time-to-first-200 is printed, and
                replay_fleet_state over the journal agrees with the
                recovered /replicas + controller views
  journal-loss  the journal is DELETED between router incarnations:
                --router-url self-registration heartbeats alone rebuild
                the registry, and a drained replica's deregister
                goodbye walks it to gone immediately instead of
                waiting out --eject-after failed polls

Follows tests/test_elastic_drills.py conventions: `fault`-marked,
subprocess-driven, tiny synthetic GPT, persistent XLA compile cache
shared through the environment (tests/conftest.py)."""

import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest
import yaml

pytestmark = pytest.mark.fault

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.environ.get(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), ".jax_cache"),
)

TINY = {
    "Global": {"global_batch_size": 8, "seed": 11},
    "Engine": {"mix_precision": {"enable": False},
               "save_load": {"save_steps": 0}},
    "Model": {
        "module": "GPTModule",
        "vocab_size": 96,
        "hidden_size": 32,
        "num_layers": 2,
        "num_attention_heads": 4,
        "max_position_embeddings": 64,
        "dtype": "float32",
    },
    "Optimizer": {"name": "FusedAdamW",
                  "lr": {"name": "Constant", "learning_rate": 1e-3}},
    "Generation": {"max_dec_len": 8, "decode_strategy": "greedy_search",
                   "pad_to_multiple": 8, "eos_token_id": 95,
                   "pad_token_id": 0},
}

# flood refills one token every 20s: the seconds-long death window can
# never refill its burst, so a restored bucket MUST still reject
TENANTS = {
    "default": {"weight": 1.0},
    "tenants": {
        "flood": {"weight": 1, "rps": 0.05, "burst": 2},
        "gold": {"weight": 4},
    },
}


def _free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _env(extra=None):
    env = dict(os.environ)
    env["PFX_PLATFORM"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env.pop("PFX_FAULT", None)
    env.pop("PFX_ADMIN_TOKEN", None)
    env.update(extra or {})
    return env


def _req(port, path, data=None, headers=None, timeout=60):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=None if data is None else json.dumps(data).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.load(r)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def _metrics(port, timeout=10):
    from test_telemetry import parse_prometheus

    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=timeout
    ) as r:
        metrics, _ = parse_prometheus(r.read().decode())
    return metrics


def _finish(proc, timeout=30):
    if proc is None:
        return ""
    if proc.poll() is None:
        proc.terminate()
        try:
            proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)
    return proc.stdout.read() if proc.stdout else ""


def _wait(predicate, timeout, what):
    end = time.time() + timeout
    last = None
    while time.time() < end:
        try:
            last = predicate()
            if last:
                return last
        except Exception as e:  # noqa: BLE001 — listener still booting
            last = e
        time.sleep(0.3)
    raise AssertionError(f"timeout waiting for {what}: {last!r}")


def _serve_cmd(cfg_path, *extra):
    return " ".join([
        sys.executable, os.path.join(REPO, "tools", "serve.py"),
        "-c", str(cfg_path), "--port", "{port}",
        "--replica-id", "{replica_id}",
        "--warmup-buckets", "4", "--warmup-batches", "1",
        "--deadline", "60", *extra,
    ])


def _pid_alive(pid):
    try:
        os.kill(pid, 0)
        return True
    except OSError:
        return False


# ---------------------------------------------------------------------------
# THE acceptance drill: SIGKILL the supervising router mid-flood
# ---------------------------------------------------------------------------


def _spawn_router(rport, bport, cfg_path, tmp_path, flight_dir, ten_path):
    """A supervised 2-replica router on FIXED ports (the restart must
    find the same slots) with the fleet journal in ``flight_dir``."""
    return subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tools", "router.py"),
         "--port", str(rport), "--poll-interval", "0.2",
         "--supervise", "--replica-cmd", _serve_cmd(cfg_path),
         "--base-port", str(bport),
         "--compile-cache-dir", CACHE_DIR,
         "--replica-log-dir", str(tmp_path / "replica-logs"),
         "--control-interval", "0.5",
         "--min-replicas", "2", "--max-replicas", "2",
         "--tenants", str(ten_path)],
        env=_env({"PFX_FLIGHT_DIR": str(flight_dir)}), cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )


def test_sigkill_router_readopts_fleet_and_restores_quotas(tmp_path):
    """THE control-plane survivability acceptance drill: SIGKILL the
    supervising router mid-two-tenant-flood, restart it on the same
    ports + flight dir, and prove router death is a non-event —
    every live replica re-adopted (zero respawns, zero flap-budget
    spend, pids unchanged), tenant 429 quotas resuming from restored
    buckets, greedy output token-identical, and the journal replaying
    to exact agreement with the recovered views."""
    from paddlefleetx_tpu.core.router import (
        read_fleet_journal,
        replay_fleet_state,
    )

    cfg_path = tmp_path / "tiny.yaml"
    cfg_path.write_text(yaml.safe_dump(TINY))
    ten_path = tmp_path / "tenants.json"
    ten_path.write_text(json.dumps(TENANTS))
    flight_dir = tmp_path / "router-artifacts"
    journal_path = flight_dir / "fleet_state.jsonl"
    rport, bport = _free_port(), _free_port()
    gold = {"X-Tenant": "gold"}
    fl = {"X-Tenant": "flood"}
    body = {"prompt_ids": [1, 2, 3], "max_tokens": 8, "deadline_s": 60}

    router = _spawn_router(rport, bport, cfg_path, tmp_path, flight_dir,
                           ten_path)
    router2 = None
    stop = threading.Event()
    flood_codes, lock = [], threading.Lock()
    try:
        _wait(lambda: _req(rport, "/healthz")[1].get("eligible", 0) >= 2,
              600, "two supervised replicas serving")
        code, ref = _req(rport, "/generate", data=body, headers=gold,
                         timeout=90)
        assert code == 200, ref
        views = {v["key"]: v for v in _req(rport, "/replicas")[1]["replicas"]}
        pids_before = {k: v["pid"] for k, v in views.items()}
        assert len(pids_before) == 2
        assert all(isinstance(p, int) for p in pids_before.values())

        # the two-tenant flood: gold trickles, flood burns its burst
        # and keeps hammering into 429s (the mid-429-storm state the
        # restart must NOT hand a fresh burst allowance)
        def flood_loop():
            while not stop.is_set():
                try:
                    c, _r = _req(rport, "/generate", data=body,
                                 headers=fl, timeout=90)
                except Exception:  # noqa: BLE001 — router is dead/rebooting
                    c = None
                with lock:
                    flood_codes.append((time.time(), c))
                time.sleep(0.1)

        flooder = threading.Thread(target=flood_loop)
        flooder.start()
        _wait(lambda: any(c == 429 for _, c in flood_codes),
              90, "flood tenant over quota (429)")
        _req(rport, "/generate", data=body, headers=gold, timeout=90)

        # the drained flood bucket must be IN the journal before the
        # kill (the poll thread journals tenants at most once a second)
        def bucket_journaled():
            recs, _ = read_fleet_journal(str(journal_path))
            buckets = replay_fleet_state(recs)["tenants"]["buckets"]
            b = buckets.get("flood")
            return b is not None and b["tokens"] < 1.0
        _wait(bucket_journaled, 30, "drained flood bucket journaled")

        # ---- SIGKILL the control plane mid-flood ----
        t_kill = time.time()
        router.kill()
        router.wait(timeout=30)
        # the fleet outlives its router: both replicas still running
        assert all(_pid_alive(p) for p in pids_before.values())

        router2 = _spawn_router(rport, bport, cfg_path, tmp_path,
                                flight_dir, ten_path)

        def first_200():
            c, _r = _req(rport, "/generate", data=body, headers=gold,
                         timeout=90)
            return c == 200
        _wait(first_200, 120, "first post-restart 200")
        print(f"recovery-time-to-first-200: "
              f"{time.time() - t_kill:.2f}s", flush=True)

        # restored buckets: the flooding tenant's first post-restart
        # over-quota request still 429s — no free burst window (rps
        # 0.05 cannot refill the burst across a seconds-long death)
        code, rej = _req(rport, "/generate", data=body, headers=fl)
        assert code == 429, (code, rej)
        stop.set()
        flooder.join(timeout=120)
        assert not flooder.is_alive()
        with lock:
            post = [c for t, c in flood_codes if t > t_kill and c]
        assert 200 not in post, post  # the 429 storm RESUMED, no hole

        # re-adoption: same keys, same pids, serving — zero respawns
        def readopted():
            vs = {v["key"]: v for v in
                  _req(rport, "/replicas")[1]["replicas"]}
            return vs if (
                set(vs) == set(pids_before)
                and all(v["state"] == "serving" for v in vs.values())
            ) else None
        vs = _wait(readopted, 120, "both replicas re-adopted + serving")
        assert {k: v["pid"] for k, v in vs.items()} == pids_before

        m = _metrics(rport)
        assert m["pfx_router_recoveries_total"][frozenset()] == 1.0
        for rid in ("m0", "m1"):
            assert m["pfx_router_adopted_replicas_total"][
                frozenset({("replica", rid)})
            ] == 1.0
        # zero respawns, zero flap-budget spend
        assert "pfx_replica_restarts_total" not in m
        assert "pfx_replica_quarantines_total" not in m
        assert m["pfx_router_journal_records"][frozenset()] >= 1.0

        # post-recovery greedy output is token-identical
        code, resp = _req(rport, "/generate", data=body, headers=gold,
                          timeout=90)
        assert code == 200
        assert resp["completion_ids"] == ref["completion_ids"]

        # replay_fleet_state over the journal == the recovered views
        # (quiesce-retry: scale records land every control tick, so
        # agreement is gated on the REPLICA record count holding still)
        def replica_records(recs):
            return [r for r in recs
                    if r["kind"] in ("replica", "snapshot")]
        for _ in range(10):
            recs, note = read_fleet_journal(str(journal_path))
            assert note is None
            live = {v["key"]: v for v in
                    _req(rport, "/replicas")[1]["replicas"]}
            _, hz = _req(rport, "/healthz")
            recs2, _ = read_fleet_journal(str(journal_path))
            if len(replica_records(recs)) != len(replica_records(recs2)):
                continue  # a transition landed mid-read; retry
            st = replay_fleet_state(recs)
            assert set(st["replicas"]) == set(live)
            for key, v in live.items():
                fold = st["replicas"][key]
                assert fold["state"] == v["state"], key
                assert fold["url"] == v["url"], key
            ctl = st["controller"]["monolith"]
            assert ctl["target"] == hz["controller"]["target"]
            assert st["tenants"]["buckets"]["flood"]["tokens"] < 1.0
            break
        else:
            raise AssertionError("registry never quiesced between reads")

        router2.send_signal(signal.SIGTERM)
        assert router2.wait(timeout=120) == 0
    finally:
        stop.set()
        log1 = _finish(router)
        log2 = _finish(router2)
    assert "re-adopted 2 live replica(s)" in log2, log2[-3000:]
    assert "restored" in log2 and "tenant bucket" in log2, log2[-3000:]
    assert "Traceback" not in log1, log1[-3000:]
    assert "Traceback" not in log2, log2[-3000:]


# ---------------------------------------------------------------------------
# journal deleted -> self-registration heartbeats rebuild the registry
# ---------------------------------------------------------------------------


@pytest.mark.slow  # ~2 replica boots (~60s warm); tier-1 keeps the
# SIGKILL-router acceptance drill above.  Replacement coverage: the
# /admin/register contract (idempotent register, identity refresh,
# deregister-walks-gone, stale-goodbye rejection) stays tier-1 via the
# test_fleet_journal.py register_replica units; still in make test-ha /
# test-all.
def test_journal_deleted_heartbeats_rebuild_registry(tmp_path):
    """THE journal-loss drill: two --router-url replicas heartbeat into
    a static router.  The router dies AND its journal is deleted; the
    restarted router rediscovers the fleet from the heartbeats alone —
    and a drained replica's deregister goodbye walks it to gone
    immediately, not after --eject-after failed polls."""
    cfg_path = tmp_path / "tiny.yaml"
    cfg_path.write_text(yaml.safe_dump(TINY))
    flight_dir = tmp_path / "router-artifacts"
    rport = _free_port()
    pa, pb = _free_port(), _free_port()
    body = {"prompt_ids": [1, 2, 3], "max_tokens": 8, "deadline_s": 60}

    def spawn_replica(port):
        return subprocess.Popen(
            [sys.executable, os.path.join(REPO, "tools", "serve.py"),
             "-c", str(cfg_path), "--port", str(port),
             "--replica-id", f"hb-{port}",
             "--warmup-buckets", "4", "--warmup-batches", "1",
             "--deadline", "60",
             "--router-url", f"http://127.0.0.1:{rport}"],
            env=_env({"PFX_REGISTER_INTERVAL_S": "0.5"}), cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )

    def spawn_router():
        # replica A is configured statically; B exists ONLY through its
        # /admin/register heartbeats.  --eject-after 100 @ 0.2s polls =
        # a 20s failed-poll eject window, so a fast gone proves the
        # deregister path, not the poller
        return subprocess.Popen(
            [sys.executable, os.path.join(REPO, "tools", "router.py"),
             "--port", str(rport), "--poll-interval", "0.2",
             "--replica", f"http://127.0.0.1:{pa}",
             "--eject-after", "100"],
            env=_env({"PFX_FLIGHT_DIR": str(flight_dir)}), cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )

    ra, rb = spawn_replica(pa), spawn_replica(pb)
    router = spawn_router()
    router2 = None
    try:
        _wait(lambda: _req(rport, "/healthz")[1].get("eligible", 0) >= 2,
              600, "static A + heartbeat-registered B both serving")
        m = _metrics(rport)
        assert m["pfx_replica_registrations_total"][
            frozenset({("outcome", "register")})
        ] >= 1.0
        code, ref = _req(rport, "/generate", data=body, timeout=90)
        assert code == 200

        # ---- kill the router AND delete its journal ----
        router.kill()
        router.wait(timeout=30)
        shutil.rmtree(flight_dir)
        router2 = spawn_router()
        # the heartbeats alone rebuild the registry: B re-appears
        # within a couple of 0.5s heartbeat intervals
        _wait(lambda: _req(rport, "/healthz")[1].get("eligible", 0) >= 2,
              120, "registry rebuilt from heartbeats after journal loss")
        m = _metrics(rport)
        assert "pfx_router_recoveries_total" not in m  # nothing replayed
        code, resp = _req(rport, "/generate", data=body, timeout=90)
        assert code == 200
        assert resp["completion_ids"] == ref["completion_ids"]

        # ---- drained replica deregisters on exit (no eject wait) ----
        code, _ = _req(pb, "/admin/drain", data={})
        assert code == 200
        assert rb.wait(timeout=60) == 0
        t0 = time.time()

        def b_gone():
            vs = _req(rport, "/replicas")[1]["replicas"]
            b = next(v for v in vs if v["url"].endswith(str(pb)))
            return b["state"] == "gone"
        _wait(b_gone, 15, "deregistered replica walked to gone")
        # far inside the 20s failed-poll eject window: the goodbye did it
        assert time.time() - t0 < 10.0
        m = _metrics(rport)
        assert m["pfx_replica_registrations_total"][
            frozenset({("outcome", "deregister")})
        ] >= 1.0

        router2.send_signal(signal.SIGTERM)
        assert router2.wait(timeout=60) == 0
        code, _ = _req(pa, "/admin/drain", data={})
        assert code == 200
        assert ra.wait(timeout=60) == 0
    finally:
        loga = _finish(ra)
        logb = _finish(rb)
        log1 = _finish(router)
        log2 = _finish(router2)
    assert "deregistered from router" in logb, logb[-3000:]
    for log in (loga, logb, log1, log2):
        assert "Traceback" not in log, log[-3000:]
