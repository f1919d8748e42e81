"""Fault-tolerant disaggregated serving fabric drills through the real
CLIs (`make test-disagg`): direct prefill->decode transfer, handoff
failover, and role-aware pool supervision (docs/serving.md
"Disaggregated operations").

  direct      the placement-ticket topology: handoff payload bytes flow
              prefill -> decode DIRECTLY (router byte counters stay
              flat while pfx_handoff_bytes_total on the replicas
              accounts the transfer), output token-identical to the
              proxy transport, prefix reuse live on the prefill replica.
  failover    PFX_FAULT=handoff_drop (direct send dropped -> proxy
              fallback) and PFX_FAULT=adopt_crash (decode replica dies
              at adoption -> bounded re-prefill through the surviving
              pair): every request exactly one honest outcome, greedy
              output token-identical across every leg.
  supervision SIGKILL a prefill replica AND a decode replica holding
              adopted rows under flood: zero hangs, honest 200/503
              accounting, the role-aware pool supervisor respawns both
              corpses, per-pool decision logs replay into the
              pool-labeled pfx_controller_* counters exactly.

Follows tests/test_router_drills.py conventions: `fault`-marked,
subprocess-driven, tiny synthetic GPT, persistent XLA compile cache
shared through the environment (tests/conftest.py)."""

import json
import os
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

# a fleet-shared "system prompt" two requests share: 34 tokens = 2 full
# KV blocks (the library's 16 slots) + a 2-token overlap in the tail block, so
# the second request exercises shared-block mapping AND the COW copy on
# the prefill replica
SYS = list(range(1, 35))


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


def _post(port, body, timeout=90, path="/generate"):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.load(r)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def _get(port, path, timeout=10):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout
    ) as r:
        return json.load(r)


def _metrics(port, timeout=10):
    from test_telemetry import parse_prometheus

    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=timeout
    ) as r:
        metrics, _ = parse_prometheus(r.read().decode())
    return metrics


def _lab(m, name, **labels):
    """One labeled series out of a parsed /metrics dump (0.0 absent)."""
    want = frozenset((k, str(v)) for k, v in labels.items())
    return m.get(name, {}).get(want, 0.0)


def _spawn_replica(cfg_path, port, *extra, env_extra=None):
    return subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tools", "serve.py"),
         "-c", str(cfg_path), "--port", str(port),
         "--queue-depth", "32", "--deadline", "60",
         "--warmup-buckets", "4", "--warmup-batches", "1", *extra],
        env=_env(env_extra), cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True,
    )


def _spawn_router(port, *args):
    return subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tools", "router.py"),
         "--port", str(port), "--poll-interval", "0.2",
         "--eject-after", "3", *args],
        env=_env(), cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True,
    )


def _wait_healthy(procs_ports, timeout=300):
    end = time.time() + timeout
    pending = dict(procs_ports)
    while pending and time.time() < end:
        for port, proc in list(pending.items()):
            if proc.poll() is not None:
                raise AssertionError(
                    f"replica on {port} died at boot: "
                    f"{proc.stdout.read()[-3000:]}"
                )
            try:
                if _get(port, "/healthz", timeout=5).get("ok"):
                    del pending[port]
            except Exception:
                pass
        time.sleep(0.3)
    assert not pending, f"never healthy: {sorted(pending)}"


def _wait_eligible(router_port, n, timeout=300, proc=None):
    end = time.time() + timeout
    h = {}
    while time.time() < end:
        if proc is not None and proc.poll() is not None:
            raise AssertionError(
                f"router died: {proc.stdout.read()[-3000:]}"
            )
        try:
            h = _get(router_port, "/healthz")
        except Exception:
            h = {}
        if h.get("eligible", 0) >= n:
            return h
        time.sleep(0.2)
    raise AssertionError(f"router never saw {n} eligible replicas: {h}")


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


def _serve_cmd(cfg_path, *extra):
    return " ".join([
        sys.executable, os.path.join(REPO, "tools", "serve.py"),
        "-c", str(cfg_path), "--port", "{port}",
        "--replica-id", "{replica_id}",
        "--warmup-buckets", "4", "--warmup-batches", "1",
        "--deadline", "60", *extra,
    ])


# ---------------------------------------------------------------------------
# direct transfer: bytes bypass the router; transport parity; prefix
# reuse live on the prefill replica
# ---------------------------------------------------------------------------


@pytest.mark.slow  # ~15s warm; tier-1 budget funding for the PR 15
# fleet-observability drill.  Replacement coverage: the byte-bypass
# (router pfx_router_handoff_bytes_total flat + replica-side direct
# bytes accounted), export/adopt counter accounting, the 3-process
# direct-topology boot, and repeat-request token-identical determinism
# all stay tier-1-drilled by tests/test_fleet_obs_drills.py (same
# replicas, same transport, plus the stitched-trace + federation
# agreement asserts); the direct-vs-proxy transport PARITY and prefill
# prefix reuse remain covered here in make test-disagg / test-all.
def test_direct_transfer_bypasses_router_and_matches_proxy(tmp_path):
    """THE direct-transfer acceptance drill: under ``--handoff direct``
    the payload provably does not transit the router (its byte counter
    stays flat while the replicas' pfx_handoff_bytes_total accounts the
    transfer), greedy output is token-identical to the proxy transport
    on the SAME replicas, and ``--prefix-cache-blocks`` on the prefill
    replica computes a shared system prefix once, not once per
    request."""
    cfg_path = tmp_path / "tiny_direct.yaml"
    cfg_path.write_text(yaml.safe_dump(TINY))
    pre_p, dec_p = _free_port(), _free_port()
    pre = _spawn_replica(cfg_path, pre_p, "--role", "prefill",
                         "--replica-id", "pre0",
                         "--prefix-cache-blocks", "16")
    dec = _spawn_replica(cfg_path, dec_p, "--role", "decode",
                         "--cb-batch", "4", "--replica-id", "dec0")
    ra_port, rb_port = _free_port(), _free_port()
    router_a = router_b = None
    try:
        _wait_healthy([(pre_p, pre), (dec_p, dec)])
        # /healthz satellite: the decode replica reports its admissible
        # blocks (the decode-pool scale + routing signal)
        assert _get(dec_p, "/healthz")["available_blocks"] > 0
        assert "available_blocks" not in _get(pre_p, "/healthz")

        router_a = _spawn_router(
            ra_port,
            "--prefill", f"http://127.0.0.1:{pre_p}",
            "--decode", f"http://127.0.0.1:{dec_p}",
            "--handoff", "direct",
        )
        _wait_eligible(ra_port, 2, proc=router_a)

        body1 = {"prompt_ids": SYS + [40, 41, 42], "max_tokens": 6,
                 "deadline_s": 60}
        body2 = {"prompt_ids": SYS + [50, 51], "max_tokens": 6,
                 "deadline_s": 60}
        c1, direct1 = _post(ra_port, body1)
        c2, direct2 = _post(ra_port, body2)
        c3, repeat1 = _post(ra_port, body1)
        assert (c1, c2, c3) == (200, 200, 200), (direct1, direct2, repeat1)
        assert repeat1["completion_ids"] == direct1["completion_ids"]

        # THE byte-bypass assert: the router never carried the payload
        m = _metrics(ra_port)
        assert m["pfx_router_handoff_bytes_total"][frozenset()] == 0.0
        assert m["pfx_router_handoff_seconds_count"][frozenset()] == 3.0
        pre_m = _metrics(pre_p)
        assert _lab(pre_m, "pfx_handoff_direct_total", outcome="ok") == 3.0
        assert _lab(pre_m, "pfx_handoff_bytes_total",
                    transport="direct") > 0
        dec_m = _metrics(dec_p)
        assert _lab(dec_m, "pfx_handoff_bytes_total",
                    transport="direct") > 0
        assert _lab(dec_m, "pfx_handoff_bytes_total",
                    transport="proxy") == 0.0
        assert dec_m["pfx_handoff_adopts_total"][frozenset()] == 3.0
        # prefix reuse on the prefill pool: request 1 published, 2 and
        # 3 hit the shared system prefix (34 tokens each)
        assert pre_m["pfx_prefix_misses_total"][frozenset()] == 1.0
        assert pre_m["pfx_prefix_hits_total"][frozenset()] == 2.0
        assert pre_m["pfx_prefix_hit_tokens_total"][frozenset()] >= 68.0
        assert pre_m["pfx_handoff_exports_total"][frozenset()] == 3.0

        # swap the transport on the SAME replicas: proxy parity
        router_a.send_signal(signal.SIGTERM)
        assert router_a.wait(timeout=60) == 0
        router_b = _spawn_router(
            rb_port,
            "--prefill", f"http://127.0.0.1:{pre_p}",
            "--decode", f"http://127.0.0.1:{dec_p}",
            "--handoff", "proxy",
        )
        _wait_eligible(rb_port, 2, proc=router_b)
        c4, proxied = _post(rb_port, body1)
        assert c4 == 200
        # token-identical across transports (f32 greedy)
        assert proxied["completion_ids"] == direct1["completion_ids"]
        mb = _metrics(rb_port)
        assert mb["pfx_router_handoff_bytes_total"][frozenset()] > 0
        assert _lab(_metrics(dec_p), "pfx_handoff_bytes_total",
                    transport="proxy") > 0

        # arena accounting closes on the decode replica
        assert _metrics(dec_p)["pfx_kv_blocks_used"][frozenset()] == 0.0
        for proc in (router_b, pre, dec):
            proc.send_signal(signal.SIGTERM)
        for proc in (router_b, pre, dec):
            assert proc.wait(timeout=60) == 0
    finally:
        logs = [_finish(p) for p in (pre, dec)]
        logs += [_finish(router_a), _finish(router_b)]
    for log in logs:
        assert "Traceback" not in log, log[-3000:]


# ---------------------------------------------------------------------------
# failure legs: handoff_drop -> proxy fallback; adopt_crash -> bounded
# re-prefill failover through the surviving pair
# ---------------------------------------------------------------------------


@pytest.mark.slow  # ~18s; tier-1 budget funding for the shard_map-port
# tests.  Replacement coverage: the failover ladder (stateless prefill
# retry, dirty-ticket avoidance, bounded re-prefill, never-replay-after-
# bytes) stays tier-1 via the test_router unit suite, and the direct
# transport's byte-bypass + parity stays tier-1-drilled by
# test_direct_transfer_bypasses_router_and_matches_proxy; still in
# make test-disagg / test-all.
def test_handoff_drop_and_adopt_crash_failover_token_identical(tmp_path):
    """Every failure leg of the direct topology, deterministically:

    - PFX_FAULT=handoff_drop:1:2 on the prefill replica drops BOTH
      attempts of the first direct send -> the payload degrades to the
      router proxy leg (router byte counter moves, outcome=fallback);
    - PFX_FAULT=adopt_crash:2 on decode replica d1 hard-exits it at its
      second adoption while the transport waits -> the router's bounded
      re-prefill failover answers through the surviving pair;
    - every request gets exactly one honest 200, token-identical
      throughout; the corpse is ejected and the survivor serves on."""
    cfg_path = tmp_path / "tiny_failover.yaml"
    cfg_path.write_text(yaml.safe_dump(TINY))
    pre_p, d1_p, d2_p = (_free_port() for _ in range(3))
    pre = _spawn_replica(cfg_path, pre_p, "--role", "prefill",
                         "--replica-id", "pre0",
                         env_extra={"PFX_FAULT": "handoff_drop:1:2"})
    d1 = _spawn_replica(cfg_path, d1_p, "--role", "decode",
                        "--cb-batch", "4", "--replica-id", "d1",
                        env_extra={"PFX_FAULT": "adopt_crash:2"})
    d2 = _spawn_replica(cfg_path, d2_p, "--role", "decode",
                        "--cb-batch", "4", "--replica-id", "d2")
    rport = _free_port()
    router = None
    try:
        _wait_healthy([(pre_p, pre), (d1_p, d1), (d2_p, d2)])
        router = _spawn_router(
            rport,
            "--prefill", f"http://127.0.0.1:{pre_p}",
            "--decode", f"http://127.0.0.1:{d1_p}",
            "--decode", f"http://127.0.0.1:{d2_p}",
            "--handoff", "direct",
        )
        _wait_eligible(rport, 3, proc=router)

        body = {"prompt_ids": SYS + [40, 41, 42], "max_tokens": 6,
                "deadline_s": 60}
        codes, outs = [], []
        for _ in range(12):
            c, resp = _post(rport, body)
            codes.append(c)
            outs.append(resp.get("completion_ids"))
            if d1.poll() is not None and len(codes) >= 3:
                break  # the fatal adoption landed (and failed over)
        # zero hangs, every request exactly one honest outcome — and
        # the failovers made every one of them a 200
        assert all(c == 200 for c in codes), codes
        assert all(o == outs[0] for o in outs), outs

        # d1 died at its second adoption (os._exit(29)) and the router
        # ejected it; the survivor keeps serving
        assert d1.wait(timeout=30) == 29
        end = time.time() + 20
        while time.time() < end:
            states = _get(rport, "/healthz")["replicas"]
            if states["r1"] == "gone":
                break
            time.sleep(0.3)
        assert _get(rport, "/healthz")["replicas"]["r1"] == "gone"
        assert _get(rport, "/healthz")["replicas"]["r2"] == "serving"

        m = _metrics(rport)
        # the dropped direct send degraded to the proxy leg: the router
        # carried at least one payload
        assert m["pfx_router_handoff_bytes_total"][frozenset()] > 0
        # the decode death ran the bounded re-prefill failover
        assert _lab(m, "pfx_handoff_failovers_total", leg="decode") >= 1.0
        pre_m = _metrics(pre_p)
        assert _lab(pre_m, "pfx_handoff_direct_total",
                    outcome="fallback") >= 1.0
        assert _lab(pre_m, "pfx_handoff_direct_total", outcome="ok") >= 1.0

        # post-failover steady state: token-identical on the survivors
        c, resp = _post(rport, body)
        assert c == 200 and resp["completion_ids"] == outs[0]
        # arena accounting closes on the survivor (no orphaned refs)
        assert _metrics(d2_p)["pfx_kv_blocks_used"][frozenset()] == 0.0

        for proc in (router, pre, d2):
            proc.send_signal(signal.SIGTERM)
        for proc in (router, pre, d2):
            assert proc.wait(timeout=60) == 0
    finally:
        logs = [_finish(p) for p in (pre, d1, d2)]
        logs += [_finish(router)]
    for log in logs:
        assert "Traceback" not in log, log[-3000:]


# ---------------------------------------------------------------------------
# role-aware pool supervision: SIGKILL both corpses under flood
# ---------------------------------------------------------------------------


def _pool_replay_agrees(rport):
    """Per-pool replay contract: each pool's decision rows fold into
    ITS pool-labeled pfx_controller_* counters exactly (retry until no
    tick lands between the two reads)."""
    from paddlefleetx_tpu.core.controller import replay_controller_log

    for _ in range(10):
        dbg = _get(rport, "/debug/controller")
        m = _metrics(rport)
        dbg2 = _get(rport, "/debug/controller")
        if any(
            len(dbg["pools"][p]["decisions"])
            != len(dbg2["pools"][p]["decisions"])
            for p in dbg["pools"]
        ):
            continue
        assert set(dbg["pools"]) == {"prefill", "decode"}
        for pool, view in dbg["pools"].items():
            replay = replay_controller_log(view["decisions"], pool=pool)
            assert replay["ticks"] > 0
            assert _lab(m, "pfx_controller_ticks_total",
                        pool=pool) == replay["ticks"]
            assert _lab(m, "pfx_controller_scale_ups_total",
                        pool=pool) == replay["scale_ups"]
            assert _lab(m, "pfx_controller_scale_downs_total",
                        pool=pool) == replay["scale_downs"]
        return dbg
    raise AssertionError("pool controllers never quiesced between reads")


@pytest.mark.slow  # ~4 supervised jax boots + respawns; covered by
# make test-disagg / test-all (the failure-leg contracts stay tier-1
# via the direct/failover drills above + the router/controller units)
def test_pool_supervisor_restarts_both_corpses_under_flood(tmp_path):
    """THE chaos acceptance drill: a supervised disaggregated fleet
    (2 prefill + 2 decode) under flood, SIGKILL one prefill replica
    AND one decode replica holding adopted rows — zero hangs, every
    request exactly one of 200/503, the role-aware pool supervisor
    respawns BOTH corpses (router walks them gone -> warm -> serving
    on new pids), post-failover output token-identical, per-pool
    decision logs replay into the pool-labeled counters exactly."""
    cfg_path = tmp_path / "tiny_pools.yaml"
    cfg_path.write_text(yaml.safe_dump(TINY))
    rport = _free_port()
    router = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tools", "router.py"),
         "--port", str(rport), "--poll-interval", "0.2",
         "--eject-after", "3",
         "--supervise",
         "--prefill-cmd", _serve_cmd(cfg_path, "--role", "prefill"),
         "--decode-cmd", _serve_cmd(cfg_path, "--role", "decode",
                                    "--cb-batch", "4"),
         "--min-prefill", "2", "--max-prefill", "2",
         "--min-decode", "2", "--max-decode", "2",
         "--prefill-base-port", str(_free_port()),
         "--decode-base-port", str(_free_port()),
         "--restart-backoff", "0.2",
         "--control-interval", "0.3",
         "--compile-cache-dir", CACHE_DIR,
         "--replica-log-dir", str(tmp_path / "replica-logs")],
        env=_env(), cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True,
    )
    try:
        h = _wait_eligible(rport, 4, timeout=300, proc=router)
        assert h["mode"] == "disaggregated", h
        assert set(h["controller"]["pools"]) == {"prefill", "decode"}

        body = {"prompt_ids": SYS + [40, 41, 42], "max_tokens": 6,
                "deadline_s": 60}
        code, ref = _post(rport, body)
        assert code == 200, (code, ref)

        views = _get(rport, "/replicas")["replicas"]
        pre_victim = next(v for v in views if v["role"] == "prefill")
        dec_victim = next(v for v in views if v["role"] == "decode")

        stop = threading.Event()
        results, lock = [], threading.Lock()

        def flood():
            while not stop.is_set():
                c, _r = _post(rport, body, timeout=90)
                with lock:
                    results.append(c)

        threads = [threading.Thread(target=flood) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(1.0)  # adopted rows live on the decode pool
        os.kill(pre_victim["pid"], signal.SIGKILL)
        os.kill(dec_victim["pid"], signal.SIGKILL)
        time.sleep(3.0)  # traffic through the failover window
        stop.set()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive(), "hung connection through the kills"
        with lock:
            codes = list(results)
        # zero hangs, honest accounting: exactly one of 200/503 each
        assert codes and all(c in (200, 503) for c in codes), codes
        assert codes.count(200) >= 1, codes

        # the pool supervisor respawns both corpses; the router walks
        # them gone -> warm -> serving on NEW pids
        def _respawned():
            vs = _get(rport, "/replicas")["replicas"]
            by_key = {v["key"]: v for v in vs}
            a = by_key[pre_victim["key"]]
            b = by_key[dec_victim["key"]]
            return (a["state"] == "serving" and a["pid"] != pre_victim["pid"]
                    and b["state"] == "serving"
                    and b["pid"] != dec_victim["pid"])

        end = time.time() + 180
        while time.time() < end and not _respawned():
            time.sleep(0.5)
        assert _respawned(), _get(rport, "/replicas")

        m = _metrics(rport)
        restarts = {
            dict(k)["replica"]: v
            for k, v in m.get("pfx_replica_restarts_total", {}).items()
        }
        assert any(r.startswith("p") for r in restarts), restarts
        assert any(r.startswith("d") for r in restarts), restarts

        # post-failover: token-identical through the healed fleet
        for _ in range(3):
            code, resp = _post(rport, body)
            assert code == 200
            assert resp["completion_ids"] == ref["completion_ids"]

        _pool_replay_agrees(rport)

        # graceful teardown: the router drains its children, exit 0
        router.send_signal(signal.SIGTERM)
        assert router.wait(timeout=120) == 0
    finally:
        rlog = _finish(router)
    assert "Traceback" not in rlog, rlog[-3000:]
