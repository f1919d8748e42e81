"""Unified telemetry units (utils/telemetry.py) + the engine's step-record
observability contract: registry semantics, Prometheus exposition (strict
line parser, shared with the serve drills), spans, the GPT FLOPs estimator
vs a hand-computed 6·N·T, peak-FLOPs resolution, and the flight recorder."""

import json
import os
import re
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddlefleetx_tpu.utils import telemetry as T

# ---------------------------------------------------------------------------
# strict Prometheus text-exposition parser (format 0.0.4).  Reused by
# tests/test_serve_drills.py against a live /metrics endpoint: every line
# must be a well-formed HELP/TYPE comment or sample, TYPE must precede its
# samples, histogram buckets must be cumulative and end at +Inf with
# matching _sum/_count.
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?P<labels>\{[^{}]*\})?"
    r" (?P<value>[-+]?(?:[0-9]*\.?[0-9]+(?:[eE][-+]?[0-9]+)?|Inf|NaN|\+Inf))$"
)
_LABEL_RE = re.compile(r'^(?P<k>[a-zA-Z_][a-zA-Z0-9_]*)="(?P<v>(?:[^"\\]|\\.)*)"$')


def parse_prometheus(text):
    """Strictly parse exposition text -> {name: {labels_frozenset: value}}.
    Raises AssertionError on any malformed line or structural violation."""
    metrics = {}
    types = {}
    for lineno, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("#"):
            m = re.match(r"^# (HELP|TYPE) ([a-zA-Z_:][a-zA-Z0-9_:]*) (.+)$", line)
            assert m, f"line {lineno}: malformed comment: {line!r}"
            if m.group(1) == "TYPE":
                assert m.group(3) in ("counter", "gauge", "histogram", "summary"), line
                types[m.group(2)] = m.group(3)
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"line {lineno}: malformed sample: {line!r}"
        name = m.group("name")
        labels = {}
        raw = (m.group("labels") or "{}")[1:-1]
        if raw:
            for part in raw.split(","):
                lm = _LABEL_RE.match(part)
                assert lm, f"line {lineno}: malformed label {part!r} in {line!r}"
                labels[lm.group("k")] = lm.group("v")
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        assert base in types or name in types, (
            f"line {lineno}: sample {name!r} before any TYPE declaration"
        )
        value = float(m.group("value").replace("+Inf", "inf").replace("Inf", "inf"))
        metrics.setdefault(name, {})[frozenset(labels.items())] = value
    # histogram structure: cumulative buckets ending at +Inf == _count
    for name, kind in types.items():
        if kind != "histogram":
            continue
        buckets = metrics.get(f"{name}_bucket", {})
        series = {}
        for labels, v in buckets.items():
            le = dict(labels)["le"]
            rest = frozenset(kv for kv in labels if kv[0] != "le")
            series.setdefault(rest, []).append((le, v))
        for rest, pairs in series.items():
            def le_key(le):
                return float("inf") if le == "+Inf" else float(le)
            pairs.sort(key=lambda p: le_key(p[0]))
            vals = [v for _, v in pairs]
            assert vals == sorted(vals), f"{name}: non-cumulative buckets {pairs}"
            assert pairs[-1][0] == "+Inf", f"{name}: missing +Inf bucket"
            count = metrics.get(f"{name}_count", {}).get(rest)
            assert count == pairs[-1][1], f"{name}: +Inf != _count"
            assert metrics.get(f"{name}_sum", {}).get(rest) is not None, name
    return metrics, types


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    r = T.Registry()
    c = r.counter("pfx_serving_requests_total")
    c.inc()
    c.inc(2)
    assert c.get() == 3
    g = r.gauge("pfx_train_loss")
    g.set(2.5)
    g.add(-0.5)
    assert g.get() == 2.0
    h = r.histogram("pfx_request_latency_seconds")
    for v in (0.002, 0.02, 0.2, 2.0):
        h.observe(v)
    st = h.state()
    assert st["count"] == 4 and abs(st["sum"] - 2.222) < 1e-9
    assert st["p50"] in (0.02, 0.2)
    assert h.percentile(0.99) == 2.0


def test_undeclared_metric_name_raises():
    r = T.Registry()
    with pytest.raises(ValueError, match="not declared"):
        r.counter("pfx_bogus_total")  # noqa — deliberately undeclared
    with pytest.raises(ValueError, match="not declared"):
        # declared name, wrong kind: a counter cannot be re-typed
        r.gauge("pfx_serving_requests_total")


def test_labels_make_distinct_children():
    r = T.Registry()
    r.counter("pfx_http_responses_total", code="200").inc(3)
    r.counter("pfx_http_responses_total", code="503").inc()
    assert r.value("pfx_http_responses_total", code="200") == 3
    assert r.value("pfx_http_responses_total", code="503") == 1


def test_render_parses_strictly_and_matches_snapshot():
    r = T.Registry()
    r.counter("pfx_http_responses_total", code="200").inc(7)
    r.gauge("pfx_queue_depth").set(2)
    h = r.histogram("pfx_request_ttft_seconds")
    h.observe(0.03)
    h.observe(1.5)
    snap = r.snapshot()
    metrics, types = parse_prometheus(r.render_prometheus(snap))
    assert types["pfx_http_responses_total"] == "counter"
    assert types["pfx_queue_depth"] == "gauge"
    assert types["pfx_request_ttft_seconds"] == "histogram"
    assert metrics["pfx_http_responses_total"][frozenset({("code", "200")})] == 7
    assert metrics["pfx_queue_depth"][frozenset()] == 2
    assert metrics["pfx_request_ttft_seconds_count"][frozenset()] == 2


def test_stats_view_dict_interface_and_collection():
    r = T.Registry()
    sv = T.StatsView(
        {"requests": "pfx_serving_requests_total", "last_error": None},
        init={"last_error": ""},
        registry=r,
    )
    sv["requests"] += 2
    sv["last_error"] = "boom"
    sv["warmup_s"] = {"8": 0.5}  # late, non-exported key
    assert sv["requests"] == 2 and dict(sv)["last_error"] == "boom"
    assert {**sv}["warmup_s"] == {"8": 0.5}
    assert r.value("pfx_serving_requests_total") == 2
    # registry holds the view WEAKLY: a dead instance leaves the snapshot
    del sv
    import gc

    gc.collect()
    assert r.value("pfx_serving_requests_total") == 0


def test_stats_view_instances_sum_in_snapshot():
    r = T.Registry()
    a = T.StatsView({"requests": "pfx_serving_requests_total"}, registry=r)
    b = T.StatsView({"requests": "pfx_serving_requests_total"}, registry=r)
    a["requests"] += 1
    b["requests"] += 4
    # per-instance views keep absolute counts; the registry reports the
    # process-wide sum
    assert a["requests"] == 1 and b["requests"] == 4
    assert r.value("pfx_serving_requests_total") == 5


def test_span_phases_and_event():
    sp = T.Span("request", t0=100.0)
    sp.mark("admission", t=100.1)
    sp.mark("queue_wait", t=100.5)
    sp.mark("decode", t=102.5)
    ph = sp.phases()
    assert list(ph) == ["admission", "queue_wait", "decode"]
    np.testing.assert_allclose(
        [ph["admission"], ph["queue_wait"], ph["decode"]], [0.1, 0.4, 2.0]
    )
    ev = sp.event(code=200)
    assert ev["event"] == "span" and ev["span"] == "request"
    assert abs(ev["total_s"] - 2.5) < 1e-6 and ev["code"] == 200
    # injected out-of-order stamps sort into place
    sp2 = T.Span("x", t0=10.0)
    sp2.mark("late", t=12.0)
    sp2.mark("early", t=11.0)
    assert list(sp2.phases()) == ["early", "late"]


# ---------------------------------------------------------------------------
# MFU accounting
# ---------------------------------------------------------------------------


def test_gpt_flops_estimator_matches_hand_computed_6nt():
    """The acceptance anchor: 6·N·T against an independently hand-computed
    N for a tiny shape (vocab=10, h=4, L=1, ffn=16).

      embed 10*4=40; qkv 3*4*4+3*4=60; attn_out 4*4+4=20;
      mlp_up 4*16+16=80; mlp_down 16*4+4=68; 2 LN 4*4=16; final LN 8
      N = 40 + (60+20+80+68+16) + 8 = 292
    """
    n = T.gpt_param_count(vocab_size=10, hidden_size=4, num_layers=1)
    assert n == 292
    per_tok = T.model_flops_per_token(
        vocab_size=10, hidden_size=4, num_layers=1
    )
    T_tokens = 50
    assert per_tok * T_tokens == 6 * 292 * 50
    # forward-only basis (decode benches): 2·N
    assert T.model_flops_per_token(
        vocab_size=10, hidden_size=4, num_layers=1, backward=False
    ) == 2 * 292


def test_flops_estimator_reads_config_objects_and_declines_non_gpt():
    from paddlefleetx_tpu.models.gpt.config import GPTConfig

    cfg = GPTConfig(vocab_size=96, hidden_size=32, num_layers=2,
                    num_attention_heads=4)
    per_tok = T.model_flops_per_token(cfg)
    assert per_tok == 6 * T.gpt_param_count(
        vocab_size=96, hidden_size=32, num_layers=2,
        ffn_hidden_size=cfg.ffn_hidden_size,
    )

    class NotGPT:
        pass

    assert T.model_flops_per_token(NotGPT()) is None


def test_peak_flops_env_override_and_table(monkeypatch):
    monkeypatch.setenv("PFX_PEAK_FLOPS", "123e12")
    assert T.peak_flops(device_kind="anything") == 123e12
    monkeypatch.setenv("PFX_PEAK_FLOPS", "not-a-number")
    with pytest.raises(ValueError, match="PFX_PEAK_FLOPS"):
        T.peak_flops()
    monkeypatch.delenv("PFX_PEAK_FLOPS")
    assert T.peak_flops(device_kind="TPU v5e") == 197e12
    assert T.peak_flops(device_kind="TPU v4") == 275e12
    assert T.peak_flops(device_kind="cpu") == 1e12  # nominal, documented
    assert T.peak_flops(device_kind="weird-npu") is None
    assert T.peak_flops(device_kind="weird-npu", default=5e12) == 5e12


def test_mfu_math():
    # 1000 tok/s * 1e6 FLOPs/tok = 1e9 FLOP/s over 2 chips of 1e12 peak
    assert T.mfu(1000.0, 1e6, 2, peak=1e12) == pytest.approx(5e-4)
    assert T.mfu(1000.0, 1e6, 2, peak=0) is None


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_flight_recorder_ring_and_dump(tmp_path, monkeypatch):
    monkeypatch.delenv("PFX_FLIGHT_RECORDER", raising=False)
    fr = T.FlightRecorder(capacity=3)
    for i in range(5):
        fr.record({"event": "step", "step": i})
    evs = fr.events()
    assert [e["step"] for e in evs] == [2, 3, 4]  # bounded: oldest dropped
    assert [e["seq"] for e in evs] == [3, 4, 5]
    path = fr.dump(path=str(tmp_path / "fr.jsonl"), reason="unit")
    lines = [json.loads(x) for x in open(path)]
    assert lines[0]["event"] == "flight_recorder_dump"
    assert lines[0]["reason"] == "unit" and lines[0]["events"] == 3
    assert [e["step"] for e in lines[1:]] == [2, 3, 4]


def test_flight_recorder_env_path_and_dump_never_raises(tmp_path, monkeypatch):
    fr = T.FlightRecorder(capacity=2)
    fr.record({"event": "x"})
    monkeypatch.setenv("PFX_FLIGHT_RECORDER", str(tmp_path / "sub" / "fr.jsonl"))
    # the operator's env path wins even over an explicit caller path
    path = fr.dump(path=str(tmp_path / "elsewhere.jsonl"), reason="env")
    assert path == str(tmp_path / "sub" / "fr.jsonl") and os.path.exists(path)
    assert not os.path.exists(tmp_path / "elsewhere.jsonl")
    # unwritable target: logged, returns None, never raises (crash path)
    monkeypatch.setenv("PFX_FLIGHT_RECORDER", "/proc/nope/fr.jsonl")
    assert fr.dump(reason="bad") is None


def test_flight_recorder_excepthook_dumps(tmp_path, monkeypatch):
    monkeypatch.setenv("PFX_FLIGHT_RECORDER", str(tmp_path / "crash.jsonl"))
    fr = T.FlightRecorder(capacity=8)
    fr.record({"event": "step", "step": 7})
    seen = []
    monkeypatch.setattr(sys, "excepthook", lambda *a: seen.append(a))
    fr.install_excepthook()
    try:
        raise RuntimeError("boom")
    except RuntimeError:
        sys.excepthook(*sys.exc_info())
    assert seen, "prior hook must still run"
    lines = [json.loads(x) for x in open(tmp_path / "crash.jsonl")]
    assert "uncaught RuntimeError" in lines[0]["reason"]
    assert any(e.get("event") == "crash" and "boom" in e.get("error", "")
               for e in lines)
    assert any(e.get("event") == "step" and e.get("step") == 7 for e in lines)


# ---------------------------------------------------------------------------
# SLO burn rates
# ---------------------------------------------------------------------------


def test_slo_tracker_disabled_by_default_and_loud_on_bad_config():
    t = T.SLOTracker()
    assert not t.enabled
    t.observe_request(ttft_s=1.0, ok=False)  # no-op when disabled
    assert t.evaluate()["enabled"] is False and t.collect() == []
    with pytest.raises(ValueError, match=">= 0"):
        T.SLOTracker(ttft_p99_s=-1)
    with pytest.raises(ValueError, match="positive"):
        T.SLOTracker(ttft_p99_s=1, windows_s=(0,))


def test_slo_ttft_burn_rate_breach_and_time_recovery():
    """p99-TTFT objective: a window where every request blows the
    objective burns 100x the budget (bad_frac 1.0 / allowed 0.01) and
    breaches on BOTH windows; once the events age out of the windows the
    burn returns to 0 and the breach clears — no manual reset."""
    t = T.SLOTracker(ttft_p99_s=0.5, windows_s=(5.0, 30.0))
    for i in range(10):
        t.observe_request(ttft_s=2.0, ok=True, t=100.0 + i * 0.1)
    ev = t.evaluate(now=101.0)
    assert ev["objectives"] == {"ttft_p99": 0.5}
    assert ev["burn"]["ttft_p99"] == {"5s": 100.0, "30s": 100.0}
    assert ev["breach"] and "ttft_p99" in ev["reason"]
    assert ev["ttft_p99_s"] == 2.0
    # recovery: the bad window ages out
    ev2 = t.evaluate(now=200.0)
    assert ev2["burn"]["ttft_p99"] == {"5s": 0.0, "30s": 0.0}
    assert not ev2["breach"] and ev2["reason"] is None


def test_slo_failed_requests_count_as_ttft_violations():
    """A request that never delivered a first token (shed 503 / 500) is
    a TTFT violation, NOT a missing sample — a fully wedged server
    where every request fails must breach the TTFT objective, not
    report zero burn (the worst-TTFT-invisible failure mode)."""
    t = T.SLOTracker(ttft_p99_s=0.5, windows_s=(5.0, 30.0))
    for i in range(10):
        t.observe_request(ok=False, t=100.0 + i * 0.1)  # no ttft at all
    ev = t.evaluate(now=101.0)
    assert ev["burn"]["ttft_p99"] == {"5s": 100.0, "30s": 100.0}
    assert ev["breach"] and "ttft_p99" in ev["reason"]
    # delivered-only observed percentile stays finite (0 when none)
    assert ev["ttft_p99_s"] == 0.0
    # mixed: 1 failure among 99 fast deliveries = 1% bad = burn 1.0
    t2 = T.SLOTracker(ttft_p99_s=0.5, windows_s=(5.0, 30.0))
    for i in range(99):
        t2.observe_request(ttft_s=0.1, ok=True, t=100.0 + i * 0.01)
    t2.observe_request(ok=False, t=101.0)
    ev2 = t2.evaluate(now=101.0)
    assert ev2["burn"]["ttft_p99"]["5s"] == 1.0
    assert not ev2["breach"]  # burning AT budget, not past it


def test_slo_long_window_is_time_pruned_not_count_truncated():
    """The event store prunes by TIME (the long window), never by a
    small count bound — under load a count-bounded ring would shrink
    the long window to minutes and let a short burst page through the
    multi-window gate it should have diluted."""
    t = T.SLOTracker(ttft_p99_s=0.5, windows_s=(5.0, 600.0))
    # 7000 events over ~580s: a 4096-cap ring would have dropped the
    # first ~half; time pruning keeps everything inside 600s
    for i in range(7000):
        t.observe_request(ttft_s=0.1, ok=True, t=100.0 + i * 0.083)
    ev = t.evaluate(now=100.0 + 7000 * 0.083)
    with t._lock:
        n = len(t._events)
    assert n == 7000
    # a 3-request bad burst at the end: diluted far below threshold on
    # the long window -> no breach
    for i in range(3):
        t.observe_request(ttft_s=2.0, ok=True, t=100.0 + 7000 * 0.083 + i)
    ev = t.evaluate(now=100.0 + 7000 * 0.083 + 3)
    assert ev["burn"]["ttft_p99"]["600s"] < 1.0
    assert not ev["breach"]
    # events beyond the long window drop off on the next observe
    t.observe_request(ttft_s=0.1, ok=True, t=100.0 + 7000 * 0.083 + 700)
    with t._lock:
        assert len(t._events) < 7003


def test_slo_multiwindow_gate_needs_both_windows_burning():
    """One bad spike inside the short window but diluted over the long
    window must NOT breach — the long window is the page-worthiness
    gate (multi-window burn-rate semantics)."""
    t = T.SLOTracker(ttft_p99_s=0.5, windows_s=(5.0, 60.0))
    # 200 good requests spread over the long window
    for i in range(200):
        t.observe_request(ttft_s=0.1, ok=True, t=50.0 + i * 0.25)
    # a short burst of bad ones right at the end
    for i in range(3):
        t.observe_request(ttft_s=2.0, ok=True, t=99.5 + i * 0.1)
    ev = t.evaluate(now=100.0)
    assert ev["burn"]["ttft_p99"]["5s"] > 1.0
    assert ev["burn"]["ttft_p99"]["60s"] <= 1.5  # diluted
    # short window burns but the long window gates the page
    if ev["burn"]["ttft_p99"]["60s"] <= 1.0:
        assert not ev["breach"]


def test_slo_error_rate_burn_and_collect_gauges():
    import time as _time

    t = T.SLOTracker(error_rate=0.1, windows_s=(5.0, 30.0))
    # real-clock-relative stamps: collect() evaluates at the live
    # monotonic now, so the window must contain them
    now = _time.monotonic()
    for i in range(8):
        t.observe_request(ok=True, t=now - 1.0 + i * 0.1)
    for i in range(2):
        t.observe_request(ok=False, t=now - 0.2 + i * 0.1)
    ev = t.evaluate(now=now)
    # 2/10 failures over a 0.1 objective = 2x burn, both windows
    assert ev["burn"]["error_rate"] == {"5s": 2.0, "30s": 2.0}
    assert ev["breach"] and "error_rate" in ev["reason"]
    # the collector exports the same numbers as declared pfx_slo_* rows
    r = T.Registry()
    r.register_collector(t)
    rows = {(n, frozenset(lab.items())): v for n, lab, v in t.collect()}
    assert rows[("pfx_slo_objective", frozenset({("objective", "error_rate")}))] == 0.1
    assert all(n in T.METRICS for (n, _), _ in zip(rows.keys(), rows.values()))
    snap = r.snapshot()
    assert "pfx_slo_burn_rate" in snap
    metrics, types = parse_prometheus(r.render_prometheus(snap))
    assert types["pfx_slo_breach"] == "gauge"
    assert metrics["pfx_slo_breach"][
        frozenset({("objective", "error_rate")})
    ] == 1.0


def test_flight_dir_routes_default_dump(tmp_path, monkeypatch):
    """Satellite: flight dumps land under PFX_FLIGHT_DIR (default
    ./artifacts/) instead of polluting the process cwd."""
    monkeypatch.delenv("PFX_FLIGHT_RECORDER", raising=False)
    monkeypatch.delenv("PFX_FLIGHT_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    fr = T.FlightRecorder(capacity=2)
    fr.record({"event": "x"})
    path = fr.dump(reason="unit")
    assert path == os.path.join("artifacts", "flight_recorder.jsonl")
    assert os.path.exists(tmp_path / "artifacts" / "flight_recorder.jsonl")
    # the env dir re-routes; an explicit caller path still wins over it
    monkeypatch.setenv("PFX_FLIGHT_DIR", str(tmp_path / "ops"))
    assert fr.dump(reason="dir") == str(
        tmp_path / "ops" / "flight_recorder.jsonl"
    )
    explicit = str(tmp_path / "here.jsonl")
    assert fr.dump(path=explicit, reason="explicit") == explicit


# ---------------------------------------------------------------------------
# engine step records: the training-side observability contract
# ---------------------------------------------------------------------------


def test_engine_step_records_carry_phases_compile_and_mfu(tmp_path, devices8):
    """Step records gain tokens_per_sec / model_flops / mfu (analytic
    estimator vs peak) and the per-phase breakdown; compile_s appears on
    the FIRST logged record only, and the ips window excludes it."""
    from paddlefleetx_tpu.core.engine import Engine
    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import AttrDict, process_configs

    cfg = AttrDict.from_nested(
        {
            "Global": {"global_batch_size": 16, "micro_batch_size": 1, "seed": 7},
            "Engine": {
                "max_steps": 3,
                "eval_freq": 0,
                "logging_freq": 1,
                "mix_precision": {"enable": False},
                "save_load": {"save_steps": 0, "output_dir": str(tmp_path / "o")},
                "metrics_file": str(tmp_path / "metrics.jsonl"),
            },
            # same tiny shape as tests/test_engine.py::tiny_cfg so the
            # train-step compile rides the shared persistent cache
            "Model": {
                "module": "GPTModule",
                "vocab_size": 128,
                "hidden_size": 64,
                "num_layers": 2,
                "num_attention_heads": 8,
                "max_position_embeddings": 32,
                "hidden_dropout_prob": 0.0,
                "attention_probs_dropout_prob": 0.0,
                "dtype": "float32",
            },
            "Distributed": {},
            "Optimizer": {
                "name": "FusedAdamW",
                "lr": {"name": "Constant", "learning_rate": 3e-3},
            },
        }
    )
    cfg = process_configs(cfg, num_devices=8)
    mesh = init_dist_env(cfg)
    module = build_module(cfg)
    rng = np.random.default_rng(0)

    def batch():
        return {
            "tokens": rng.integers(0, 128, (16, 32)).astype(np.int64),
            "labels": rng.integers(0, 128, (16, 32)).astype(np.int64),
            "loss_mask": np.ones((16, 32), np.float32),
            "position_ids": np.tile(np.arange(32), (16, 1)),
        }

    loader = [batch() for _ in range(3)]
    with mesh:
        engine = Engine(cfg, module, mesh)
        engine.fit(loader)

    records = [json.loads(x) for x in open(cfg.Engine.metrics_file)]
    assert len(records) == 3
    first = records[0]
    # the acceptance keys
    for key in ("mfu", "tokens_per_sec", "data_wait_s", "host_s", "step_s",
                "model_flops", "compile_s"):
        assert key in first, (key, first)
    assert first["compile_s"] > 0
    assert all("compile_s" not in r for r in records[1:]), records
    assert first["tokens_per_sec"] == first["ips"] > 0
    # compile excluded from the window: the first window's per-step wall
    # time must not contain the multi-second trace+compile
    assert first["step_s"] < first["compile_s"] + 1.0
    # mfu = tokens/s * flops/tok / (peak * devices), vs the same estimator
    per_tok = T.model_flops_per_token(module.config)
    peak = T.peak_flops()
    # the record rounds mfu to 6 places: half a unit in the last place is
    # part of the tolerance (at this toy size it can exceed rel=1e-3)
    assert first["mfu"] == pytest.approx(
        first["ips"] * per_tok / (peak * mesh.size), rel=1e-3, abs=5.1e-7
    )
    assert first["host_s"] >= 0 and first["data_wait_s"] >= 0
    # the registry mirrors the logged values
    reg = T.get_registry()
    assert reg.value("pfx_train_steps_total") == 3
    assert reg.value("pfx_train_mfu") == records[-1]["mfu"]
    # every record also landed in the flight recorder ring
    steps = [e.get("step") for e in T.get_flight_recorder().events()
             if e.get("event") == "step"]
    assert {1, 2, 3} <= set(steps)
    # the fit's trace mirrors each logged window as a step_window span
    # (records link to it via trace_id)
    from paddlefleetx_tpu.utils.tracing import get_trace_buffer

    assert all(r["trace_id"] == records[0]["trace_id"] for r in records)
    tc = get_trace_buffer().get(records[0]["trace_id"])
    assert tc is not None and tc.name == "train"
    spans = [e for e in tc.timeline()["events"]
             if e["name"] == "step_window"]
    assert [s["args"]["step"] for s in spans] == [1, 2, 3]
    assert spans[0]["args"]["loss"] == records[0]["loss"]
    assert spans[0]["args"]["data_wait_s"] == records[0]["data_wait_s"]
