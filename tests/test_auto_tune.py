"""tools/auto.py --tune sweep end-to-end (reference AutoEngine.tune,
core/engine/auto_engine.py:146 + Strategy tuning knobs utils/config.py:
515-590): candidates may vary recompute / accumulation / precision, not
just mesh layout."""

import json
import os
import subprocess
import sys

import pytest

from tools.auto import enumerate_layouts, overrides_for

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_enumerate_layouts_covers_non_layout_knobs():
    cands = enumerate_layouts(8)
    assert {"dp": 8, "mp": 1, "pp": 1} in cands
    assert any(c.get("recompute") == "selective" for c in cands)
    assert any(c.get("recompute") == "full" for c in cands)
    assert any(c.get("accumulate") == 2 for c in cands)
    assert any(c.get("amp") == "bf16" for c in cands)
    # precision-memory knobs (the 1.3B-fit levers)
    assert any(c.get("main_grad") is False for c in cands)
    assert any(c.get("multi_precision") is False for c in cands)
    # single device still tunes execution knobs
    assert len(enumerate_layouts(1)) >= 5


def test_overrides_for_execution_knobs():
    ov = overrides_for(
        {"dp": 2, "recompute": "selective", "accumulate": 2, "amp": "bf16"},
        global_batch=16,
    )
    assert "Global.local_batch_size=8" in ov
    assert "Global.micro_batch_size=4" in ov  # local / accumulate
    assert "Model.use_recompute=True" in ov
    assert "Model.recompute_granularity=selective" in ov
    assert "Engine.mix_precision.enable=True" in ov
    assert "Engine.mix_precision.dtype=bfloat16" in ov
    # off-switches
    ov = overrides_for({"recompute": "none", "amp": "fp32"}, global_batch=8)
    assert "Model.use_recompute=False" in ov
    assert "Engine.mix_precision.enable=False" in ov
    # precision-memory knobs
    ov = overrides_for(
        {"amp": "bf16", "main_grad": False, "multi_precision": False},
        global_batch=8,
    )
    assert "Engine.mix_precision.main_grad=False" in ov
    assert "Optimizer.multi_precision=False" in ov


@pytest.mark.slow
def test_tune_sweep_e2e(tmp_path):
    """Two-candidate sweep varying only execution knobs: results JSON has
    per-candidate ips and a best line is printed."""
    from paddlefleetx_tpu.data.gpt_dataset import write_synthetic_corpus

    data = tmp_path / "data"
    data.mkdir()
    write_synthetic_corpus(str(data / "corp"), vocab_size=128, num_docs=16)
    out_dir = tmp_path / "out"

    base = os.path.join(REPO, "configs/gpt/pretrain_gpt_345M_single.yaml")
    cfg_path = tmp_path / "tune_tiny.yaml"
    cfg_path.write_text(
        f"""_base_: {base}

Global:
  global_batch_size: 8
  local_batch_size: 8
  micro_batch_size: 8

Model:
  num_layers: 2
  hidden_size: 64
  num_attention_heads: 4
  vocab_size: 128
  max_position_embeddings: 32

Engine:
  mix_precision:
    enable: False
  save_load:
    output_dir: {out_dir}

Data:
  Train:
    dataset:
      input_dir: {data}
      max_seq_len: 32

Tuning:
  candidates:
    - {{dp: 1, mp: 1, pp: 1, recompute: selective, amp: bf16}}
    - {{dp: 1, mp: 1, pp: 1, accumulate: 2}}
"""
    )

    env = dict(os.environ)
    env["PFX_PLATFORM"] = "cpu"
    # single-device sweep: conftest's 8-device XLA flag would leak in and
    # change the inferred dp world
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "auto.py"),
         "-c", str(cfg_path), "--tune", "--tune-steps", "4"],
        capture_output=True, text=True, timeout=540, cwd=REPO, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "best layout:" in out.stdout

    results = json.load(open(out_dir / "auto_tune_results.json"))
    assert len(results) == 2
    assert all(r["ok"] and r["ips"] > 0 for r in results)
    assert results[0]["layout"]["recompute"] == "selective"
    assert results[0]["layout"]["amp"] == "bf16"
    assert results[1]["layout"]["accumulate"] == 2


def test_overrides_for_attn_knobs():
    ov = overrides_for({"sep": 2, "attn": "ring", "zigzag": True}, global_batch=8)
    assert "Model.attn_impl=ring" in ov
    assert "Distributed.sep_zigzag=True" in ov
    assert "Distributed.sep_degree=2" in ov


def test_tune_parent_never_touches_the_backend(monkeypatch, tmp_path, capsys):
    """One process per chip: the sweeping parent learns the device count
    from a child and hands every candidate to a tools/train.py child — it
    must not initialize a backend itself (it would hold the chip)."""
    import jax

    import tools.auto as auto

    def boom(*a, **k):
        raise AssertionError("the tune parent touched the jax backend")

    for name in ("devices", "device_count", "local_devices", "default_backend"):
        monkeypatch.setattr(jax, name, boom)
    monkeypatch.setattr(auto, "_device_count", lambda: 4)
    seen = []

    def fake_candidate(config, base, cand, steps, gbs):
        seen.append(cand)
        return {"layout": cand, "ok": True, "ips": 1000.0 + len(seen)}

    monkeypatch.setattr(auto, "run_candidate", fake_candidate)
    auto.main([
        "-c", os.path.join(REPO, "configs/gpt/pretrain_gpt_345M_single.yaml"),
        "--tune", "-o", f"Engine.save_load.output_dir={tmp_path}",
        "-o", "Global.local_batch_size=4", "-o", "Global.micro_batch_size=4",
    ])
    assert {"dp": 4, "mp": 1, "pp": 1} in seen and {"dp": 2, "mp": 2, "pp": 1} in seen
    assert "on 4 devices" in capsys.readouterr().out
    assert (tmp_path / "auto_tune_results.json").exists()
