"""Unit tests for benchmarks/bench_extra.py case configs.

The GPT-1.3B single-chip fit hangs on three exact knobs
(multi_precision=False, main_grad=False, bf16 first moment: fp32 masters
+ two fp32 moments alone are ~15.6 GB of a 16 GB chip); a silent default
regression would OOM the next chip run instead of benchmarking.  Lock the layered config frames.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.bench_extra import _gpt4k_cfg, _gpt_cfg  # noqa: E402


def test_1p3b_memory_levers_default_on():
    raw, batch, seq = _gpt_cfg(n_dev=1, steps=2)
    assert batch == 8 and seq == 1024  # measured sweet spot
    assert raw["Optimizer"]["multi_precision"] is False
    assert raw["Optimizer"]["moment_dtype"] == "bfloat16"
    assert raw["Engine"]["mix_precision"]["main_grad"] is False
    assert raw["Model"]["hidden_size"] == 2048
    assert raw["Model"]["use_chunked_ce"] is True
    assert raw["Model"]["flash_block"] == 512
    assert raw["Model"]["flash_bwd"] == "fused"
    assert raw["Distributed"]["sharding"]["sharding_offload"] is False


def test_4k_case_shares_frame_without_1p3b_levers():
    raw, batch, seq = _gpt4k_cfg(n_dev=1, steps=2)
    assert batch == 4 and seq == 4096
    assert raw["Model"]["hidden_size"] == 1024  # 345M shape at 4x seq
    assert raw["Model"]["flash_block"] == 512  # 512 divides 4096
    assert raw["Model"]["use_chunked_ce"] is True
    # the 1.3B memory levers must NOT leak into the shared frame
    assert "multi_precision" not in raw["Optimizer"]
    assert "main_grad" not in raw["Engine"]["mix_precision"]


def test_shrink_seq_falls_back_to_auto_block(monkeypatch):
    # CI shrink seqs not divisible by 512 must drop to the auto ladder
    # (flash_block 0) instead of a trace-time divisor error
    monkeypatch.setenv("BENCH_4K_SEQ", "128")
    raw, _, seq = _gpt4k_cfg(n_dev=1, steps=2)
    assert seq == 128
    assert raw["Model"]["flash_block"] == 0
