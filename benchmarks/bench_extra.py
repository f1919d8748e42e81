"""Real-chip benchmarks beyond the bench.py headline: the BASELINE.md
north-star configs that fit ONE chip.

Cases (per-chip baselines from the reference's published numbers):
  gpt1p3b    GPT-1.3B pretrain, seq 1024      — ref ~11,500 tok/s/V100-32G
             (projects/gpt/docs/hybrid_parallel.md:100-109, fp16+dp8+recompute)
  vit_b16    ViT-B/16 224 ImageNet pretrain   — ref 7350/16 = 459 img/s/A100
             (projects/vit/README.md:84, A100*N2C16)
  vit_l16    ViT-L/16 384 finetune shape      — ref 519/16 = 32.4 img/s/A100
             (projects/vit/README.md:86)
  ernie_base ERNIE-345M MLM+NSP pretrain      — no published ref number
             (shape: pretrain_ernie_base_345M_single_card.yaml)
  imagen_base64  Imagen base-64 unet1 train   — no published ref number
             (shape: imagen_397M_text2im_64x64.yaml, precomputed embeds)

GPT-6.7B (mp2 pp4 sharding16) does NOT fit one 16 GB chip in any precision
(13.4 GB params + 26.8 GB adam moments at bf16/fp32 mix); recorded as
infeasible-single-chip rather than benchmarked dishonestly.

Each case prints ONE JSON line {"metric", "value", "unit", "vs_baseline"}
and appends it to benchmarks/results_extra.jsonl.  Usage:

  python benchmarks/bench_extra.py [--cases gpt1p3b,vit_b16,vit_l16]
      [--steps N]
"""

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _gpt_base_cfg(env: str, n_dev: int, steps: int, *, batch: int, seq: int,
                  hidden: int, layers: int):
    """Shared GPT bench config frame: bf16 compute, selective remat,
    chunked CE, flash fused/512 (auto ladder when 512 does not divide a
    shrink-knob seq).  ``env`` is the BENCH_<env>_* knob prefix; cases
    layer their memory levers on top of the returned dict."""
    batch = int(os.environ.get(f"BENCH_{env}_BATCH", batch)) * n_dev
    seq = int(os.environ.get(f"BENCH_{env}_SEQ", seq))
    return {
        "Global": {
            "global_batch_size": batch,
            "micro_batch_size": batch // n_dev,
            "seed": 1024,
            "prng_impl": "rbg",
        },
        "Engine": {
            "max_steps": steps,
            "eval_freq": 0,
            "logging_freq": 10**9,
            "mix_precision": {"enable": True, "dtype": "bfloat16"},
            "save_load": {"save_steps": 0},
        },
        "Model": {
            "module": "GPTModule",
            # BENCH_<env>_* shrink knobs exist for CI smoke only
            "vocab_size": int(os.environ.get(f"BENCH_{env}_VOCAB", 50304)),
            "hidden_size": int(os.environ.get(f"BENCH_{env}_HIDDEN", hidden)),
            "num_layers": int(os.environ.get(f"BENCH_{env}_LAYERS", layers)),
            "num_attention_heads": 16,
            "max_position_embeddings": seq,
            "hidden_dropout_prob": 0.1,
            "attention_probs_dropout_prob": 0.1,
            "attn_impl": "flash",
            "use_recompute": True,
            "recompute_granularity":
                os.environ.get(f"BENCH_{env}_REMAT", "selective"),
            "use_fused_ln": True,
            "use_chunked_ce": True,
            # fused/512 measured end-to-end on-chip 18:57Z: 1.3B 14,024
            # tok/s at b8 vs 13,480 with split/256 (results_extra.jsonl)
            "flash_block": int(os.environ.get(
                f"BENCH_{env}_FLASH_BLOCK", 512 if seq % 512 == 0 else 0)),
            "flash_bwd": os.environ.get(f"BENCH_{env}_FLASH_BWD", "fused"),
        },
        "Distributed": {},
        "Optimizer": {
            "name": "FusedAdamW",
            "weight_decay": 0.01,
            "beta1": 0.9,
            "beta2": 0.95,
            "lr": {"name": "Constant", "learning_rate": 1e-4},
            "grad_clip": {"name": "ClipGradByGlobalNorm", "clip_norm": 1.0},
        },
    }, batch, seq


def _gpt_cfg(n_dev: int, steps: int):
    """GPT-1.3B (reference pretrain_gpt_1.3B_dp8.yaml model shape: hidden
    2048, 24 layers, 16 heads) on one chip: the memory levers that fit
    1.3B params + moments + activations in 16 GB HBM layered on the
    shared frame."""
    # b8 is the measured sweet spot (18:57Z on-chip: b8 14,024 tok/s /
    # 58.1% MFU vs b4 13,445; b12 OOMs; b8+full-remat 13,511)
    raw, batch, seq = _gpt_base_cfg(
        "1P3B", n_dev, steps, batch=8, seq=1024, hidden=2048, layers=24)
    # bf16 grads (main_grad off) halve the 4.1G of fp32 grad
    # accumulators — measured necessary to fit AdamW-complete 1.3B on one
    # 15.75G chip (03:18Z window: b2+full-remat+offload still OOM'd by
    # 853M with fp32 grads)
    raw["Engine"]["mix_precision"]["main_grad"] = (
        os.environ.get("BENCH_1P3B_MAIN_GRAD", "0") == "1")
    # fp32 masters (5.2G) + bf16 mu (2.6G) + fp32 nu (5.2G) alone are
    # 13G of the chip's 15.75G HBM; grads + activations push the step
    # past 21G (measured OOM).  Host offload of the moments does NOT
    # save the day either: the monolithic device_put stages every
    # stacked nu leaf on-device at once (measured 03:24Z window: 4.1G
    # of copy-start temps, still 1.19G over).  What fits is the
    # reference's OTHER knob: multi_precision=False — bf16 params, no
    # fp32 masters, moments in bf16 — ~10.4G peak including grads.
    raw["Distributed"] = {
        "sharding": {
            "sharding_offload":
                os.environ.get("BENCH_1P3B_OFFLOAD", "0") == "1",
        },
    }
    raw["Optimizer"]["multi_precision"] = (
        os.environ.get("BENCH_1P3B_MULTI_PRECISION", "0") == "1")
    # bf16 first moment halves the largest optimizer buffer
    # (optims/optimizer.py:46 moment_dtype -> optax mu_dtype)
    raw["Optimizer"]["moment_dtype"] = "bfloat16"
    return raw, batch, seq


def _vit_cfg(n_dev: int, steps: int, large: bool):
    """ViT-B/16 224 pretrain / ViT-L/16 384 finetune shapes (reference
    configs/vis/vit/ViT_{base,large}_patch16_*.yaml)."""
    if large:
        image, hidden, layers, heads = 384, 1024, 24, 16
        batch = int(os.environ.get("BENCH_VITL_BATCH", 32)) * n_dev
    else:
        image, hidden, layers, heads = 224, 768, 12, 12
        batch = int(os.environ.get("BENCH_VITB_BATCH", 128)) * n_dev
    layers = int(os.environ.get("BENCH_VIT_LAYERS", layers))  # CI shrink knob
    return {
        "Global": {
            "global_batch_size": batch,
            "micro_batch_size": batch // n_dev,
            "seed": 1024,
            "prng_impl": "rbg",
        },
        "Engine": {
            "max_steps": steps,
            "eval_freq": 0,
            "logging_freq": 10**9,
            "mix_precision": {"enable": True, "dtype": "bfloat16"},
            "save_load": {"save_steps": 0},
        },
        "Model": {
            "module": "ViTModule",
            "image_size": image,
            "patch_size": 16,
            "num_classes": 1000,
            "hidden_size": hidden,
            "num_layers": layers,
            "num_attention_heads": heads,
            "hidden_dropout_prob": 0.1,
            # without remat the 12-layer scan stashes every block activation
            # (443M apiece at b128) and the step OOMs; one extra forward is
            # far cheaper than spilling (measured: OOM -> fits)
            "use_recompute": os.environ.get("BENCH_VIT_REMAT", "1") == "1",
        },
        "Distributed": {},
        "Optimizer": {
            "name": "AdamW",
            "weight_decay": 0.3,
            "lr": {"name": "Constant", "learning_rate": 3e-4},
            "grad_clip": {"name": "ClipGradByGlobalNorm", "clip_norm": 1.0},
        },
    }, batch, image


def _gpt4k_cfg(n_dev: int, steps: int):
    """GPT-345M at seq 4096 (4x the headline): long-context single-chip
    evidence — flash fused/512 at 4096 rows, selective remat, chunked CE
    (the fp32 logits buffer at 4096x50304 would be 3.3 GB at b4).  The
    reference documents seq-1024 configs only, so the row reports an
    absolute rate (vs_baseline null) with the headline config cited."""
    return _gpt_base_cfg(
        "4K", n_dev, steps, batch=4, seq=4096, hidden=1024, layers=24)


CASES = {
    "gpt1p3b": {"baseline": 11500.0, "unit": "tokens/s/chip"},
    "gpt_seq4096": {
        "baseline": None, "unit": "tokens/s/chip",
        "note": "no published reference number at seq 4096 (reference GPT "
                "docs are seq-1024); shape = headline 345M at 4x sequence",
    },
    "vit_b16": {"baseline": 459.0, "unit": "images/s/chip"},
    "vit_l16": {"baseline": 32.4, "unit": "images/s/chip"},
    # the reference publishes NO throughput number for these two families
    # (projects/ernie/, projects/imagen/ ship configs + scripts only), so
    # the rows report absolute per-chip rates with vs_baseline null and a
    # citation of the config whose shape they reproduce
    "ernie_base": {
        "baseline": None, "unit": "tokens/s/chip",
        "note": "no published reference number; shape = "
                "pretrain_ernie_base_345M_single_card.yaml",
    },
    "imagen_base64": {
        "baseline": None, "unit": "images/s/chip",
        "note": "no published reference number; shape = "
                "imagen_397M_text2im_64x64.yaml unet1 (text embeds "
                "precomputed, encoder frozen as in only_train_unet_number=1)",
    },
}


def _ernie_cfg(n_dev: int, steps: int):
    """ERNIE-345M MLM+NSP pretrain shape (reference
    ppfleetx/configs/nlp/ernie/pretrain_ernie_base_345M_single_card.yaml:
    vocab 40000, hidden 1024, 24 layers, 16 heads, seq 512)."""
    batch = int(os.environ.get("BENCH_ERNIE_BATCH", 32)) * n_dev
    seq = int(os.environ.get("BENCH_ERNIE_SEQ", 512))
    return {
        "Global": {
            "global_batch_size": batch,
            "micro_batch_size": batch // n_dev,
            "seed": 1024,
            "prng_impl": "rbg",
        },
        "Engine": {
            "max_steps": steps,
            "eval_freq": 0,
            "logging_freq": 10**9,
            "mix_precision": {"enable": True, "dtype": "bfloat16"},
            "save_load": {"save_steps": 0},
        },
        "Model": {
            "module": "ErnieModule",
            "vocab_size": 40000,
            "hidden_size": int(os.environ.get("BENCH_ERNIE_HIDDEN", 1024)),
            "num_layers": int(os.environ.get("BENCH_ERNIE_LAYERS", 24)),
            "num_attention_heads": 16,
            "ffn_hidden_size": 4096,
            "max_position_embeddings": seq,
            "type_vocab_size": 4,
            "binary_head": True,
            "attn_impl": "flash",
            "use_chunked_ce": True,
        },
        "Distributed": {},
        "Optimizer": {
            "name": "FusedAdamW",
            "weight_decay": 0.01,
            "beta1": 0.9,
            "beta2": 0.999,
            "lr": {"name": "Constant", "learning_rate": 1e-4},
            "grad_clip": {"name": "ClipGradByGlobalNorm", "clip_norm": 1.0},
        },
    }, batch, seq


def _imagen_cfg(n_dev: int, steps: int):
    """Imagen base-64 text2im unet (reference
    ppfleetx/configs/multimodal/imagen/imagen_397M_text2im_64x64.yaml:
    dim 512, mults 1/2/3/4, 3 resblocks, text_embed_dim 1024, loader
    batch 16).  Text embeds are fed precomputed: the reference trains
    unet 1 only with the T5 encoder frozen, so encoder FLOPs are not part
    of the trained-throughput comparison either way."""
    batch = int(os.environ.get("BENCH_IMAGEN_BATCH", 16)) * n_dev
    dim = int(os.environ.get("BENCH_IMAGEN_DIM", 512))
    return {
        "Global": {
            "global_batch_size": batch,
            "micro_batch_size": batch // n_dev,
            "seed": 1024,
            "prng_impl": "rbg",
        },
        "Engine": {
            "max_steps": steps,
            "eval_freq": 0,
            "logging_freq": 10**9,
            "mix_precision": {"enable": True, "dtype": "bfloat16"},
            "save_load": {"save_steps": 0},
        },
        "Model": {
            "module": "ImagenModule",
            "unets": [{
                "dim": dim,
                "dim_mults": [1, 2, 3, 4],
                "num_resnet_blocks": 3,
                "layer_attns": [False, True, True, True],
                "layer_cross_attns": [False, True, True, True],
                "attn_heads": 8,
            }],
            "image_sizes": [64],
            "text_embed_dim": 1024,
            "timesteps": 1000,
            "noise_schedules": ["cosine"],
            "cond_drop_prob": 0.1,
            "unet_number": 1,
        },
        "Distributed": {},
        "Optimizer": {
            "name": "FusedAdamW",
            "weight_decay": 0.01,
            "lr": {"name": "Constant", "learning_rate": 1e-4},
            "grad_clip": {"name": "ClipGradByGlobalNorm", "clip_norm": 1.0},
        },
    }, batch, 64


def run_case(name: str, steps: int) -> dict:
    import jax
    import numpy as np

    from paddlefleetx_tpu.core.engine import Engine
    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import AttrDict, process_configs

    n_dev = jax.device_count()
    if name == "gpt1p3b":
        raw, batch, seq = _gpt_cfg(n_dev, steps)
    elif name == "gpt_seq4096":
        raw, batch, seq = _gpt4k_cfg(n_dev, steps)
    elif name == "ernie_base":
        raw, batch, seq = _ernie_cfg(n_dev, steps)
    elif name == "imagen_base64":
        raw, batch, seq = _imagen_cfg(n_dev, steps)
    else:
        raw, batch, seq = _vit_cfg(n_dev, steps, large=name == "vit_l16")

    cfg = process_configs(AttrDict.from_nested(raw), num_devices=n_dev)
    mesh = init_dist_env(cfg)
    module = build_module(cfg)

    rng = np.random.default_rng(0)
    if name in ("gpt1p3b", "gpt_seq4096"):
        vocab = int(cfg.Model.vocab_size)
        host_batch = {
            "tokens": rng.integers(0, vocab, (batch, seq)).astype(np.int64),
            "labels": rng.integers(0, vocab, (batch, seq)).astype(np.int64),
            "loss_mask": np.ones((batch, seq), np.float32),
            "position_ids": np.tile(np.arange(seq), (batch, 1)),
        }
        per_step = batch * seq  # tokens
    elif name == "ernie_base":
        vocab = int(cfg.Model.vocab_size)
        # ~15% masked positions, -1 everywhere else (ernie/model.py label
        # contract: -1 = unmasked, ignored by the CE)
        labels = np.full((batch, seq), -1, np.int64)
        mask = rng.random((batch, seq)) < 0.15
        labels[mask] = rng.integers(0, vocab, mask.sum())
        host_batch = {
            "input_ids": rng.integers(0, vocab, (batch, seq)).astype(np.int64),
            "masked_lm_labels": labels,
            "next_sentence_label": rng.integers(0, 2, (batch,)).astype(np.int64),
        }
        per_step = batch * seq  # tokens
    elif name == "imagen_base64":
        text_len = 128  # reference text_max_len
        emb_dim = int(cfg.Model.text_embed_dim)
        host_batch = {
            "images": rng.uniform(0, 1, (batch, seq, seq, 3)).astype(np.float32),
            "text_embeds": rng.normal(0, 1, (batch, text_len, emb_dim)).astype(np.float32),
            "text_mask": np.ones((batch, text_len), np.int32),
        }
        per_step = batch  # images
    else:
        host_batch = {
            "images": rng.normal(0, 1, (batch, seq, seq, 3)).astype(np.float32),
            "labels": rng.integers(0, 1000, (batch,)).astype(np.int64),
        }
        per_step = batch  # images

    with mesh:
        engine = Engine(cfg, module, mesh)
        dev_batch = engine._put_batch(host_batch)
        for _ in range(3):
            engine.state, m = engine.train_step(engine.state, dev_batch)
        float(m["loss"])  # drain the warmup chain (see bench.py)
        t0 = time.time()
        for _ in range(steps):
            engine.state, m = engine.train_step(engine.state, dev_batch)
        final_loss = float(m["loss"])
        dt = time.time() - t0

    meta = CASES[name]
    if not np.isfinite(final_loss):
        return {"metric": f"{name}_throughput_per_chip", "value": 0.0,
                "unit": f"{meta['unit']} (non-finite loss)",
                "vs_baseline": 0.0 if meta["baseline"] else None,
                "platform": jax.default_backend()}
    rate = per_step * steps / dt / n_dev
    row = {
        "metric": f"{name}_throughput_per_chip",
        "value": round(rate, 1),
        "unit": meta["unit"],
        "vs_baseline": (round(rate / meta["baseline"], 3)
                        if meta["baseline"] else None),
        # CPU smoke rows must never read as chip evidence
        "platform": jax.default_backend(),
    }
    if meta.get("note"):
        row["note"] = meta["note"]
    if name in ("gpt1p3b", "gpt_seq4096"):
        from bench import model_flops_per_token

        mc = cfg.Model
        flops_tok = model_flops_per_token(
            mc.hidden_size, mc.num_layers, mc.vocab_size, seq
        )
        peak = float(os.environ.get("BENCH_PEAK_TFLOPS", 197)) * 1e12
        row["mfu"] = round(rate * flops_tok / peak, 4)
    return row


OUT_PATH = os.path.join(ROOT, "benchmarks", "results_extra.jsonl")


def _zero_vsb(name: str):
    """Honest-zero rows keep the success-path vs_baseline convention:
    0.0 ratio where a baseline exists, null where none is published."""
    return 0.0 if CASES[name]["baseline"] else None


def _emit(row: dict) -> None:
    line = json.dumps(row)
    print(line, flush=True)
    with open(OUT_PATH, "a") as f:
        f.write(line + "\n")


def _parse_cases(cases_arg: str) -> list:
    out = []
    for name in cases_arg.split(","):
        name = name.strip()
        if name not in CASES:
            print(f"unknown case {name!r}; have {sorted(CASES)}", file=sys.stderr)
            continue
        out.append(name)
    return out


def _parent(argv) -> int:
    """Same always-emit contract as bench.py (shared harness): the child
    runs the cases, the pure-Python parent stays signal-responsive and
    writes an honest 0.0 row for every case the child did not finish."""
    from bench import run_child_with_honest_fallback

    ap = argparse.ArgumentParser()
    ap.add_argument("--cases", default="gpt1p3b,vit_b16,vit_l16")
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args(argv)
    cases = _parse_cases(args.cases)
    if not cases:
        # fail fast: spawning a child with no cases would probe the TPU
        # for minutes and exit 0 with zero rows
        print(f"no valid cases in {args.cases!r}; have {sorted(CASES)}",
              file=sys.stderr)
        return 2

    def emit_missing(seen, reason):
        for name in cases:
            metric = f"{name}_throughput_per_chip"
            if metric not in seen:
                _emit({"metric": metric, "value": 0.0,
                       "unit": f"{CASES[name]['unit']} ({reason})",
                       "vs_baseline": _zero_vsb(name)})

    return run_child_with_honest_fallback(
        [sys.executable, os.path.abspath(__file__), "--child",
         "--cases", ",".join(cases), "--steps", str(args.steps)],
        float(os.environ.get("BENCH_EXTRA_DEADLINE_S", 1500)),
        emit_missing,
    )


def _child(argv) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cases", default="gpt1p3b,vit_b16,vit_l16")
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args(argv)

    from paddlefleetx_tpu.utils.device import apply_platform_env

    apply_platform_env()

    for name in _parse_cases(args.cases):
        try:
            row = run_case(name, args.steps)
        except Exception as e:  # noqa: BLE001 — e.g. RESOURCE_EXHAUSTED on a
            # memory-tight case must not abort the remaining cases
            traceback.print_exc(file=sys.stderr)
            import jax

            row = {"metric": f"{name}_throughput_per_chip", "value": 0.0,
                   "unit": f"{CASES[name]['unit']} ({type(e).__name__})",
                   "vs_baseline": _zero_vsb(name),
                   "platform": jax.default_backend()}
        _emit(row)


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--child" in argv:
        argv.remove("--child")
        _child(argv)
        return
    sys.exit(_parent(argv))


if __name__ == "__main__":
    main()
