#!/usr/bin/env python3
"""The second reading behind the limits of ``runners/train_arch.py``'s
comparison (PERF.md section 6): what the plain reference gives when it is
computed in the nearest precision BELOW the one the configuration states.
The configuration computes in bfloat16 (8 bits of mantissa); below it is
float8_e4m3 (4 bits), emulated here by rounding every weight matrix to it
(activations stay float32, so this reading is the kinder half of an fp8
computation).  That low-precision reference takes the system's place in the
comparison and has to come out as NOT correct by at least one limit.

    chiprun -- python3 pfx_bench/selftest/precision_reading.py train-trinity-mini-1of8 [seed]

By hand, on the chip (``--rehearse``: toy widths on the CPU).  One JSON line;
the same under ``chiprun_out/pfx_bench/<cell>/precision_reading.json``."""

import importlib.util
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import common  # noqa: E402


def main(argv) -> int:
    rehearse = "--rehearse" in argv
    args = [a for a in argv[1:] if a != "--rehearse"]
    cell = common.load_cell(args[0])
    seed = int(args[1]) if len(args) > 1 else 1
    os.environ["PFX_PLATFORM"] = "cpu" if rehearse else "tpu"
    sys.path.insert(0, common.ROOT)
    from paddlefleetx_tpu.utils.device import apply_platform_env

    apply_platform_env()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.utils.config import get_config

    spec = importlib.util.spec_from_file_location(
        "pfx_bench_runners_train_arch", os.path.join(BENCH, "runners", "train_arch.py"))
    arch = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(arch)
    config = cell["config_data"]
    ref = arch._load("pfx_bench_reference", os.path.join(common.ROOT, config["reference"]))
    sizes = config["rehearse_model"] if rehearse else config["model"]
    cfg = get_config(os.path.join(common.ROOT, config["yaml"]),
                     overrides=common.train_overrides(cell, seed, rehearse))
    params = jax.jit(build_module(cfg).init_params)(jax.random.PRNGKey(seed))
    s = int(cfg.Data.Train.dataset.max_seq_len)
    rng = np.random.default_rng(seed + 17)
    seq = rng.integers(1, int(cfg.Model.vocab_size), size=(1, s + 1))
    tokens, labels = jnp.asarray(seq[:, :-1], jnp.int32), jnp.asarray(seq[:, 1:], jnp.int32)
    mask = jnp.ones((1, s), jnp.float32)

    def low(p):  # matrices to float8_e4m3 and back; norm scales stay
        return jax.tree.map(
            lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32) if a.ndim > 1 and
            a.shape[-1] > 1 and a.size > 4096 else a, p)

    def reading(p):
        def loss(q, cast):
            lg = ref.logits(cast(q), tokens, sizes, None)
            return ref.loss_from_logits(lg, labels, mask), lg

        (rl, want), rg = jax.value_and_grad(lambda q: loss(q, lambda t: t), has_aux=True)(p)
        (sl, got), sg = jax.value_and_grad(lambda q: loss(q, low), has_aux=True)(p)
        return arch.compare(got, want, sl, rl, sg, rg)

    v = arch.verdict(jax.device_get(jax.jit(reading)(params)), params)
    out = {"cell": cell["name"], "seed": seed, "platform": jax.devices()[0].platform,
           "reading": "the reference with its weight matrices rounded to float8_e4m3, in the "
                      "system's place, against the reference in float32", **v}
    out["not_correct_by"] = [k for k, bad in (
        ("rms_over_std", v["rms_err_over_std"] > arch.LOGIT_RMS_BAND),
        ("tokens_off_share", v["tokens_off_share"] > arch.TOKENS_OFF_MAX),
        ("loss", abs(v["loss"] - v["reference_loss"]) > arch.LOSS_ABS_MAX),
        ("grad_cosine", v["grad_cosine"] < arch.GRAD_COSINE_MIN),
        ("grad_norm", v["grad_norm_rel_diff"] > arch.GRAD_NORM_REL_MAX),
        ("leaf_cosine", not v["grad_worst_leaf_cosine"] >= arch.LEAF_COSINE_MIN)) if bad]
    path = os.path.join(common.ROOT, "chiprun_out", common.BENCH_REL, cell["name"])
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "precision_reading.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
