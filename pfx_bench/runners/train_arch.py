"""Train runner for a configuration whose block family is not the dense
GPT's (``runners/train_arch.md``).  It loads ``runners/train.py`` by path and
keeps its flow (the child: config -> mesh -> module -> Engine -> loader,
``WindowLoader``, the traced stretch after the window; the parent: ``judge``)
and replaces what is literal there:

* the plain reference and the FLOP arithmetic are found through the
  configuration file's ``reference`` and ``math`` keys;
* the reference is given the configuration's sizes and the program's
  routing bias after the program's own warm start (``Engine.warm_start``,
  which the yaml asks for and ``tools/train.py`` runs alike); the whole
  comparison is one program (compiling is most of its time), the gradient
  is judged whole and by its worst leaf, and the Adam moments (zeros before
  the first step) make room for its length;
* the first loss is judged against the reference's own loss at the same
  weights, not against ln(50304);
* the readers' context gains ``math``, ``global_batch_size`` and the records
  of the traced steps."""

import argparse
import importlib.util
import json
import math
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import common  # noqa: E402
from common import Fail  # noqa: E402


def _load(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


base = _load("pfx_bench_runners_train", os.path.join(BENCH, "runners", "train.py"))

# Bands of the comparison that decides ``correct`` (PERF.md section 6 has the
# two readings each lies between).  Logits: within LOGIT_BAND of the
# reference's spread, as the dense cells.  The gradient over all leaves
# together: cosine and relative length; every single leaf: a cosine of its
# own, so that a wrong gradient of a small leaf (the router's, a norm's)
# cannot hide under the norm of the large ones beside it.  The loss: the
# system's on the check's sequence against the reference's, at three times
# the largest difference of the sound runs (the precision below does not
# move it; what does is a loss that reads another head matrix, other labels
# or another mask than the logits' path: tests/test_trinity_block.py plants
# one).
LOGIT_BAND, GRAD_COSINE_MIN, GRAD_NORM_REL_MAX, LOSS_ABS_MAX = 0.15, 0.98, 0.05, 0.002
LEAF_COSINE_MIN = 0.9
# The router's choice is a discontinuity: where bf16 rounding swaps the 8th
# and 9th expert of a token and one of the two is held here, that token's
# logits move by a third of their spread or more, in the program and in
# any other bf16 implementation alike.  So the logits are judged by their
# RMS error over all positions, and by the share of tokens whose own RMS
# error passes LOGIT_BAND of the spread, not by the largest single error.
LOGIT_RMS_BAND, TOKENS_OFF_MAX = 0.1, 0.1
# First training loss against the reference's loss at the same weights on
# the check's own sequence: both are ln(vocabulary) plus half the variance of
# the initial logits, on other tokens; the mean over 8k+ tokens of a
# per-token loss that spreads by about 1 moves by 0.01.  A wrong slice of
# the vocabulary, shifted labels or a missing final norm moves it by 0.3+.
FIRST_LOSS_ABS_MAX = 0.15


def run(cell: dict, args, t0: float) -> dict:
    """``train.run`` with this file as the child."""
    out = common.out_dir(cell["name"], args.seed, args.trace)
    result_path = os.path.join(out, "train_result.json")
    if os.path.exists(result_path):
        os.unlink(result_path)
    argv = [common.python(), os.path.abspath(__file__), "--child",
            "--workload", cell["name"], "--seed", str(args.seed),
            "--seconds", str(args.seconds), "--trace", str(args.trace),
            "--t0", repr(t0), "--result", result_path]
    if args.rehearse:
        argv.append("--rehearse")
    log = os.path.join(out, "train_child.log")
    rc = common.run_to_end(argv, common.child_env(args.rehearse, int(cell["chips"])),
                           log, timeout=args.seconds + 1100)
    if rc != 0 or not os.path.exists(result_path):
        raise Fail(f"train child exited {rc} (log: {log})\n{common.tail(log)}")
    with open(result_path) as f:
        return json.load(f)


# ===========================================================================
# Child half
# ===========================================================================


def compare(got, want, sl, rl, sg, rg):
    """Traced: logits [1, s, v] float32, losses, gradient trees -> scalars
    and (dot, |system|^2, |reference|^2) a leaf."""
    import jax
    import jax.numpy as jnp

    scale = jnp.std(want)
    token_rms = jnp.sqrt(jnp.mean(jnp.square(got - want), axis=-1))[0]
    f32 = lambda t: [x.astype(jnp.float32) for x in jax.tree.leaves(t)]  # noqa: E731
    per_leaf = jnp.stack([jnp.stack([jnp.vdot(a, b), jnp.vdot(a, a), jnp.vdot(b, b)])
                          for a, b in zip(f32(sg), f32(rg))])
    return {
        "loss": sl, "reference_loss": rl, "logit_std": scale,
        "max_abs_err": jnp.max(jnp.abs(got - want)),
        "rms_err": jnp.sqrt(jnp.mean(jnp.square(got - want))),
        "token_q": jnp.quantile(token_rms, jnp.array([0.5, 0.9, 0.99, 1.0])) / scale,
        "tokens_off_share": jnp.mean(token_rms > LOGIT_BAND * scale),
        "argmax_agree": jnp.mean(jnp.argmax(got, -1) == jnp.argmax(want, -1)),
        "per_leaf": per_leaf,
    }


def verdict(out, params) -> dict:
    """Host: what ``compare`` returned (fetched) -> the numbers, the limits
    and whether each holds."""
    import jax
    import numpy as np

    def ratio(dot, a, b):
        return {"cosine": float(dot / math.sqrt(a * b)) if a > 0 and b > 0 else float("nan"),
                "norm_rel_diff": float(abs(math.sqrt(a) - math.sqrt(b)) / math.sqrt(b))
                if b > 0 else float("nan")}

    rows = np.asarray(out["per_leaf"], np.float64)
    leaves = {jax.tree_util.keystr(path): ratio(*row) for (path, _), row in zip(
        jax.tree_util.tree_leaves_with_path(params), rows)}
    dot, sn2, rn2 = rows.sum(axis=0)
    whole = ratio(dot, sn2, rn2)
    # min() passes a NaN by; a leaf without a cosine is the worst there is
    worst_leaf = min(leaves, key=lambda k: leaves[k]["cosine"]
                     if math.isfinite(leaves[k]["cosine"]) else -2.0)
    worst = leaves[worst_leaf]["cosine"]
    sl, rl, scale, rms = (float(out[k]) for k in ("loss", "reference_loss", "logit_std", "rms_err"))
    off_share = float(out["tokens_off_share"])
    logits_ok = bool(math.isfinite(rms) and rms <= LOGIT_RMS_BAND * scale
                     and off_share <= TOKENS_OFF_MAX)
    grad_ok = bool(math.isfinite(whole["cosine"]) and whole["cosine"] >= GRAD_COSINE_MIN
                   and whole["norm_rel_diff"] <= GRAD_NORM_REL_MAX and math.isfinite(worst)
                   and worst >= LEAF_COSINE_MIN and abs(sl - rl) <= LOSS_ABS_MAX)
    return {"max_abs_err": float(out["max_abs_err"]), "rms_err": rms,
            "rms_err_over_std": rms / scale,
            "token_rms_err_over_std_q50_q90_q99_max": [float(x) for x in out["token_q"]],
            "tokens_off_share": off_share,
            "logit_bands": {"rms_over_std_max": LOGIT_RMS_BAND, "token_band_over_std": LOGIT_BAND,
                            "tokens_off_share_max": TOKENS_OFF_MAX},
            "logit_std": scale, "band": LOGIT_BAND * scale,
            "argmax_agree": float(out["argmax_agree"]),
            "loss": sl, "reference_loss": rl,
            "grad_norm": math.sqrt(sn2), "reference_grad_norm": math.sqrt(rn2),
            "grad_norm_rel_diff": whole["norm_rel_diff"], "grad_cosine": whole["cosine"],
            "grad_worst_leaf": worst_leaf, "grad_worst_leaf_cosine": worst,
            "grad_leaves": leaves,
            "grad_bands": {"cosine_min": GRAD_COSINE_MIN, "norm_rel_max": GRAD_NORM_REL_MAX,
                           "leaf_cosine_min": LEAF_COSINE_MIN, "loss_abs_max": LOSS_ABS_MAX},
            "logits_ok": logits_ok, "grad_ok": grad_ok, "ok": logits_ok and grad_ok}


def check_fn(module, ctx, ref, sizes, mcfg):
    """ONE program for the whole comparison (each further program costs a
    minute or more of compiling at these widths): the system's logits,
    its loss and gradient through ``module.loss_fn`` as it trains, the
    reference's logits, loss and ``jax.grad``; out come scalars and three
    numbers a leaf.  Both gradient trees are its temporaries."""
    import jax
    import jax.numpy as jnp

    from paddlefleetx_tpu.models.gpt import model as gpt

    def check(params, extra, batch):
        tokens, bias = batch["tokens"], extra["expert_bias"]
        got = gpt.forward(params, tokens, mcfg, ctx=ctx, train=False,
                          expert_bias=bias).astype(jnp.float32)

        def ref_loss(p):
            lg = ref.logits(p, tokens, sizes, bias)
            return ref.loss_from_logits(lg, batch["labels"], batch["loss_mask"]), lg

        (rl, want), rg = jax.value_and_grad(ref_loss, has_aux=True)(params)
        sl, sg = jax.value_and_grad(lambda p: module.loss_fn(
            p, batch, ctx=ctx, train=False, extra=extra)[0])(params)
        return compare(got, want, sl, rl, sg, rg)

    return jax.jit(check)


def reference_check(engine, cfg, rehearse: bool) -> dict:
    """The system against the configuration's plain reference on one seeded
    sequence at the configuration's own widths and the timed sequence
    length, outside the window: logits, loss, and the gradient, whole and
    leaf by leaf.  The system runs as it trains (bf16 compute, its kernels,
    ``module.loss_fn``); the reference in float32 at highest precision,
    differentiated by ``jax.grad``.

    First the program's warm start of the routing bias runs, as ``fit``
    would run it before the first step, over the first batches of the
    run's own loader (the window's loader goes on behind them), so the
    comparison also covers a bias that moves the router's choice.

    Two float32 gradient trees (2 x 2.8 GB) and the reference's float32
    activations do not fit beside 8.5 GB of training state, so for the
    length of the check the Adam moments, all zeros before the first step,
    are freed; ``tx.init`` makes them again before the engine trains."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddlefleetx_tpu.core.engine import TrainState
    from paddlefleetx_tpu.data.builders import build_dataloader

    config = common.load_cell(ARGS.workload)["config_data"]
    ref = _load("pfx_bench_reference", os.path.join(common.ROOT, config["reference"]))
    sizes = config["rehearse_model"] if rehearse else config["model"]
    s = int(cfg.Data.Train.dataset.max_seq_len)
    rng = np.random.default_rng(int(cfg.Global.seed) + 17)
    seq = rng.integers(1, int(cfg.Model.vocab_size), size=(1, s + 1))
    batch = {"tokens": jnp.asarray(seq[:, :-1], jnp.int32),
             "labels": jnp.asarray(seq[:, 1:], jnp.int32),
             "loss_mask": jnp.ones((1, s), jnp.float32)}
    loader = build_dataloader(cfg, "Train", consumed_samples=0)
    held = engine.warm_start(iter(loader))
    loader.close()
    balance = None if not held else {
        "passes": len(held), "held_pairs_a_layer_first_pass": [int(x) for x in held[0]],
        "held_pairs_a_layer_last_passes": [[int(x) for x in row] for row in held[-3:]]}
    state = engine.state
    extra = state.extra
    engine.state = TrainState(state.step, state.params, None, extra, state.scaler)
    jax.tree.map(lambda a: a.delete(), state.opt_state)
    try:
        out = jax.device_get(check_fn(engine.module, engine.ctx, ref, sizes,
                                      engine.module.config)(state.params, extra, batch))
    finally:
        engine.state = TrainState(
            state.step, state.params,
            jax.jit(engine.tx.init, out_shardings=engine.opt_shardings)(state.params),
            extra, state.scaler)
    return dict(verdict(out, state.params), tokens=int(s), rehearse=rehearse, balance=balance)


# ===========================================================================
# Parent half: train.judge, with the first-loss band of this configuration
# ===========================================================================


def judge(cell: dict, raw: dict, args) -> dict:
    res = base.judge(cell, raw, args)
    notes = [n for n in res["notes"] if not n.startswith("first loss ")]
    first, want = res["info"]["first_loss"], raw["reference"]["reference_loss"]
    if not abs(first - want) <= FIRST_LOSS_ABS_MAX:
        notes.append(f"first loss {first:.4f} more than {FIRST_LOSS_ABS_MAX} from the "
                     f"reference's {want:.4f} at the same weights")
    res["notes"], res["correct"] = notes, not notes and res["attempted"] > 0
    res["info"]["reference_grad_worst_leaf"] = [
        raw["reference"].get("grad_worst_leaf"), raw["reference"].get("grad_worst_leaf_cosine")]
    res["info"]["balance"] = raw["reference"].get("balance")
    recs = raw["records"]
    last = raw["warmup_steps"] + raw["window_steps"]
    a, b = cell["traffic_data"]["trace_steps"]
    if args.trace == 1:  # traced in mid-window: steps warm-up + a + 1 .. warm-up + b
        lo, hi = raw["warmup_steps"] + a, raw["warmup_steps"] + b
    else:  # --trace 2: the last b - a steps after the window
        hi = recs[-1]["step"] if recs else last
        lo = max(last, hi - (b - a))
    traced = [r for r in recs if lo < r["step"] <= hi]
    res["context"].update({
        "math": cell["config_data"].get("math"),
        "global_batch_size": raw["global_batch_size"],
        "traced_records": traced if args.trace else [],
        "traced_base_record": next((r for r in recs if r["step"] == lo), None),
        "traced_steps": len(traced) if args.trace else 0,
    })
    return res


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--t0", type=float, default=0.0)
    ap.add_argument("--result", required=True)
    ap.add_argument("--rehearse", action="store_true")
    ARGS = ap.parse_args()
    base.reference_check = reference_check  # the one literal of train.child
    sys.exit(base.child(ARGS))
