"""Train runner.  Parent half (stdlib): start one chip-owning child, read
what it wrote.  Child half (``--child``): config -> mesh -> module -> Engine
-> loader exactly as tools/train.py builds them, overrides from the cell's
data files only; warm-up steps, then ``Engine.fit`` over a loader wrapper
that stops handing out batches when the window's seconds are spent.  With
``--trace 2`` the wrapper, once the window is closed and every number of a
``--trace 0`` run is taken, arms the engine's profiler in the running fit
(a throwaway window first, so the profiler's first start falls into no
number) and hands out the few more batches the traced steps need."""

import argparse
import json
import math
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import common  # noqa: E402
from common import Fail  # noqa: E402


# ===========================================================================
# Parent half
# ===========================================================================


def run(cell: dict, args, t0: float) -> dict:
    """Returns the run's raw results (see ``child`` for the keys)."""
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
# Child half: owns the chip(s) for its lifetime
# ===========================================================================


# the Python call tracer of the --trace 2 captures (PERF.md section 6, PR 24:
# off, the program's pfx.* spans and the runtime's events name the gaps)
PYTHON_TRACER = False


class WindowLoader:
    """Hands the engine ``warmup`` batches, then batches for ``seconds``
    seconds.  The window opens when the first post-warm-up batch is asked
    for (the device is drained first) and closes, after a
    ``block_until_ready`` on the train state, when a batch is asked for
    after the seconds are spent.  ``trace`` = (directory, steps) keeps the
    iteration going after the close: ``engine.profiler`` is armed for one
    throwaway step, then for ``steps`` steps into the directory (each
    window needs one more batch than it traces: it starts at the step
    boundary after the arming)."""

    def __init__(self, inner, engine, warmup: int, seconds: float, compile_count,
                 trace=None):
        self.inner, self.engine = inner, engine
        self.warmup, self.seconds = warmup, seconds
        self.compile_count = compile_count
        self.handed = 0
        self.window_steps = 0  # steps dispatched inside the window
        # after the close: [(log_dir, steps)] still to arm, batches still owed
        self._to_trace = []
        if trace is not None:
            trace_dir, steps = trace
            self._to_trace = [(trace_dir + "-first-start", 1), (trace_dir, steps)]
        self._owed = 0
        self.t_start = self.t_end = None  # time.time()
        self.m_start = self.m_end = None  # time.monotonic()
        self.compiles_start = self.compiles_end = None
        self.exhausted = False
        self._it = None

    def __getattr__(self, name):  # stats / close / skips / rewind of the real loader
        return getattr(self.inner, name)

    def __iter__(self):
        self._it = iter(self.inner)
        return self

    def _fence(self):
        import jax

        jax.block_until_ready(self.engine.state)

    def __next__(self):
        if self.handed == self.warmup:
            self._fence()
            self.t_start, self.m_start = time.time(), time.monotonic()
            self.compiles_start = self.compile_count()
        elif self.m_end is not None or (
                self.handed > self.warmup
                and time.monotonic() - self.m_start >= self.seconds):
            self._close_window()
            if not self._owed:
                if not self._to_trace:
                    raise StopIteration
                log_dir, steps = self._to_trace.pop(0)
                self.engine.profiler.arm(log_dir, steps, python_tracer=PYTHON_TRACER,
                                         summary=False)
                self._owed = steps + 1
            self._owed -= 1
        try:
            batch = next(self._it)
        except StopIteration:
            self.exhausted = self.m_end is None  # inside the window, or before it
            if self.m_start is not None:
                self._close_window()
            raise
        self.handed += 1
        return batch

    def _close_window(self):
        if self.m_end is None:
            self._fence()
            self.t_end, self.m_end = time.time(), time.monotonic()
            self.compiles_end = self.compile_count()
            self.window_steps = max(0, self.handed - self.warmup)


def _load_reference():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "pfx_bench_reference_gpt", os.path.join(BENCH, "reference", "gpt.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    return ref


def backward_fn(module, ctx, ref):
    """One program that returns five scalars (system loss, reference loss,
    <g_sys, g_ref>, |g_sys|, |g_ref|): the two gradient trees are its
    temporaries, never live arrays, so the device's memory peak stays the
    training's own."""
    import jax
    import jax.numpy as jnp

    def backward(p, tokens, labels, mask):
        batch = {"tokens": tokens, "labels": labels, "loss_mask": mask}
        sl, sg = jax.value_and_grad(
            lambda q: module.loss_fn(q, batch, ctx=ctx, train=False))(p)
        rl, rg = jax.value_and_grad(lambda q: ref.loss(q, tokens, labels, mask))(p)
        flat = lambda t: [x.astype(jnp.float32) for x in jax.tree.leaves(t)]  # noqa: E731
        dot = sum(jnp.vdot(a, b) for a, b in zip(flat(sg), flat(rg)))
        n2 = lambda t: sum(jnp.vdot(a, a) for a in flat(t))  # noqa: E731
        return sl, rl, dot, jnp.sqrt(n2(sg)), jnp.sqrt(n2(rg))

    return backward


def reference_check(engine, cfg, rehearse: bool) -> dict:
    """The system against the plain reference on one seeded sequence at the
    configuration's own widths, outside the window: the logits of a forward
    pass, and the loss and its gradient (the backward pass: flash backward,
    the recomputed forward, the CE head).  The system runs as it trains
    (bf16 compute, its attention kernel, its sharding, ``module.loss_fn``),
    without dropout; the reference runs in float32 at highest matmul
    precision and differentiates itself with ``jax.grad``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddlefleetx_tpu.models.gpt import model as gpt

    ref = _load_reference()
    s = int(cfg.Data.Train.dataset.max_seq_len)
    vocab = int(cfg.Model.vocab_size)
    rng = np.random.default_rng(int(cfg.Global.seed) + 17)
    seq = rng.integers(1, vocab, size=(1, s + 1))
    tokens, labels = jnp.asarray(seq[:, :-1], jnp.int32), jnp.asarray(seq[:, 1:], jnp.int32)
    mask = jnp.ones((1, s), jnp.float32)
    mcfg, ctx, module = engine.module.config, engine.ctx, engine.module
    params = engine.state.params
    got = jax.jit(lambda p, t: gpt.forward(p, t, mcfg, ctx=ctx, train=False))(params, tokens)
    want = jax.jit(ref.logits)(params, tokens)
    got32 = got.astype(jnp.float32)
    err = float(jnp.max(jnp.abs(got32 - want)))
    scale = float(jnp.std(want))
    agree = float(jnp.mean(jnp.argmax(got32, -1) == jnp.argmax(want, -1)))
    del got, got32, want
    # bf16 keeps 8 bits: each of the ~100 roundings between embedding and
    # logits is 2^-9 relative, and they add like a random walk, so the
    # system sits within a tenth or so of a logit's spread from the
    # float32 reference.  A wrong mask, scale, GELU variant or a dropped
    # layer moves logits by about their whole spread (>= 0.5 of it).
    band = 0.15 * scale

    sl, rl, dot, sn, rn = (float(x) for x in jax.jit(backward_fn(module, ctx, ref))(
        params, tokens, labels, mask))
    cosine = dot / (sn * rn) if sn > 0 and rn > 0 else float("nan")
    norm_rel = abs(sn - rn) / rn if rn > 0 else float("nan")
    # the gradient is a sum over ~1000 positions of bf16-rounded terms: the
    # system's sits within a few percent of the reference's in length and
    # direction.  A wrong dq, dk or dv, a missing recomputed term or a
    # mis-scaled head turns whole leaves, which shows as a cosine far
    # under 1 or a norm off by tens of percent.
    grad_ok = bool(math.isfinite(cosine) and cosine >= GRAD_COSINE_MIN
                   and norm_rel <= GRAD_NORM_REL_MAX and abs(sl - rl) <= LOSS_ABS_MAX)
    return {"max_abs_err": err, "logit_std": scale, "band": band,
            "argmax_agree": agree, "tokens": int(s), "rehearse": rehearse,
            "loss": sl, "reference_loss": rl, "grad_norm": sn, "reference_grad_norm": rn,
            "grad_norm_rel_diff": norm_rel, "grad_cosine": cosine,
            "grad_bands": {"cosine_min": GRAD_COSINE_MIN, "norm_rel_max": GRAD_NORM_REL_MAX,
                           "loss_abs_max": LOSS_ABS_MAX},
            "logits_ok": bool(math.isfinite(err) and err <= band), "grad_ok": grad_ok,
            "ok": bool(math.isfinite(err) and err <= band and grad_ok)}


GRAD_COSINE_MIN, GRAD_NORM_REL_MAX, LOSS_ABS_MAX = 0.98, 0.05, 0.02


def child(args) -> int:
    root = common.ROOT
    sys.path.insert(0, root)
    from paddlefleetx_tpu.utils.device import apply_platform_env, device_identity

    apply_platform_env()
    try:
        ident = device_identity()
    except RuntimeError as e:
        print(f"no accelerator: {str(e).splitlines()[0]}", flush=True)
        return 3
    cell = common.load_cell(args.workload)
    chips = int(cell["chips"])
    want_platform = "cpu" if args.rehearse else "tpu"
    if ident["platform"] != want_platform or ident["device_count"] < chips:
        print(f"cell needs {chips} {want_platform} device(s), jax found {ident}", flush=True)
        return 3

    import jax

    from paddlefleetx_tpu.core.engine import Engine
    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.data.builders import build_dataloader
    from paddlefleetx_tpu.data.gpt_dataset import write_synthetic_corpus
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import get_config
    from paddlefleetx_tpu.utils.log import advertise
    from paddlefleetx_tpu.utils.model_stats import (
        get_compile_watcher, install_compile_watcher)

    traffic, config = cell["traffic_data"], cell["config_data"]
    t = traffic["rehearse"] if args.rehearse else traffic
    batch, seq = int(t["global_batch_size"]), int(t["seq_len"])
    vocab = int((config["rehearse_model"] if args.rehearse else config["model"])["vocab_size"])
    warmup = int(traffic["warmup_steps"])
    max_steps = warmup + int(args.seconds * float(t["steps_per_s_hint"]) * 4) + 16

    work = common.work_dir(cell["name"])
    out = os.path.dirname(os.path.abspath(args.result))
    data_dir = os.path.join(work, "data")
    corpus = traffic["corpus"]
    tokens_needed = int(max_steps * batch * (seq + 1) * 0.4)
    write_synthetic_corpus(
        os.path.join(data_dir, "corp"), vocab_size=vocab,
        num_docs=max(int(corpus["min_docs"]), tokens_needed // int(corpus["mean_doc_len"])),
        mean_len=min(int(corpus["mean_doc_len"]), 8 * seq), seed=args.seed)
    metrics_path = os.path.join(out, "train_metrics.jsonl")
    trace_dir = os.path.join(work, "trace")
    overrides = common.train_overrides(cell, args.seed, args.rehearse) + [
        f"Data.Train.dataset.input_dir={data_dir}",
        f"Engine.max_steps={max_steps}",
        f"Engine.save_load.output_dir={os.path.join(work, 'out')}",
        f"Engine.metrics_file={metrics_path}",
    ]
    a, b = traffic["trace_steps"]
    if args.trace:
        import shutil

        for d in (trace_dir, trace_dir + "-first-start"):
            shutil.rmtree(d, ignore_errors=True)
    if args.trace == 1:
        overrides.append("Profiler={enable: True, scheduler: [%d, %d], log_dir: %s, "
                         "summary: False}" % (warmup + a, warmup + b, trace_dir))
    cfg = get_config(os.path.join(root, config["yaml"]), overrides=overrides)
    advertise()
    install_compile_watcher()
    watcher = get_compile_watcher()
    mesh = init_dist_env(cfg)
    module = build_module(cfg)
    with mesh:
        engine = Engine(cfg, module, mesh)
        ref = reference_check(engine, cfg, args.rehearse)
        print("reference: " + json.dumps(ref), flush=True)
        loader = WindowLoader(
            build_dataloader(cfg, "Train", consumed_samples=engine._consumed_samples),
            engine, warmup, float(args.seconds), lambda: len(watcher.snapshot()),
            trace=(trace_dir, b - a) if args.trace == 2 else None)
        engine.fit(loader, None)
        if args.trace == 2:
            shutil.rmtree(trace_dir + "-first-start", ignore_errors=True)
        if loader.m_start is not None and loader.m_end is None:
            # the engine stopped at max_steps before the seconds were spent
            loader.exhausted = True
            loader._close_window()

    with open(metrics_path) as f:
        recs = [r for r in map(json.loads, f) if "loss" in r and "step" in r]
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    window_s = (loader.m_end - loader.m_start) if loader.m_end else 0.0
    result = {
        "device": {"platform": ident["platform"], "kind": ident["device_kind"],
                   "count": ident["device_count"], "memory_peak_bytes": peak},
        "chips": chips, "global_batch_size": batch, "seq_len": seq,
        "warmup_steps": warmup, "window_steps": loader.window_steps,
        "window_s": window_s, "t_window_start": loader.t_start,
        "loader_exhausted": loader.exhausted,
        "compiles_in_window": (None if loader.compiles_end is None
                               else loader.compiles_end - loader.compiles_start),
        "compile_events": len(watcher.snapshot()),
        "records": recs, "reference": ref,
        "trace_dir": trace_dir if args.trace else None,
        "traces_taken": engine.profiler.traces,
    }
    with open(args.result, "w") as f:
        json.dump(result, f)
    return 0


# ===========================================================================
# From the child's raw results to metrics and the verdict (parent, stdlib)
# ===========================================================================


def _step_times(records) -> list:
    """The engine's own per-step seconds over the window: says whether a
    slow run was slow in every step or stalled in one."""
    xs = sorted(r["step_s"] for r in records if "step_s" in r)
    return [xs[0], xs[len(xs) // 2], xs[-1]] if xs else []


def judge(cell: dict, raw: dict, args) -> dict:
    """-> {"correct", "attempted", "failed", "values", "notes", "context"}."""
    recs = raw["records"]
    warm = raw["warmup_steps"]
    last = warm + raw["window_steps"]
    window = [r for r in recs if warm < r["step"] <= last]
    traced = [r for r in recs if r["step"] > last]  # --trace 2: after the window
    notes = []
    bad_steps = [r["step"] for r in window
                 if not (math.isfinite(r["loss"]) and math.isfinite(r["grad_norm"]))
                 or r.get("found_inf")]
    if bad_steps:
        notes.append(f"non-finite loss or grad norm (update skipped) at steps {bad_steps[:8]}")
    if len(window) != raw["window_steps"] and not raw["loader_exhausted"]:
        notes.append(f"{raw['window_steps']} steps dispatched, {len(window)} records")
    first = recs[0]["loss"] if recs else float("nan")
    vocab = (cell["config_data"]["rehearse_model"] if args.rehearse
             else cell["config_data"]["model"])["vocab_size"]
    lo, hi = (10.7, 11.5) if not args.rehearse else (math.log(vocab) - 0.5, math.log(vocab) + 0.5)
    if not (lo <= first <= hi):
        notes.append(f"first loss {first:.4f} outside {lo:.2f}-{hi:.2f}")
    if raw["compiles_in_window"] != 0:
        notes.append(f"{raw['compiles_in_window']} compile event(s) inside the window")
    if raw["loader_exhausted"]:
        notes.append("the loader ran out before the window's seconds were spent: "
                     "raise steps_per_s_hint in the traffic file")
    if args.trace == 2 and raw.get("traces_taken") != 2:
        notes.append(f"{raw.get('traces_taken')} profiler window(s) closed after the "
                     "measured window, not the first start and the trace")
    if not raw["reference"]["ok"]:
        notes.append(f"logits or gradient off the plain reference: {raw['reference']}")
    want = "cpu" if args.rehearse else "tpu"
    if raw["device"]["platform"] != want:
        notes.append(f"ran on {raw['device']['platform']}, not {want}")
    steps = raw["window_steps"]
    tokens = steps * raw["global_batch_size"] * raw["seq_len"]
    values = {}
    if steps and raw["window_s"] > 0:
        values["train_tokens_per_s"] = tokens / raw["window_s"] / raw["chips"]
    base = next((r for r in recs if r["step"] == warm), None)
    return {
        "correct": not notes and steps > 0,
        "attempted": steps, "failed": len(bad_steps),
        "values": values, "notes": notes,
        "info": {"window_s": raw["window_s"], "steps": steps, "first_loss": first,
                 "last_loss": window[-1]["loss"] if window else None,
                 "reference_max_abs_err": raw["reference"]["max_abs_err"],
                 "reference_band": raw["reference"]["band"],
                 "reference_argmax_agree": raw["reference"]["argmax_agree"],
                 "reference_loss_diff": raw["reference"]["loss"] - raw["reference"]["reference_loss"],
                 "reference_grad_cosine": raw["reference"]["grad_cosine"],
                 "reference_grad_norm_rel_diff": raw["reference"]["grad_norm_rel_diff"],
                 "step_s_min_median_max": _step_times(window),
                 "traced_step_s": [r["step_s"] for r in traced if "step_s" in r]},
        "context": {"engine_records": window, "engine_base_record": base,
                    "window_s": raw["window_s"], "chips": raw["chips"],
                    "tokens_per_step": raw["global_batch_size"] * raw["seq_len"],
                    "seq_len": raw["seq_len"]},
        "t_window_start": raw["t_window_start"],
        "device": raw["device"], "trace_dir": raw["trace_dir"],
    }


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
    sys.exit(child(ap.parse_args()))
