"""The chip-owning child of ``runners/serve_deep.py``: ``serve_arch_child.py``
itself, run unchanged from its file (no copy of it), with two things that a
configuration with row state needs (``runners/serve_deep.md``):

- the three limits of its reference check come from the configuration's file
  (``reference_limits``: ``band_in_spreads``, ``past_band_share_max``,
  ``argmax_agree_min``);
- one more number decides ``correct``: the recurrent state that the SERVING
  ENGINE holds after a prefill and ``max_dec_len`` decode steps, in the first
  state-space layer, against the reference's sequential state over the same
  tokens, as the worst head's relative error (``state_error_worst_head_max``).
  Served tokens cannot see the precision the state is kept in; this can.

How: ``tools.serve.main`` is wrapped before ``serve_arch_child.py`` runs.  When
it returns (drained, the scheduler's thread gone, the engine and its pools
still there) the wrapper sets the limits in that module's globals, drives one
row through the engine, and wraps its ``reference_check`` so that the verdict
carries ``state`` and is ``ok`` only if both are.  The names it relies on are
``NAMES``: it stops with a message if ``serve_arch_child.py`` ever lacks one
(tests/test_nemotron_h_block.py holds the file to them too)."""

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
PARENT = os.path.join(HERE, "serve_arch_child.py")
# what this file reads or sets in serve_arch_child.py's globals
NAMES = ("BAND", "PAST_BAND_SHARE_MAX", "ARGMAX_AGREE_MIN", "reference_check", "CONFIG", "SIZES",
         "served_path", "_server", "_queues")
STEP_SLACK = 8  # steps past max_dec_len before the probe gives up


def engine_state(server, engine, prompt):
    """One row through the engine that served: the prefill that admits it and
    ``max_dec_len`` greedy decode steps -> (the tokens its state has read, the
    first state-space layer's state of its slot [heads, P, N] float32)."""
    import jax.numpy as jnp
    import numpy as np

    from paddlefleetx_tpu.ops.ssm import unpack_state

    cfg, steps = server.module.config, int(server.gen.max_dec_len)
    engine.flush()  # a scheduler that dispatches ahead leaves a step in flight: commit it first
    slot = engine.admit(list(prompt), steps)
    for _ in range(steps + STEP_SLACK):
        if slot in engine.step():
            break
    else:
        raise RuntimeError(f"the probe's row did not finish in {steps + STEP_SLACK} steps")
    engine.flush()
    out = list(engine.slots[slot].tokens)
    if len(out) != steps:
        raise RuntimeError(f"the probe's row was given {len(out)} tokens, not {steps}")
    # the pools' states are [state-space layers, slots, ...]: layer 0, this slot
    state = unpack_state(engine.pools.ssm[0, slot].astype(jnp.float32),
                         cfg.ssm_heads, cfg.ssm_head_dim)
    engine.release(slot)
    # a step samples a row's token from its pending logits and feeds it in the
    # same step: the state has read every token the row was given
    return list(prompt) + out, np.asarray(state)


def state_verdict(ref, params, sizes, tokens, got, limit: float) -> dict:
    """The engine's state against the sequential one of the reference module
    ``ref`` over the same tokens: each head's error over the head's size."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    want = np.asarray(jax.jit(lambda p, t: ref.first_state(p, t, sizes))(
        params, jnp.asarray([tokens], jnp.int32)))[0]
    heads = want.shape[0]
    miss = np.linalg.norm((got - want).reshape(heads, -1), axis=1)
    size = np.linalg.norm(want.reshape(heads, -1), axis=1)
    worst = float(np.max(miss / size))
    return {"ok": bool(np.isfinite(got).all() and worst <= limit), "tokens_read": len(tokens),
            "state_error_worst_head": worst, "state_error_worst_head_max": limit,
            "state_error": float(np.linalg.norm(miss) / np.linalg.norm(size))}


def main(argv):
    """``tools.serve.main``, then the limits and the state's part of the check."""
    missing = [n for n in NAMES if n not in arch]
    if missing:  # before the server boots, not after its window
        raise SystemExit(f"{PARENT} no longer has {missing}: runners/serve_deep_child.py reads them")
    rc = _main(argv)
    arch["BAND"] = float(LIMITS["band_in_spreads"])
    arch["PAST_BAND_SHARE_MAX"] = float(LIMITS["past_band_share_max"])
    arch["ARGMAX_AGREE_MIN"] = float(LIMITS["argmax_agree_min"])
    if not (arch["_server"] and os.path.exists(arch["served_path"])):
        return rc  # a window that judges no tokens (the knee's sweep)
    probe, failed = None, None
    try:
        with open(arch["served_path"]) as f:
            prompt = json.load(f)[0]["prompt_ids"]
        engine = next(q.engine for q in arch["_queues"] if getattr(q, "engine", None) is not None)
        probe = engine_state(arch["_server"][0], engine, prompt)
    except Exception as e:  # noqa: BLE001 — the verdict says what went wrong
        failed = {"ok": False, "error": repr(e)[:1000]}
    judged = arch["reference_check"]

    def reference_check(server, served, control=""):
        """serve_arch_child's verdict and the state's; ``ok`` only if both."""
        verdict = judged(server, served, control)
        try:
            # here, not above: by now serve_arch_child has freed the pools
            verdict["state"] = failed or state_verdict(
                _reference(), server.params, arch["SIZES"], *probe,
                float(LIMITS["state_error_worst_head_max"]))
        except Exception as e:  # noqa: BLE001
            verdict["state"] = {"ok": False, "error": repr(e)[:1000]}
        verdict["ok"] = bool(verdict.get("ok") and verdict["state"]["ok"])
        return verdict

    arch["reference_check"] = reference_check
    return rc


def _reference():
    spec = importlib.util.spec_from_file_location(
        "pfx_bench_reference_state", os.path.join(ROOT, arch["CONFIG"]["reference"]))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    return ref


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    with open(os.path.join(BENCH, "configs", f"{sys.argv[4]}.json")) as _f:
        LIMITS = json.load(_f)["reference_limits"]

    import tools.serve as serve  # (applies the platform pin on import)

    arch = {"__name__": "__main__", "__file__": PARENT}  # serve_arch_child.py's globals
    _main, serve.main = serve.main, main
    with open(PARENT) as _f:
        exec(compile(_f.read(), PARENT, "exec"), arch)
