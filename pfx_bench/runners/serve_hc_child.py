"""The chip-owning child of ``runners/serve_hc.py``: ``serve_arch_child.py``
itself, run unchanged from its file (no copy of it), as ``serve_paged_child.py``
runs it, for a configuration whose residual stream is several copies mixed by
maps computed from the stream (``runners/serve_hc.md``):

- a program without the configuration's residual path (a parent commit: its
  ``GPTConfig`` has no ``hc_mult`` and would silently serve ONE stream) is
  refused before the server boots, in seconds, by the missing field's name;
- the three limits of its reference check come from the configuration's file
  (``reference_limits``: ``band_in_spreads``, ``past_band_share_max``,
  ``argmax_agree_min``); the routing bias is made as ``serve_arch_child.py``
  makes it;
- a control that the configuration's REFERENCE names (its ``CONTROLS``) reaches
  it: ``PFX_SERVE_ARCH_CONTROL=hc_off`` teacher-forces the served sequences
  through the reference with that control on, and has to come back not ok;
- one more number decides ``correct`` (``reference_limits.hc_map_err_max``):
  the precision the maps' arithmetic runs in, which served tokens see only
  through twelve sub-blocks of bfloat16 drift.  :func:`hc_verdict` puts the
  reference's own float32 stream at the input of the SECOND layer through
  the PROGRAM's maps (``ops/hyper_connection.hc_pre``: on the chip, the
  kernel every served program runs) and through the reference's, so that no
  drift of the layers before it is in the number, only the maps' arithmetic:
  float32 at full product precision errs by roundings, bfloat16 by a
  thousand times more, one Sinkhorn round for twenty by more still.

How: ``tools.serve.main`` is wrapped before ``serve_arch_child.py`` runs; the
names read or set in its globals are ``NAMES``, and this file stops with a
message if one is missing."""

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
         "make_routing_bias")
HC_LAYER = 1  # the maps judged are those of this layer's attention sub-block


def _reference(config: dict):
    spec = importlib.util.spec_from_file_location(
        "pfx_bench_reference_hc", os.path.join(ROOT, config["reference"]))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    return ref


def reference_controls(config: dict) -> tuple:
    """The controls the configuration's reference module names."""
    return tuple(getattr(_reference(config), "CONTROLS", ()))


def program_fields(sizes: dict) -> list:
    """The keys of the configuration's ``model`` group that the program's
    ``GPTConfig`` does not have (it ignores what it does not know)."""
    import dataclasses

    from paddlefleetx_tpu.models.gpt.config import GPTConfig

    known = {f.name for f in dataclasses.fields(GPTConfig)}
    return sorted(k for k in sizes if k not in known)


def hc_verdict(server, served, sizes, config, limit: float) -> dict:
    """The maps' arithmetic, apart from every drift before it.  Over prompt +
    served tokens of every judged sequence the reference's float32 stream at
    the input of layer ``HC_LAYER`` goes through the PROGRAM's maps (``hc_pre``
    with the served ``phi`` / ``alpha`` / ``bias`` of that layer's attention
    sub-block) and through the reference's: a token's error is the largest
    difference in ``H_res`` (its 16 numbers lie in (0, 1) and have been
    through ``exp`` and forty normalisations), and the number judged is the
    MEDIAN token's; ``h_pre`` / ``h_post``'s largest difference rides along."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddlefleetx_tpu.ops.hyper_connection import hc_pre

    ref, cfg = _reference(config), server.module.config
    ctx_len, n = int(cfg.max_position_embeddings), int(cfg.hc_mult)

    @jax.jit
    def errors(params, tokens):
        x, want = ref.stream_at(params, tokens, sizes, layer=HC_LAYER)
        _, got = hc_pre(x[0], params["blocks"][HC_LAYER]["hc_attn"], cfg)
        miss = jnp.abs(got - want[0])
        return jnp.max(miss[:, 2 * n:], axis=-1), jnp.max(miss[:, :2 * n], axis=-1)

    res, gates = [], []
    for seq in served:
        full = (list(seq["prompt_ids"]) + list(seq["tokens"]))[:ctx_len]
        tokens = np.zeros((1, ctx_len), np.int32)  # right padding: causal, so unseen
        tokens[0, :len(full)] = full
        r, g = errors(server.params, jnp.asarray(tokens))
        res.append(np.asarray(r)[:len(full)])
        gates.append(np.asarray(g)[:len(full)])
    if not res:
        return {"ok": False, "tokens": 0}
    res, gates = np.concatenate(res), np.concatenate(gates)
    median = float(np.median(res))
    return {"ok": bool(np.isfinite(res).all() and median <= limit), "tokens": int(res.size),
            "layer": HC_LAYER, "h_res_err_median": median, "hc_map_err_max": limit,
            "h_res_err_p99": float(np.quantile(res, 0.99)), "h_res_err_worst": float(res.max()),
            "gates_err_median": float(np.median(gates))}


def main(argv):
    """``tools.serve.main`` under the configuration's limits and controls."""
    missing = [n for n in NAMES if n not in arch]
    if missing:  # before the server boots, not after its window
        raise SystemExit(f"{PARENT} no longer has {missing}: runners/serve_hc_child.py reads them")
    known = reference_controls(arch["CONFIG"])
    judged = arch["reference_check"]

    def reference_check(server, served, control=""):
        """serve_arch_child's verdict, the reference told of its own control,
        and the maps' part; ``ok`` only if both are."""
        sizes = arch["SIZES"]
        if control in known:
            arch["SIZES"] = dict(sizes, control=control)
        try:
            verdict = judged(server, served, control)
            try:
                verdict["hc"] = hc_verdict(server, served, arch["SIZES"], arch["CONFIG"],
                                           float(LIMITS["hc_map_err_max"]))
            except Exception as e:  # noqa: BLE001 — the verdict says what went wrong
                verdict["hc"] = {"ok": False, "error": repr(e)[:1000]}
            verdict["ok"] = bool(verdict.get("ok") and verdict["hc"]["ok"])
            return verdict
        finally:
            arch["SIZES"] = sizes

    arch["reference_check"] = reference_check
    rc = _main(argv)
    arch["BAND"] = float(LIMITS["band_in_spreads"])
    arch["PAST_BAND_SHARE_MAX"] = float(LIMITS["past_band_share_max"])
    arch["ARGMAX_AGREE_MIN"] = float(LIMITS["argmax_agree_min"])
    return rc


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    with open(os.path.join(BENCH, "configs", f"{sys.argv[4]}.json")) as _f:
        _config = json.load(_f)
    LIMITS = _config["reference_limits"]

    import tools.serve as serve  # (applies the platform pin on import)

    _unknown = program_fields(_config["rehearse_model" if sys.argv[5] == "rehearse" else "model"])
    if _unknown:
        raise SystemExit(f"unknown Model field(s) {_unknown}: this program's GPTConfig does not "
                         f"have them and would serve {sys.argv[4]} without them")

    arch = {"__name__": "__main__", "__file__": PARENT}  # serve_arch_child.py's globals
    _main, serve.main = serve.main, main
    with open(PARENT) as _f:
        exec(compile(_f.read(), PARENT, "exec"), arch)
