"""The chip-owning child of ``runners/serve_paged.py``: ``serve_arch_child.py``
itself, run unchanged from its file (no copy of it), as ``serve_deep_child.py``
runs it, WITHOUT the state's part (``runners/serve_paged.md``):

- the three limits of its reference check come from the configuration's file
  (``reference_limits``: ``band_in_spreads``, ``past_band_share_max``,
  ``argmax_agree_min``);
- a router without a bias (``moe_gate: softmax``) gets none: the balance loop
  that ``serve_arch_child.py`` runs before warm-up moves a bias this rule never
  reads, so it could not end; it is replaced by nothing;
- a control that the configuration's REFERENCE names (its ``CONTROLS``) reaches
  it: ``PFX_SERVE_ARCH_CONTROL=window_off`` teacher-forces the served sequences
  through the reference with that control on, and has to come back not ok;
- one more number decides ``correct`` where the configuration states its limit
  (``reference_limits.route_weight_err_max``): the precision the router's
  arithmetic runs in, which served tokens cannot see (a held expert swapped
  for its near-tie moves a token's logits by little: the reference with its
  router in bfloat16 reads as a sound run does by every share of tokens).
  :func:`route_verdict` puts the reference's own float32 input of the first
  expert layer through the program's routing rule and the reference's, so
  that no drift of the layers before it is in the number, only the rule's
  arithmetic: float32 errs by about 1e-7, bfloat16 by about 1e-3.

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


def _reference(config: dict):
    spec = importlib.util.spec_from_file_location(
        "pfx_bench_reference_paged", os.path.join(ROOT, config["reference"]))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    return ref


def reference_controls(config: dict) -> tuple:
    """The controls the configuration's reference module names."""
    return tuple(getattr(_reference(config), "CONTROLS", ()))


def route_verdict(server, served, sizes, config, limit: float) -> dict:
    """The router's arithmetic, apart from every drift before it.  Over prompt
    + served tokens of every judged sequence the reference's float32 normed
    input of the first expert layer goes through the PROGRAM's routing rule
    (``moe.route(cfg)``: the function every served program routes by, on the
    served router matrix) and through the reference's: a token's error is the
    largest difference between the two sides' weights over all experts (0
    where neither chose it), and the number judged is the MEDIAN token's (a
    near-tie that float32 rounding swaps moves one token, not the median)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddlefleetx_tpu.models.gpt import moe

    ref, cfg = _reference(config), server.module.config
    ctx_len = int(cfg.max_position_embeddings)
    first = cfg.layer_pattern.index("E")

    def spread(idx, w):  # [s, k] -> [s, experts]
        return jnp.zeros((idx.shape[0], cfg.num_experts), jnp.float32).at[
            jnp.arange(idx.shape[0])[:, None], idx].add(w)

    @jax.jit
    def errors(params, tokens):
        m, want_idx, want_w = ref.first_router(params, tokens, sizes)
        mlp = params["blocks"][first]["mlp"]
        idx, w = moe.route(cfg)(m[0], mlp["router_kernel"], mlp["e_score_correction_bias"], cfg)
        got, want = spread(idx, w), spread(want_idx[0], want_w[0])
        return jnp.max(jnp.abs(got - want), axis=-1), jnp.all((got > 0) == (want > 0), axis=-1)

    errs, same = [], []
    for seq in served:
        full = (list(seq["prompt_ids"]) + list(seq["tokens"]))[:ctx_len]
        tokens = np.zeros((1, ctx_len), np.int32)  # right padding: causal, so unseen
        tokens[0, :len(full)] = full
        err, kept = errors(server.params, jnp.asarray(tokens))
        errs.append(np.asarray(err)[:len(full)])
        same.append(np.asarray(kept)[:len(full)])
    if not errs:
        return {"ok": False, "tokens": 0}
    errs, same = np.concatenate(errs), np.concatenate(same)
    median = float(np.median(errs))
    return {"ok": bool(median <= limit), "tokens": int(errs.size), "weight_err_median": median,
            "weight_err_max": limit, "weight_err_p99": float(np.quantile(errs, 0.99)),
            "same_set_share": float(same.mean())}


def main(argv):
    """``tools.serve.main`` under the configuration's limits and controls."""
    missing = [n for n in NAMES if n not in arch]
    if missing:  # before the server boots, not after its window
        raise SystemExit(f"{PARENT} no longer has {missing}: runners/serve_paged_child.py reads them")
    if arch["SIZES"].get("moe_gate") == "softmax":
        arch["make_routing_bias"] = lambda server: {"passes": 0}  # the rule reads no bias
    known = reference_controls(arch["CONFIG"])
    judged = arch["reference_check"]

    def reference_check(server, served, control=""):
        """serve_arch_child's verdict, the reference told of its own control."""
        sizes = arch["SIZES"]
        if control in known:
            arch["SIZES"] = dict(sizes, control=control)
        try:
            verdict = judged(server, served, control)
            if "route_weight_err_max" in LIMITS:
                try:
                    verdict["route"] = route_verdict(server, served, arch["SIZES"], arch["CONFIG"],
                                                     float(LIMITS["route_weight_err_max"]))
                except Exception as e:  # noqa: BLE001 — the verdict says what went wrong
                    verdict["route"] = {"ok": False, "error": repr(e)[:1000]}
                verdict["ok"] = bool(verdict.get("ok") and verdict["route"]["ok"])
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
        LIMITS = json.load(_f)["reference_limits"]

    import tools.serve as serve  # (applies the platform pin on import)

    arch = {"__name__": "__main__", "__file__": PARENT}  # serve_arch_child.py's globals
    _main, serve.main = serve.main, main
    with open(PARENT) as _f:
        exec(compile(_f.read(), PARENT, "exec"), arch)
