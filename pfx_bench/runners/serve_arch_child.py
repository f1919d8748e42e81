"""The chip-owning child of ``runners/serve_arch.py``: ``tools/serve.py``'s own
``main`` in the one process that holds the chip and the weights, as
``serve_child.py``, with what a second block family needs:

- before warm-up (inside ``setup_s``) the routing bias is made from the seed:
  forward-only passes of the program's balance rule over seeded prompts from
  the vocabulary's slice, until no expert of any layer holds twice the mean
  (the configuration's ``assumed.routing_bias``);
- after the server has drained and stopped, the served sequences are
  teacher-forced through the plain reference the configuration names
  (``reference``), with the very weights (and bias) the server served from.

Usage: serve_arch_child.py <mem.json> <served.json> <verdict.json> <config> <real|rehearse>
<serve.py arguments>.  ``PFX_SERVE_ARCH_CONTROL`` (by hand only, PERF.md section
6): ``fp8_reference`` / ``no_group_step`` change the reference, ``decode_no_rope``
the program; each has to fail the check.  Several, comma-separated, are judged
one after the other on the same served sequences (the run's verdict is the
first's)."""

import gc
import importlib.util
import json
import math
import os
import sys
import threading
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

mem_path, served_path, verdict_path, config_name, mode = sys.argv[1:6]
argv = sys.argv[6:]
with open(os.path.join(BENCH, "configs", f"{config_name}.json")) as _f:
    CONFIG = json.load(_f)
SIZES = CONFIG["rehearse_model" if mode == "rehearse" else "model"]
CONTROLS = os.environ.get("PFX_SERVE_ARCH_CONTROL", "").split(",")

# The band, as the dense cells': a served token is the reference's greedy
# choice or within BAND of the logits' own spread of it.  An expert layer's
# top-k is a discontinuity: where bf16 rounding swaps a token's 8th and 9th
# expert and one of the two is held here, that token's logits move by a few
# tenths of the spread in any bf16 implementation, so a SHARE of the tokens
# may sit past the band (PAST_BAND_SHARE_MAX), and the served token has to
# BE the reference's choice for ARGMAX_AGREE_MIN of the tokens.  The largest
# single deficit is printed and limits nothing: it is the extreme of
# thousands of tokens, and the sound runs' (up to 0.81 spreads) lie among
# the controls' (1.12, 0.51; a token picked at random sits about four under).
# Readings: PERF.md section 6.
BAND, PAST_BAND_SHARE_MAX, ARGMAX_AGREE_MIN = 0.15, 0.01, 0.915
BIAS_PASSES_MAX, BIAS_PROMPT = 240, 4096
BIAS_RATE_FIRST, BIAS_RATE_LAST, BIAS_RATE_PASSES = 0.04, 0.001, 64
BIAS_JUDGED_PASSES = 4  # the load is judged over this many passes together

import tools.serve as serve  # noqa: E402  (applies the platform pin on import)

_built = threading.Event()
_server, _queues = [], []
_build_server, _build_scheduler = serve.build_server, serve.build_scheduler


def _seed() -> int:
    for a in argv:
        if a.startswith("Global.seed="):
            return int(a.split("=", 1)[1])
    return 0


def make_routing_bias(server) -> dict:
    """The routing bias of every expert layer, from the seed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddlefleetx_tpu.models.gpt import generation, moe

    cfg, params = server.module.config, server.params
    layers = [b["mlp"] for b in params["blocks"] if "router_kernel" in b["mlp"]]
    if not layers:
        return {"passes": 0}
    rng = np.random.default_rng([_seed(), 0xB1A5])
    n = min(BIAS_PROMPT, int(cfg.max_position_embeddings))
    load_fn = jax.jit(lambda p, t: generation.expert_load(p, t, cfg))
    t0 = time.time()
    worst, passes, recent = math.inf, 0, []
    for i in range(BIAS_PASSES_MAX):
        tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, size=(1, n)), jnp.int32)
        load = load_fn(params, tokens)
        # one batch's fullest expert is an extreme of 1,536 noisy counts:
        # the last few passes' loads are judged together
        recent = (recent + [load])[-BIAS_JUDGED_PASSES:]
        seen = sum(recent).astype(jnp.float32)
        worst = float(jnp.max(seen / jnp.mean(seen, axis=-1, keepdims=True)))
        passes = i
        if worst < 2.0 and i >= 8:
            break
        frac = min(i, BIAS_RATE_PASSES - 1) / (BIAS_RATE_PASSES - 1)
        rate = BIAS_RATE_FIRST * (BIAS_RATE_LAST / BIAS_RATE_FIRST) ** frac
        bias = jnp.stack([m["e_score_correction_bias"] for m in layers])
        bias = moe.next_expert_bias(bias, load, rate)
        for m, b in zip(layers, bias):
            m["e_score_correction_bias"] = b
    out = {"passes": passes, "fullest_over_mean": worst, "seconds": round(time.time() - t0, 1),
           "bias_abs_max": float(max(jnp.max(jnp.abs(m["e_score_correction_bias"]))
                                     for m in layers))}
    print("[serve_arch_child] routing bias: " + json.dumps(out), flush=True)
    if not worst < 2.0:
        raise RuntimeError(f"the routing bias did not balance the experts: {out}")
    return out


def build_server(*a, **kw):
    try:
        _server.append(_build_server(*a, **kw))
        make_routing_bias(_server[0])
        if "decode_no_rope" in CONTROLS:
            _decode_without_rope()
        return _server[0]
    finally:
        _built.set()  # the backend is up: memory_stats() starts nothing


def build_scheduler(*a, **kw):
    _queues.append(_build_scheduler(*a, **kw))
    return _queues[-1]


serve.build_server, serve.build_scheduler = build_server, build_scheduler


def _decode_without_rope():
    """Control: the decode kernel scores without the rotated part."""
    from paddlefleetx_tpu.models.gpt import generation

    real = generation.mla_paged_decode_attention

    def without(q, pool, tables, positions, *, kv_lora, **kw):
        return real(q.at[..., kv_lora:].set(0), pool, tables, positions, kv_lora=kv_lora, **kw)

    generation.mla_paged_decode_attention = without


def _write(path, obj):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


def write_peak():
    import jax

    peak = 0
    for d in jax.local_devices():
        peak = max(peak, int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)))
    _write(mem_path, {"memory_peak_bytes": peak})


def sampler():
    _built.wait()
    while True:
        try:
            write_peak()
        except Exception as e:  # noqa: BLE001 — never take the server down
            print(f"[serve_arch_child] memory sample failed: {e}", flush=True)
        if _stop.wait(2.0):
            return


def reference_check(server, served, control="") -> dict:
    """Each served token against the reference's logits at its position,
    given the prompt and the tokens served before it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    spec = importlib.util.spec_from_file_location(
        "pfx_bench_reference", os.path.join(ROOT, CONFIG["reference"]))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)

    gen, ctx_len = server.gen, int(server.module.config.max_position_embeddings)
    kw = {}
    if control == "fp8_reference":
        kw["weight_dtype"] = jnp.float8_e4m3fn
    if control == "no_group_step":
        kw["group_step"] = False

    @jax.jit
    def rows(params, tokens, at):
        return ref.logits(params, tokens, SIZES, at=at, **kw)[0]  # [n, vocab]

    worst, spreads, agree, n_tok, past, misses = 0.0, [], 0, 0, 0, []
    for seq in served:
        prompt, out = list(seq["prompt_ids"]), list(seq["tokens"])
        full = prompt + out
        if len(full) > ctx_len + 1:
            return {"ok": False, "error": f"request {seq['idx']}: {len(full)} tokens > context"}
        tokens = np.zeros((1, ctx_len), np.int32)
        tokens[0, :len(full) - 1] = full[:-1]  # right padding: causal, so unseen
        at = np.arange(len(prompt) - 1, len(full) - 1, dtype=np.int32)
        at = np.pad(at, (0, gen.max_dec_len - len(at)), mode="edge")  # one compiled shape
        lg = np.asarray(rows(server.params, jnp.asarray(tokens), jnp.asarray(at)))[:len(out)]
        spread = float(lg.std())
        for i, tok in enumerate(out):
            row = lg[i].copy()
            if i < gen.min_dec_len:
                row[gen.eos_token_id] = -np.inf  # the server may not end here either
            deficit = float(row.max() - row[tok]) / spread
            agree += int(row.argmax() == tok)
            n_tok += 1
            worst = max(worst, deficit)
            if not deficit <= BAND:
                past += 1
                if len(misses) < 8:
                    misses.append({"request": seq["idx"], "position": i, "token": tok,
                                   "deficit_in_spreads": deficit})
        spreads.append(spread)
    share, agreed = past / max(1, n_tok), agree / max(1, n_tok)
    ok = bool(n_tok and share <= PAST_BAND_SHARE_MAX and agreed >= ARGMAX_AGREE_MIN
              and math.isfinite(worst))
    return {"ok": ok, "sequences": len(served), "tokens": n_tok, "band_in_spreads": BAND,
            "past_band_share": share, "past_band_share_max": PAST_BAND_SHARE_MAX,
            "max_deficit_in_spreads": worst,
            "argmax_agree": agreed, "argmax_agree_min": ARGMAX_AGREE_MIN, "control": control,
            "logit_std": sum(spreads) / max(1, len(spreads)), "misses": misses}


_stop = threading.Event()
threading.Thread(target=sampler, daemon=True).start()
rc = 1
try:
    rc = serve.main(argv)
finally:
    _stop.set()
    if _built.is_set():
        try:
            write_peak()
        except Exception:  # noqa: BLE001
            pass
    if _server and os.path.exists(served_path):
        try:
            # the reference's float32 layer and activations need the room
            # the arena and the rows' logits took
            for q in _queues:
                engine = getattr(q, "engine", None)
                if engine is not None:
                    engine.pools = engine._logits = engine._counts = None
                    engine._compiled_step.clear()
                    engine._compiled_prefill.clear()
            gc.collect()
            with open(served_path) as f:
                served = json.load(f)
            verdicts = [reference_check(_server[0], served, c) for c in CONTROLS]
        except Exception as e:  # noqa: BLE001 — the verdict says what went wrong
            verdicts = [{"ok": False, "error": repr(e)[:1000]}]
        for verdict in verdicts:
            print("[serve_arch_child] reference: " + json.dumps(verdict), flush=True)
        _write(verdict_path, verdicts[0])
sys.exit(rc)
