"""The serve cells' chip-owning child: ``tools/serve.py``'s own ``main``,
unchanged, in the one process that holds the chip and the weights.  It adds
two things only that process can do:

- the device's peak memory, written to a file while the server runs (the
  server exports no device-memory gauge);
- after the server has drained and stopped, the reference check: the
  sequences the parent hands over (prompt and the tokens the server
  streamed for it) are teacher-forced through the plain float32 reference
  (reference/gpt.py) with the very weights the server served from, and
  every served token has to be the reference's greedy choice or within a
  band of it.

Usage: serve_child.py <mem.json> <served.json> <verdict.json> <serve.py arguments>."""

import importlib.util
import json
import math
import os
import sys
import threading

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

mem_path, served_path, verdict_path, argv = sys.argv[1], sys.argv[2], sys.argv[3], sys.argv[4:]

import tools.serve as serve  # noqa: E402  (applies the platform pin on import)

_built = threading.Event()
_server = []
_build_server = serve.build_server


def build_server(*a, **kw):
    try:
        _server.append(_build_server(*a, **kw))
        return _server[0]
    finally:
        _built.set()  # the backend is up: memory_stats() starts nothing


serve.build_server = build_server


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
            print(f"[serve_child] memory sample failed: {e}", flush=True)
        if _stop.wait(2.0):
            return


def reference_check(server, served) -> dict:
    """Each served token against the reference's logits at its position,
    given the prompt and the tokens served before it.  The server computes
    in bf16 over a bf16 KV cache, so its greedy choice may be a token whose
    float32 logit sits a little under the maximum: the band is the train
    cells' (0.15 of the logits' own spread).  A wrong mask, a stale or
    misplaced KV block, or a dropped layer picks tokens at random, which
    sit about four spreads under the maximum."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    spec = importlib.util.spec_from_file_location(
        "pfx_bench_reference_gpt", os.path.join(BENCH, "reference", "gpt.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)

    gen, ctx_len = server.gen, int(server.module.config.max_position_embeddings)

    @jax.jit
    def rows(params, tokens, at):
        return ref.logits(params, tokens)[0, at]  # [n, vocab] at the asked positions

    worst, spreads, agree, n_tok, misses = 0.0, [], 0, 0, []
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
                misses.append({"request": seq["idx"], "position": i, "token": tok,
                               "deficit_in_spreads": deficit})
        spreads.append(spread)
    ok = bool(n_tok and not misses and math.isfinite(worst))
    return {"ok": ok, "sequences": len(served), "tokens": n_tok, "band_in_spreads": BAND,
            "max_deficit_in_spreads": worst, "argmax_agree": agree / max(1, n_tok),
            "logit_std": sum(spreads) / max(1, len(spreads)), "misses": misses[:5]}


BAND = 0.15

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
            with open(served_path) as f:
                verdict = reference_check(_server[0], json.load(f))
        except Exception as e:  # noqa: BLE001 — the verdict says what went wrong
            verdict = {"ok": False, "error": repr(e)[:1000]}
        print("[serve_child] reference: " + json.dumps(verdict), flush=True)
        _write(verdict_path, verdict)
sys.exit(rc)
