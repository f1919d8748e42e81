#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the full width and depth of GPT-345M (24 x 1024, vocab 50304, seq 1024,
bf16; random weights and a synthetic corpus, both from ``--seed``):

  kernels  every Pallas kernel of the default paths, COMPILED (interpret
           off), against its own lax/XLA reference on the chip
  train    tools/train.py -c configs/gpt/pretrain_gpt_345M_single.yaml,
           ~8 steps, then a checkpoint
  serve    tools/serve.py --scheduler continuous on that checkpoint, a
           handful of /generate requests (mixed lengths, some concurrent,
           one streamed), then the same requests through the default
           coalesce scheduler

``--chips 4`` runs ONLY the hybrid-parallel path and what it is compared
with: three dp2·mp2 (tensor + sequence parallel) steps of GPT-345M against
the same three steps on one device of the same process, then dp2·pp2.

The parent is pure standard-library Python and never imports jax: a chip
belongs to one process at a time, so each phase is a child that owns the
chip and has exited before the next starts.  Children run with
``PFX_PLATFORM=tpu`` — a machine without a chip fails in the first child's
first backend touch, in seconds, before any model is built, whatever
``JAX_PLATFORMS`` says.  The last line of stdout is one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

(``"ok": false`` and a non-zero exit on any failure).  ``--rehearse`` is
the CPU dress rehearsal: same phases, same checks, toy sizes, interpreted
kernels; its last line says ``"rehearsal": true`` and names the cpu device.
"""

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, "chip_smoke_out")            # corpus, checkpoint (GBs)
LOGS = os.path.join(ROOT, "chiprun_out", "chip_smoke")  # small; comes back
CONFIG = os.path.join("configs", "gpt", "pretrain_gpt_345M_single.yaml")
MARK = "@@smoke "  # a child's structured result lines

# --rehearse shrinks the MODEL; every phase, flag and check stays the same
TINY_MODEL = [
    "Model.num_layers=2", "Model.hidden_size=64", "Model.num_attention_heads=4",
    "Model.vocab_size=512", "Model.max_position_embeddings=128",
]


def shape_of(rehearse: bool) -> dict:
    if rehearse:
        return {"vocab": 512, "seq": 128, "batch": 4, "heads": 8,
                "head_dims": (16,), "hidden": 64, "overrides": TINY_MODEL + [
                    "Data.Train.dataset.max_seq_len=128",
                    "Global.global_batch_size=4", "Global.local_batch_size=4",
                    "Global.micro_batch_size=4"]}
    return {"vocab": 50304, "seq": 1024, "batch": 16, "heads": 16,
            "head_dims": (64, 128), "hidden": 1024, "overrides": []}


# ===========================================================================
# Parent: stdlib only
# ===========================================================================


class Fail(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def child_env(rehearse: bool, chips: int) -> dict:
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["PFX_PLATFORM"] = "cpu" if rehearse else "tpu"
    env.setdefault("TPU_LOG_DIR", "disabled")
    env["PYTHONUNBUFFERED"] = "1"
    if rehearse:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    return env


def run_child(name: str, argv: list, env: dict, timeout: float) -> dict:
    """Run one chip-owning child to its end; tee its output to our stdout
    and a log file; return its ``@@smoke`` records merged into one dict.
    Raises Fail on a non-zero exit or a timeout (the child is killed)."""
    log_path = os.path.join(LOGS, f"{name}.log")
    records: dict = {}
    t0 = time.time()
    proc = subprocess.Popen(
        argv, cwd=ROOT, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, start_new_session=True,
    )
    timer = threading.Timer(timeout, lambda: os.killpg(proc.pid, signal.SIGKILL))
    timer.start()
    try:
        with open(log_path, "w") as log:
            for line in proc.stdout:
                log.write(line)
                if line.startswith(MARK):
                    records.update(json.loads(line[len(MARK):]))
                    line = line[len(MARK):]
                elif "mesh placement:" in line:
                    records.setdefault("mesh_placement", []).append(
                        line.split("mesh placement:")[1].strip())
                say(f"[{name}] {line.rstrip()}")
        rc = proc.wait()
    finally:
        timer.cancel()
        if proc.poll() is None:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
    records["seconds"] = round(time.time() - t0, 1)
    if rc != 0:
        raise Fail(f"phase {name} exited {rc} after {records['seconds']}s "
                   f"(log: {log_path})")
    return records


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(port: int, path: str, body=None, timeout: float = 120.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def metric_values(text: str, name: str) -> dict:
    """{label-string: value} of one Prometheus metric family."""
    out = {}
    for line in text.splitlines():
        if line.startswith(name) and not line.startswith("#"):
            head, _, val = line.rpartition(" ")
            if head == name or head.startswith(name + "{"):
                out[head[len(name):]] = float(val)
    return out


def request_plan(seed: int, shape: dict) -> list:
    """The handful of requests both schedulers answer: mixed prompt
    lengths across two prompt buckets, every max_tokens <= max_dec_len so
    the warmed compile families cover all of them."""
    import random

    rng = random.Random(seed)
    if shape["seq"] >= 1024:
        lens, toks = [5, 23, 64, 100, 17, 70], [16, 32, 24, 32, 8, 32]
    else:
        lens, toks = [3, 9, 16, 20, 5, 12], [4, 8, 6, 8, 2, 8]
    return [
        {"prompt_ids": [rng.randrange(1, shape["vocab"]) for _ in range(n)],
         "max_tokens": t, "deadline_s": 300}
        for n, t in zip(lens, toks)
    ]


def check_completion(ids, want: int, vocab: int, what: str) -> None:
    if not (isinstance(ids, list) and len(ids) == want
            and all(isinstance(t, int) and 0 <= t < vocab for t in ids)):
        raise Fail(f"{what}: expected {want} token ids in [0, {vocab}), got {ids!r}")


def post_generate(port: int, req: dict, vocab: int, what: str) -> list:
    code, body = http(port, "/generate", req, timeout=400)
    if code != 200:
        raise Fail(f"{what}: HTTP {code}: {body[:300]}")
    ids = json.loads(body).get("completion_ids")
    check_completion(ids, req["max_tokens"], vocab, what)
    return ids


def post_generate_stream(port: int, req: dict, vocab: int, what: str):
    """One SSE request: tokens from the ``token`` frames, in index order."""
    code, body = http(port, "/generate?stream=1", req, timeout=400)
    if code != 200:
        raise Fail(f"{what}: HTTP {code}: {body[:300]}")
    toks, frames, event = {}, 0, None
    for line in body.splitlines():
        if line.startswith("event: "):
            event = line[len("event: "):]
        elif line.startswith("data: "):
            data = json.loads(line[len("data: "):])
            if event == "error":
                raise Fail(f"{what}: stream error frame {data}")
            if event == "token":
                frames += 1
                for i, t in enumerate(data["tokens"]):
                    toks[data["index"] + i] = t
    ids = [toks[i] for i in sorted(toks)]
    check_completion(ids, req["max_tokens"], vocab, what)
    return ids, frames


def serve_phase(name, scheduler, ckpt, seed, shape, env, want_device, timeout):
    """Start tools/serve.py on the checkpoint, wait for /healthz, send the
    request plan, check the books, stop it.  Returns (results, completions)."""
    port = free_port()
    gen = ("Generation={max_dec_len: %d, min_dec_len: %d, decode_strategy: "
           "greedy_search, eos_token_id: 0, pad_token_id: 0, pad_to_multiple: %d}"
           % ((32, 32, 64) if shape["seq"] >= 1024 else (8, 8, 16)))
    buckets = "8,64,128" if shape["seq"] >= 1024 else "4,16,32"
    argv = [sys.executable, os.path.join("tools", "serve.py"), "-c", CONFIG,
            "--port", str(port), "--replica-id", f"smoke-{scheduler}",
            "--warmup-buckets", buckets,
            "-o", f"Engine.save_load.ckpt_dir={ckpt}", "-o", gen]
    for o in shape["overrides"]:
        if o.startswith("Model."):
            argv += ["-o", o]
    if scheduler == "continuous":
        argv += ["--scheduler", "continuous"]
    else:  # the default scheduler; requests arrive one at a time
        argv += ["--warmup-batches", "1", "--max-coalesce", "1"]
    log_path = os.path.join(LOGS, f"{name}.log")
    t0 = time.time()
    res = {}
    with open(log_path, "w") as log:
        proc = subprocess.Popen(argv, cwd=ROOT, env=env, stdout=log,
                                stderr=subprocess.STDOUT, start_new_session=True)
        try:
            health = None
            while time.time() - t0 < timeout:
                if proc.poll() is not None:
                    raise Fail(f"{name}: serve.py exited {proc.returncode} "
                               f"before /healthz (log: {log_path})")
                try:
                    code, body = http(port, "/healthz", timeout=5)
                    if code == 200 and json.loads(body).get("ok"):
                        health = json.loads(body)
                        break
                except (OSError, ValueError):
                    pass
                time.sleep(1.0)
            if health is None:
                raise Fail(f"{name}: no /healthz within {timeout:.0f}s")
            res["boot_s"] = round(time.time() - t0, 1)
            ident = health["identity"]
            device = {"platform": ident["platform"], "kind": ident["device_kind"],
                      "count": ident["device_count"]}
            if device != want_device:
                raise Fail(f"{name}: /healthz device {device} != {want_device}")
            compiles0 = sum(metric_values(
                http(port, "/metrics")[1], "pfx_compile_events_total").values())

            plan = request_plan(seed, shape)
            outs = [None] * len(plan)
            t1 = time.time()
            if scheduler == "continuous":
                for i in (0, 1):
                    outs[i] = post_generate(port, plan[i], shape["vocab"],
                                            f"{name} request {i}")
                errs = []

                def one(i):
                    try:
                        outs[i] = post_generate(port, plan[i], shape["vocab"],
                                                f"{name} concurrent request {i}")
                    except Exception as e:  # noqa: BLE001 — re-raised below
                        errs.append(e)

                threads = [threading.Thread(target=one, args=(i,)) for i in (2, 3, 4)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=600)
                if errs or any(t.is_alive() for t in threads):
                    raise Fail(f"{name}: concurrent requests failed: {errs or 'hung'}")
                outs[5], frames = post_generate_stream(
                    port, plan[5], shape["vocab"], f"{name} streamed request")
                res["stream_frames"] = frames
            else:
                for i, req in enumerate(plan):
                    outs[i] = post_generate(port, req, shape["vocab"],
                                            f"{name} request {i}")
            res["requests"] = len(plan)
            res["traffic_s"] = round(time.time() - t1, 2)

            metrics = http(port, "/metrics")[1]
            res["compiles_after_warmup"] = int(sum(metric_values(
                metrics, "pfx_compile_events_total").values()) - compiles0)
            if scheduler == "continuous":
                led = {k.split('"')[1]: v for k, v in metric_values(
                    metrics, "pfx_token_ledger_total").items()}
                in_flight = sum(metric_values(
                    metrics, "pfx_token_ledger_in_flight").values())
                res["token_ledger"] = {**{k: int(v) for k, v in led.items()},
                                       "in_flight": int(in_flight)}
                booked = sum(v for k, v in led.items() if k != "admitted") + in_flight
                want = sum(r["max_tokens"] for r in plan)
                if led.get("admitted") != booked or led.get("delivered") != want:
                    raise Fail(f"{name}: token ledger does not close: "
                               f"{res['token_ledger']} (sent for {want})")
            if res["compiles_after_warmup"]:
                late = json.loads(http(port, "/debug/state")[1]).get(
                    "compile_events", [])[-res["compiles_after_warmup"]:]
                raise Fail(f"{name}: {res['compiles_after_warmup']} compile(s) "
                           f"after warmup: {json.dumps(late)}")
        finally:
            if proc.poll() is None:
                os.killpg(proc.pid, signal.SIGTERM)  # graceful drain
                try:
                    proc.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    os.killpg(proc.pid, signal.SIGKILL)
                    proc.wait()
    res["seconds"] = round(time.time() - t0, 1)
    say(f"[{name}] {json.dumps(res)}")
    return res, outs


def parent(args) -> int:
    result = {"ok": False, "device": None}
    if args.rehearse:
        result["rehearsal"] = True
    seconds = {}
    t_all = time.time()
    try:
        for needed in (CONFIG, os.path.join("tools", "train.py"),
                       os.path.join("tools", "serve.py"), "paddlefleetx_tpu"):
            if not os.path.exists(os.path.join(ROOT, needed)):
                raise Fail(f"{needed} not found beside chip_smoke.py: the smoke "
                           "drives the repository it sits in")
        shutil.rmtree(WORK, ignore_errors=True)
        os.makedirs(WORK)
        os.makedirs(LOGS, exist_ok=True)
        env = child_env(args.rehearse, args.chips)
        shape = shape_of(args.rehearse)
        me = [sys.executable, os.path.abspath(__file__), "--seed", str(args.seed)]
        if args.rehearse:
            me.append("--rehearse")
        want_platform = "cpu" if args.rehearse else "tpu"

        def check_device(dev, who):
            if dev is None or dev["platform"] != want_platform \
                    or dev["count"] != args.chips:
                raise Fail(f"{who} ran on {dev}; this run needs {args.chips} "
                           f"{want_platform} device(s)")
            if result["device"] not in (None, dev):
                raise Fail(f"{who} ran on {dev}, an earlier phase on "
                           f"{result['device']}")
            result["device"] = dev

        if args.chips == 4:
            r = run_child("multichip", me + ["--phase", "multichip"], env, 1500)
            check_device(r.get("device"), "multichip")
            seconds["multichip"] = r["seconds"]
            result["multichip"] = {k: r[k] for k in r if k not in ("device", "seconds")}
        else:
            # the first child is also the chip check: no accelerator -> it
            # dies in jax.devices(), seconds in, before any model exists
            r = run_child("kernels", me + ["--phase", "kernels"], env, 900)
            check_device(r.get("device"), "kernels")
            seconds["kernels"] = r["seconds"]
            result["kernel_max_abs_err"] = r.get("kernels")

            r = run_child("train", me + ["--phase", "train", "--steps",
                                         str(args.steps)], env, 1500)
            check_device(r.get("device"), "train")
            seconds["train"] = r["seconds"]
            result["train"] = {k: r[k] for k in r if k not in ("device", "seconds")}
            ckpt = r["checkpoint"]

            cb, outs_cb = serve_phase("serve_continuous", "continuous", ckpt,
                                      args.seed, shape, env, result["device"], 900)
            seconds["serve_continuous"] = cb["seconds"]
            co, outs_co = serve_phase("serve_coalesce", "coalesce", ckpt,
                                      args.seed, shape, env, result["device"], 900)
            seconds["serve_coalesce"] = co["seconds"]
            same = sum(a == b for x, y in zip(outs_cb, outs_co) for a, b in zip(x, y))
            total = sum(len(x) for x in outs_cb)
            # information, not a verdict: a bf16 argmax over near-uniform
            # logits (8 training steps from random init) may tip either way
            result["schedulers_agree"] = f"{same}/{total} greedy tokens"
            result["serve"] = {"continuous": cb, "coalesce": co}
        result["ok"] = True
    except Fail as e:
        result["error"] = str(e)
        say(f"FAILED: {e}")
    except Exception as e:  # noqa: BLE001 — the last line must still print
        result["error"] = f"{type(e).__name__}: {e}"
        say(f"FAILED: {result['error']}")
    finally:
        if not args.keep:
            shutil.rmtree(WORK, ignore_errors=True)
    seconds["total"] = round(time.time() - t_all, 1)
    result["seconds"] = seconds
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


# ===========================================================================
# Children: each owns the chip for its lifetime
# ===========================================================================


def emit(**kv) -> None:
    print(MARK + json.dumps(kv), flush=True)


def claim_device(rehearse: bool) -> dict:
    """Pin the platform, touch the backend, report what JAX found.  The
    one place a missing chip surfaces: one line, exit 3, no traceback."""
    sys.path.insert(0, ROOT)
    from paddlefleetx_tpu.utils.device import apply_platform_env, device_identity

    apply_platform_env()
    try:
        ident = device_identity()
    except RuntimeError as e:
        print(f"no accelerator: {str(e).splitlines()[0]}", flush=True)
        sys.exit(3)
    dev = {"platform": ident["platform"], "kind": ident["device_kind"],
           "count": ident["device_count"]}
    emit(device=dev)
    from paddlefleetx_tpu.utils import device

    if device.pallas_interpret() != rehearse:
        print(f"pallas_interpret() is {device.pallas_interpret()} on {dev}", flush=True)
        sys.exit(4)
    return dev


def phase_kernels(args) -> int:
    """Interpret mode and Mosaic have never been compared: run every
    kernel compiled, at the shapes tests/test_chip_compile.py compiles,
    against the lax / XLA spelling of the same math on the same inputs."""
    claim_device(args.rehearse)
    import functools
    from unittest import mock

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddlefleetx_tpu.models.gpt import model as gpt_model
    from paddlefleetx_tpu.models.gpt.model import layer_norm
    from paddlefleetx_tpu.ops.attention import xla_attention
    from paddlefleetx_tpu.ops.decode_attention import (
        decode_attention, kv_cache_len, paged_decode_attention, quantize_kv,
    )
    from paddlefleetx_tpu.ops.flash_attention import _block_sizes, _flash_bsnd

    shape = shape_of(args.rehearse)
    n, s, hidden = shape["heads"], shape["seq"], shape["hidden"]
    bf16 = jnp.bfloat16
    rng = np.random.default_rng(args.seed)
    errs, bad = {}, []

    def rand(shp, dtype=bf16, scale=1.0):
        return jnp.asarray(rng.normal(size=shp) * scale, jnp.float32).astype(dtype)

    def f32(tree):
        return jax.tree.map(lambda a: np.asarray(a, np.float32), tree)

    def check(name, got, ref, truth=None):
        """max |got - ref| over the tree, against a band set by the
        reference's own magnitude (bf16: 2^-8 relative per rounding; the
        two spellings round at different points).  With ``truth`` (the f32
        answer) the band is instead how far the REFERENCE is from truth."""
        got, ref = f32(got), f32(ref)
        leaves = list(zip(jax.tree.leaves(got), jax.tree.leaves(ref)))
        err = max(float(np.max(np.abs(g - r))) for g, r in leaves)
        scale = max(float(np.max(np.abs(r))) for _, r in leaves)
        band = 0.03 * max(scale, 1.0)
        if truth is not None:
            ref_err = max(float(np.max(np.abs(r - t))) for (_, r), t in
                          zip(leaves, jax.tree.leaves(f32(truth))))
            band = max(band, 4.0 * ref_err)
        finite = all(np.isfinite(g).all() for g, _ in leaves)
        errs[name] = float(f"{err:.3g}")
        ok = finite and err <= band
        print(f"kernel {name}: max_abs_err {err:.3g} (ref magnitude {scale:.3g}, "
              f"band {band:.3g}) {'ok' if ok else 'FAIL'}", flush=True)
        if not ok:
            bad.append(name)

    def vs_lax(name, fn, *args, **kw):
        """A decode kernel as "auto" resolves it here (Mosaic on the chip)
        against the lax spelling of the same math on the same inputs."""
        run = {impl: jax.jit(functools.partial(fn, impl=impl, **kw))(*args)
               for impl in ("auto", "lax")}
        check(name, run["auto"], run["lax"])

    for d in shape["head_dims"]:
        tag = f"d{d}"
        # ---- flash fwd + both bwd schedules vs XLA attention ----
        q, k, v = (rand((2, s, n, d)) for _ in range(3))
        ct32 = rand((2, s, n, d)).astype(jnp.float32)
        block = 512 if s % 512 == 0 else 0

        def weighted(attn, q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32) * ct32)

        xla = functools.partial(xla_attention, causal=True)
        ref_out = jax.jit(xla)(q, k, v)
        truth_out = jax.jit(xla)(*(a.astype(jnp.float32) for a in (q, k, v)))
        ref_grads = jax.jit(jax.grad(functools.partial(weighted, xla), (0, 1, 2)))(q, k, v)
        scale, tile = float(d ** -0.5), _block_sizes(s, block)
        for bwd in ("split", "fused"):
            def flash(q, k, v, bwd=bwd):
                return _flash_bsnd(q, k, v, scale, tile, bwd)

            if bwd == "split":
                check(f"flash_fwd_{tag}", jax.jit(flash)(q, k, v), ref_out, truth_out)
            check(f"flash_bwd_{bwd}_{tag}",
                  jax.jit(jax.grad(functools.partial(weighted, flash), (0, 1, 2)))(q, k, v),
                  ref_grads)
        if 128 % d == 0 and (n * d) % 128 == 0:
            # ---- the same in the model's layout (whole heads a 128-lane block) ----
            def flash_bsh(q, k, v):
                return _flash_bsnd(q, k, v, scale, tile, "fused", 0, "bsh")

            check(f"flash_fwd_bsh_{tag}", jax.jit(flash_bsh)(q, k, v), ref_out, truth_out)
            check(f"flash_bwd_fused_bsh_{tag}",
                  jax.jit(jax.grad(functools.partial(weighted, flash_bsh), (0, 1, 2)))(q, k, v),
                  ref_grads)

        # ---- contiguous decode: t=1, spec chunk, prefill-sized t ----
        # a cache init_cache would allocate: 8-aligned, NOT a multiple of
        # the 256 block, so the last visited block is the clamped tail
        L = kv_cache_len(s + 4)
        kc, vc = rand((2, n, L, d)), rand((2, n, L, d))
        vf = jnp.asarray([3, 0], jnp.int32)
        for t in (1, 5, s // 2):
            # the chunk ends on the cache's last slot
            vs_lax(f"decode_t{t}_{tag}", decode_attention,
                   rand((2, t, n, d)), kc, vc, jnp.int32(L - t), kv_valid_from=vf)
        # int8 cache (not a default path; its kernels had only ever compiled)
        L8 = kv_cache_len(s + 4, quantized=True)
        kq, ks = quantize_kv(rand((2, n, L8, d)))
        vq, vs = quantize_kv(rand((2, n, L8, d)))
        for t in (1, 5):
            vs_lax(f"decode_int8_t{t}_{tag}", decode_attention,
                   rand((2, t, n, d)), kq, vq, jnp.int32(L8 - t - 7),
                   k_scale=ks, v_scale=vs)

        # ---- paged decode: default block 16 and the documented 32 ----
        rows = 8
        for bs in (16, 32):
            M = s // bs
            nb = rows * M + 1
            kp, vp = rand((nb, n, bs, d)), rand((nb, n, bs, d))
            tables = jnp.asarray(
                rng.permutation(np.arange(1, nb)).reshape(rows, M), jnp.int32)
            # t 80: a prefill chunk through the paged path, two query
            # tiles of the kernel, the second ragged
            for t in (1, 5, 80):
                qd = rand((rows, t, n, d))
                positions = jnp.asarray(
                    rng.integers(0, s - t, rows), jnp.int32).at[0].set(s - t)
                vs_lax(f"paged_bs{bs}_t{t}_{tag}", paged_decode_attention,
                       qd, kp, vp, tables, positions)
                if t == 1:
                    kpq, kps = quantize_kv(kp)
                    vpq, vps = quantize_kv(vp)
                    vs_lax(f"paged_int8_bs{bs}_{tag}", paged_decode_attention,
                           qd, kpq, vpq, tables, positions, k_scale=kps, v_scale=vps)
                    # the arena as the serving step hands it over: every
                    # layer's pool in one stack, one layer read in place
                    kp3, vp3 = (jnp.stack([rand(p.shape), p, rand(p.shape)])
                                for p in (kp, vp))
                    vs_lax(f"paged_arena_bs{bs}_{tag}", paged_decode_attention,
                           qd, kp3, vp3, tables, positions, layer=1)
                    (kq3, ks3), (vq3, vs3) = quantize_kv(kp3), quantize_kv(vp3)
                    vs_lax(f"paged_arena_int8_bs{bs}_{tag}", paged_decode_attention,
                           qd, kq3, vq3, tables, positions, layer=1,
                           k_scale=ks3, v_scale=vs3)

    # ---- fused LayerNorm fwd and bwd vs the jnp composite ----
    # 4 sequences, and the 16 the 345M step norms at once (16,384 rows x
    # 1,024: the shape the benchmark's train cell runs the kernel at, which
    # its reference check's probe of one sequence does not reach)
    scale = jnp.asarray(rng.normal(size=(hidden,)) * 0.1 + 1.0, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(hidden,)) * 0.1, jnp.float32)
    for batch, tag in ((4, ""), (16, "_b16")):
        x, res, ct = (rand((batch, s, hidden)) for _ in range(3))

        def ln(x, res, scale, bias):
            return layer_norm(x + res, scale, bias)

        def ln_loss(x, res, scale, bias):
            return jnp.sum(ln(x, res, scale, bias).astype(jnp.float32) * ct.astype(jnp.float32))

        def ln_both(schedule):
            # the rule held to one answer, so both of layer_norm's paths run
            # here whatever its table says of this shape
            with mock.patch.object(gpt_model, "_norm_schedule", lambda *a: schedule):
                return jax.jit(lambda *a: (ln(*a), jax.grad(ln_loss, (0, 2, 3))(*a)))(
                    x, res, scale, bias)

        (got_y, got_g), (ref_y, ref_g) = ln_both("kernel"), ln_both("composite")
        check(f"fused_ln_fwd{tag}", got_y, ref_y)
        # each gradient against its own magnitude: dscale / dbias sum 4,096
        # rows and more, and their band would hide a wrong dx
        for name, got, ref in zip(("dx", "dscale", "dbias"), got_g, ref_g):
            check(f"fused_ln_bwd_{name}{tag}", got, ref)

    emit(kernels=errs)
    if bad:
        print(f"kernels outside their band: {bad}", flush=True)
        return 1
    return 0


def phase_train(args) -> int:
    """tools/train.py on the documented single-chip config — data, steps,
    logging and output overridden, nothing else."""
    claim_device(args.rehearse)
    import math
    import statistics

    import jax
    import numpy as np

    from paddlefleetx_tpu.data import indexed
    from paddlefleetx_tpu.data.cpp import build as cpp_build
    from paddlefleetx_tpu.data.gpt_dataset import write_synthetic_corpus

    shape = shape_of(args.rehearse)
    # the index helper is BUILT from helpers.cpp / bpe.cpp here, never
    # taken from a stale libpfx_helpers.so that happens to look newer
    try:
        cpp_build.build(force=True)
    except Exception as e:  # noqa: BLE001 — no toolchain: numpy fallback serves
        print(f"C++ index helper build failed: {type(e).__name__}: {e}", flush=True)
        if os.path.exists(cpp_build._SO):
            os.unlink(cpp_build._SO)
    data_dir = os.path.join(WORK, "data")
    tokens_needed = (args.steps + 4) * shape["batch"] * (shape["seq"] + 1) * 2
    write_synthetic_corpus(
        os.path.join(data_dir, "corp"), vocab_size=shape["vocab"],
        num_docs=max(64, tokens_needed // 2048), mean_len=2048, seed=args.seed)
    out_dir = os.path.join(WORK, "train")
    metrics_path = os.path.join(LOGS, "train_metrics.jsonl")
    if os.path.exists(metrics_path):
        os.unlink(metrics_path)
    argv = ["-c", os.path.join(ROOT, CONFIG)]
    for o in shape["overrides"] + [
        f"Global.seed={args.seed}",
        f"Data.Train.dataset.input_dir={data_dir}",
        f"Engine.max_steps={args.steps}", "Engine.logging_freq=1",
        "Engine.eval_freq=0", f"Engine.save_load.output_dir={out_dir}",
        f"Engine.metrics_file={metrics_path}",
    ]:
        argv += ["-o", o]

    import tools.train as train_cli

    t0 = time.time()
    engine = train_cli.main(argv)
    wall = time.time() - t0

    with open(metrics_path) as f:
        recs = [r for r in map(json.loads, f) if "loss" in r and "step" in r]
    losses = [r["loss"] for r in recs]
    print("losses: " + " ".join(f"{x:.5f}" for x in losses), flush=True)
    fails = []
    if len(losses) != args.steps or not all(math.isfinite(x) for x in losses):
        fails.append(f"expected {args.steps} finite losses, got {losses}")
    uniform = math.log(shape["vocab"])
    if not args.rehearse and not (10.7 <= losses[0] <= 11.3):
        fails.append(f"first loss {losses[0]:.4f} outside 10.7-11.3 "
                     f"(ln {shape['vocab']} = {uniform:.2f})")
    if args.rehearse and abs(losses[0] - uniform) > 0.5:
        fails.append(f"first loss {losses[0]:.4f} far from ln(vocab) {uniform:.2f}")

    # nothing compiles after the first step: the engine's own books (the
    # compile bucket of its time ledger) and the compile watcher's events
    from paddlefleetx_tpu.utils.model_stats import get_compile_watcher

    compile_ledger = [r["time_ledger"]["compile"] for r in recs]
    if compile_ledger and compile_ledger[-1] != compile_ledger[0]:
        fails.append(f"compile seconds grew after step 1: {compile_ledger}")
    events = get_compile_watcher().snapshot()
    step_events = [e for e in events if "train_step" in e["fn"]]
    if len(step_events) != 1:
        fails.append(f"train_step compiled {len(step_events)} times: {step_events}")
    cache_hit = bool(step_events and step_events[0].get("cache_hit"))

    # the kernel is IN the step that ran (the flash -> XLA switch for odd
    # sequence lengths is only a warning): ask the compiled program
    seq = shape["seq"]
    host = {"tokens": np.zeros((shape["batch"], seq), np.int64),
            "labels": np.zeros((shape["batch"], seq), np.int64),
            "loss_mask": np.ones((shape["batch"], seq), np.float32),
            "position_ids": np.tile(np.arange(seq), (shape["batch"], 1))}
    with engine.mesh:
        dev_batch = engine._put_batch(host)
        text = engine._train_step.lower(engine.state, dev_batch).compile().as_text()
        has_kernel = "tpu_custom_call" in text
        if not args.rehearse and not has_kernel:
            fails.append("no tpu_custom_call in the compiled train step")

        # step time fenced by block_until_ready on everything the step returns
        def timed(n=5):
            out = []
            for _ in range(n):
                t = time.perf_counter()
                engine.state, m = engine.train_step(engine.state, dev_batch)
                jax.block_until_ready((engine.state, m))
                out.append(time.perf_counter() - t)
            return statistics.median(out)

        timed(n=2)  # drain + settle
        step_block = timed()

    steady = statistics.median([r["step_s"] for r in recs[2:]] or [recs[-1]["step_s"]])
    ckpt = os.path.join(out_dir, f"step_{args.steps}")
    if not os.path.isdir(ckpt):
        fails.append(f"no checkpoint at {ckpt}")
    emit(
        checkpoint=ckpt, first_loss=round(losses[0], 5), last_loss=round(losses[-1], 5),
        compile_s=recs[0].get("compile_s"), train_step_cache_hit=cache_hit,
        steady_step_s=round(steady, 4),
        tokens_per_s=round(shape["batch"] * seq / steady, 1),
        step_s_block_until_ready=round(step_block, 4),
        kernel_in_step=has_kernel, compile_events=len(events),
        index_helper=("built library" if indexed._LIB is not None
                      else "numpy fallback"),
        fit_wall_s=round(wall, 1),
    )
    for f_ in fails:
        print(f"train check failed: {f_}", flush=True)
    return 1 if fails else 0


def phase_multichip(args) -> int:
    """Hybrid parallelism on four chips: dp2·mp2 with tensor + sequence
    parallel (as pretrain_gpt_1.3B_mp8.yaml sets them), three steps, against
    the same three steps on ONE device of this process; then dp2·pp2."""
    dev = claim_device(args.rehearse)
    if dev["count"] != 4:
        print(f"--chips 4 needs four devices, found {dev['count']}", flush=True)
        return 3
    import gc

    import jax
    import numpy as np

    from paddlefleetx_tpu.core.engine import Engine
    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import get_config

    shape = shape_of(args.rehearse)
    b, seq = shape["batch"], shape["seq"]
    rng = np.random.default_rng(args.seed)
    batches = []
    for _ in range(3):
        toks = rng.integers(0, shape["vocab"], (b, seq + 1)).astype(np.int64)
        batches.append({"tokens": toks[:, :-1], "labels": toks[:, 1:],
                        "loss_mask": np.ones((b, seq), np.float32),
                        "position_ids": np.tile(np.arange(seq), (b, 1))})
    base = shape["overrides"] + [f"Global.seed={args.seed}", "Engine.eval_freq=0",
                                 "Engine.save_load.save_steps=0"]

    def run(label, overrides, devices):
        cfg = get_config(os.path.join(ROOT, CONFIG), overrides=base + overrides,
                         num_devices=len(devices))
        mesh = init_dist_env(cfg, devices=devices)
        out = {"loss": [], "grad_norm": []}
        with mesh:
            engine = Engine(cfg, build_module(cfg), mesh)
            t0 = time.time()
            for host in batches:
                dev_batch = engine._put_batch(host)
                engine.state, m = engine.train_step(engine.state, dev_batch)
                out["loss"].append(float(m["loss"]))
                out["grad_norm"].append(float(m["grad_norm"]))
            out["seconds"] = round(time.time() - t0, 1)
            if len(devices) > 1:
                # the work is really spread
                sharded = [
                    (jax.tree_util.keystr(path), leaf) for path, leaf in
                    jax.tree_util.tree_leaves_with_path(engine.state.params)
                    if any("model" in str(e) for e in leaf.sharding.spec)
                ] if "mp_degree=2" in " ".join(overrides) else []
                if sharded:
                    name, leaf = sharded[0]
                    homes = {s.device.id for s in leaf.addressable_shards}
                    out["model_sharded_param"] = f"{name} {leaf.sharding.spec} on devices {sorted(homes)}"
                    out["shards_on_4_devices"] = len(homes) == 4
                if not args.rehearse:  # the CPU backend reports no stats
                    use = [d.memory_stats()["bytes_in_use"] for d in devices]
                    out["bytes_in_use"] = use
                    out["all_devices_hold_state"] = all(u > 0 for u in use)
                text = engine._train_step.lower(engine.state, dev_batch).compile().as_text()
                out["all_reduces"] = text.count("all-reduce")
                out["flash_kernel_in_step"] = "tpu_custom_call" in text
            del engine
        gc.collect()
        print(f"{label}: loss {out['loss']} grad_norm {out['grad_norm']}", flush=True)
        return out

    devs = jax.devices()
    half = b // 2
    mp = run("dp2·mp2+sp", [
        "Distributed.dp_degree=2", "Distributed.mp_degree=2",
        "Distributed.sequence_parallel=True", "Model.sequence_parallel=True",
        f"Global.local_batch_size={half}", f"Global.micro_batch_size={half}"], devs)
    pp = run("dp2·pp2", [
        "Distributed.dp_degree=2", "Distributed.pp_degree=2",
        f"Global.local_batch_size={half}", f"Global.micro_batch_size={half // 2}"], devs)
    one = run("one device", [], devs[:1])

    fails = []
    report = {}
    # The bands.  dp2·mp2 is the SAME computation as one device (the
    # partitionable PRNG draws the same dropout masks under any sharding):
    # only bf16 rounding and reduction order differ.  1F1B draws its masks
    # per microbatch, so dp2·pp2 is the same model on different noise — a
    # wider band, and still far inside what a broken schedule would show.
    for label, got, band in (("dp2mp2", mp, 0.05), ("dp2pp2", pp, 0.15)):
        dl = [round(a - r, 5) for a, r in zip(got["loss"], one["loss"])]
        dg = [round(a / r - 1.0, 5) for a, r in zip(got["grad_norm"], one["grad_norm"])]
        report[label] = {"loss": [round(x, 5) for x in got["loss"]],
                         "loss_minus_one_device": dl,
                         "grad_norm_rel_diff": dg, "band": band,
                         "seconds": got["seconds"],
                         **{k: got[k] for k in got
                            if k not in ("loss", "grad_norm", "seconds")}}
        # NaN-proof: "not inside" rather than "outside"
        if not all(abs(x) <= band for x in dl + dg):
            fails.append(f"{label} leaves its band {band}: loss diff {dl}, "
                         f"grad-norm rel diff {dg} (grad norms {got['grad_norm']})")
        if not got["all_reduces"]:
            fails.append(f"{label}: no all-reduce in the step")
        if not args.rehearse and not got["flash_kernel_in_step"]:
            fails.append(f"{label}: no tpu_custom_call in the step")
        if not args.rehearse and not got["all_devices_hold_state"]:
            fails.append(f"{label}: a device holds nothing: {got['bytes_in_use']}")
    if not mp.get("shards_on_4_devices"):
        fails.append(f"no model-sharded parameter on 4 devices: {mp.get('model_sharded_param')}")
    report["one_device"] = {"loss": [round(x, 5) for x in one["loss"]],
                            "grad_norm": [round(x, 5) for x in one["grad_norm"]],
                            "seconds": one["seconds"]}
    emit(**report)
    for f_ in fails:
        print(f"multichip check failed: {f_}", flush=True)
    return 1 if fails else 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the corpus, the weights and the requests")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the hybrid-parallel path (four chips)")
    ap.add_argument("--steps", type=int, default=8, help="training steps")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU dress rehearsal at toy sizes (never a chip result)")
    ap.add_argument("--keep", action="store_true",
                    help="keep chip_smoke_out/ (corpus, checkpoint) afterwards")
    ap.add_argument("--phase", choices=("kernels", "train", "multichip"),
                    help=argparse.SUPPRESS)  # internal: a chip-owning child
    args = ap.parse_args()
    if args.phase:
        return {"kernels": phase_kernels, "train": phase_train,
                "multichip": phase_multichip}[args.phase](args)
    return parent(args)


if __name__ == "__main__":
    sys.exit(main())
