"""Pipeline parallelism: microbatch schedules over the ``stages`` mesh axis.

TPU-native replacement for the reference's ``PipelineLayer`` runtime
(``GPTForPretrainingPipe`` hybrid_model.py:1055-1206: LayerDesc flattening,
1F1B schedule, ``num_virtual_pipeline_stages`` interleaving, p2p send/recv
between pp ranks, tied embeddings via SharedLayerDesc): layers are stacked
on a leading axis and sharded over ``stages``; schedules run inside a
``stages``-manual ``shard_map`` (explicit ``ppermute`` hops between
neighbour stages, riding ICI) through the version-split adapter
``parallel/shard_map_compat.py``: on jax >= 0.9 the map is *partially
manual* (TP/FSDP/DP keep flowing through GSPMD inside each stage); on jax
0.4.x — where partial-auto lowering is broken (PartitionId / SPMD CHECK,
see shard_map_compat docstring) — the same body runs *full-manual*, with
non-stage axes replicated at the map boundary (in-body activation
constraints naming them are dropped by ``sharding.with_logical_constraint``)
and ring attention nesting via ambient manual collectives instead of an
inner map.

Two schedules:

* :func:`pipelined_stack` — GPipe fill-drain, forward only.  Used for
  eval/inference where no backward wave exists and all-microbatch
  residency is the algorithmic minimum anyway.

* :func:`pipeline_loss_1f1b` — the training schedule.  True 1F1B memory
  behavior (reference hybrid_model.py:1206 / Megatron fig. 4): the
  backward of microbatch ``m`` starts as soon as its forward drains from
  the last stage, so each stage holds at most ``min(2*C-1, M)`` stashed
  stage inputs (C = total chunks) instead of GPipe's ``M``.  Because JAX
  autodiff would otherwise delay every backward until all forwards finish,
  the schedule computes gradients *inside* the forward pass (per-microbatch
  VJPs against stashed stage inputs) and exposes them through
  ``jax.custom_vjp`` — the outer ``jax.grad`` just scales them.  The
  per-microbatch loss (head + CE) runs on the last chunk inside the
  schedule, so the only cross-stage outputs are the scalar loss numerator
  and parameter gradients: the fp32 activation-psum output seam of the
  fill-drain path does not exist here.

Virtual stages (reference ``num_virtual_pipeline_stages``,
hybrid_model.py:1190-1206): with V > 1 each device holds V layer *chunks*
assigned round-robin (chunk c lives on device ``c % S``), shrinking the
bubble from (S-1)/T to ~(S-1)/(V*T').  The caller passes the stacked
layer params pre-permuted so each device's contiguous ``stages`` shard
contains its V chunks in slot order (see ``interleave_permutation``).

Tied embeddings need no SharedLayerDesc machinery: embedding and head
params enter the schedule as separate arguments; passing the same array
for both makes outer autodiff sum the two returned cotangents — exactly
the first/last-rank embedding-grad allreduce the reference does manually.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from paddlefleetx_tpu.parallel import shard_map_compat
from paddlefleetx_tpu.parallel.mesh import AXIS_STAGES


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    num_stages: int
    num_microbatches: int
    num_virtual_stages: int = 1


def interleave_permutation(num_layers: int, S: int, V: int) -> np.ndarray:
    """Index array mapping storage order -> schedule order for V>1.

    Execution chunk ``c`` (semantic layers [c*pc, (c+1)*pc)) runs on device
    ``c % S`` in local slot ``c // S``; device s's contiguous stage shard
    must therefore hold chunks ``[s, S+s, 2S+s, ...]`` back to back."""
    C = S * V
    pc = num_layers // C
    idx = []
    for s in range(S):
        for v in range(V):
            c = v * S + s
            idx.extend(range(c * pc, (c + 1) * pc))
    return np.asarray(idx, dtype=np.int32)


def _is_cpu(mesh) -> bool:
    return next(iter(mesh.devices.flat)).platform == "cpu"


def pipelined_stack(
    layer_fn: Callable[[Any, jax.Array, jax.Array, jax.Array], jax.Array],
    layers_params: Any,
    x: jax.Array,
    pcfg: PipelineConfig,
    mesh,
) -> jax.Array:
    """Run a stacked-layer transformer body as a forward-only stage pipeline.

    layer_fn(local_params, x_mb, stage_index, mb_index) -> y_mb runs this
    stage's layer block (a lax.scan over the local layers); ``mb_index`` is
    the microbatch the stage is processing this tick (for per-microbatch
    dropout keys).  ``layers_params`` leaves have leading dim num_layers,
    sharded over ``stages``; x: [b, s, h].
    """
    S, M = pcfg.num_stages, pcfg.num_microbatches
    b = x.shape[0]
    if b % M:
        raise ValueError(f"batch {b} not divisible by pipeline microbatches {M}")

    in_dtype = x.dtype
    # XLA CPU's AllReducePromotion pass crashes on bf16 all-reduces, so the
    # seam runs fp32 there; on TPU the boundary stays in the compute dtype
    # (VERDICT r1: don't pay S-wide fp32 broadcasts on real hardware).
    seam_dtype = jnp.float32 if _is_cpu(mesh) else in_dtype

    def pipe(local_layers, x):
        x = x.astype(in_dtype)
        stage = jax.lax.axis_index(AXIS_STAGES)
        mbs = x.reshape((M, b // M) + x.shape[1:])
        T = M + S - 1

        def tick(carry, t):
            buf, out = carry
            mb_idx = jnp.minimum(t, M - 1)
            x0 = jax.lax.dynamic_index_in_dim(mbs, mb_idx, axis=0, keepdims=False)
            x_in = jnp.where(stage == 0, jnp.where(t < M, 1.0, 0.0) * x0, buf)
            # stage s processes microbatch t-s at tick t (clamped: out-of-
            # range ticks compute on garbage that is never emitted)
            mb_live = jnp.clip(t - stage, 0, M - 1)
            y = layer_fn(local_layers, x_in, stage, mb_live)
            # last stage emits microbatch t-(S-1) at tick t
            emit_idx = jnp.maximum(t - (S - 1), 0)
            emit = jnp.where((stage == S - 1) & (t >= S - 1), y, 0.0)
            prev = jax.lax.dynamic_index_in_dim(out, emit_idx, axis=0, keepdims=False)
            out = jax.lax.dynamic_update_index_in_dim(out, prev + emit, emit_idx, axis=0)
            buf = jax.lax.ppermute(
                y, AXIS_STAGES, [(i, (i + 1) % S) for i in range(S)]
            )
            return (buf, out), None

        buf0 = jnp.zeros_like(mbs[0])
        out0 = jnp.zeros_like(mbs)
        (_, out), _ = jax.lax.scan(tick, (buf0, out0), jnp.arange(T))
        # outputs live on the last stage only; replicate across stages so the
        # (stage-replicated) head can consume them everywhere
        out = jax.lax.psum(out.astype(seam_dtype), AXIS_STAGES)
        return out.reshape(x.shape)

    out = shard_map_compat.shard_map(
        pipe,
        mesh,
        in_specs=(P(AXIS_STAGES), P()),
        out_specs=P(),
        manual_axes={AXIS_STAGES},
    )(layers_params, x.astype(seam_dtype))
    return out.astype(in_dtype)


# ---------------------------------------------------------------------------
# 1F1B training schedule
# ---------------------------------------------------------------------------
#
# Event timetable (C = S*V chunks, M microbatches; lockstep SPMD ticks):
#   fwd(m, c)  at tick  m + c
#   bwd(m, c)  at tick  m + 2C - 1 - c
# so microbatch m's backward enters the last chunk one tick after its
# forward leaves it, and drains toward stage 0 while later microbatches are
# still filling — the 1F1B interleave.  A stage input stashed at fwd(m, c)
# is consumed at bwd(m, c): lifetime 2(C-c)-1 <= 2C-1 ticks, so a circular
# stash of K = min(2C-1, M) slots suffices (the memory claim).
#
# Each tick every device runs, per local chunk slot: one forward
# (embed|recv -> chunk) and one VJP (recompute embed+chunk+head from the
# stashed input, pull back the cotangent arriving from the next chunk).
# Out-of-range events compute on zeros and are SELECTED out of every
# accumulator (``_masked``: never multiplied out).  Activations and cotangents ride neighbour-to-neighbour
# ppermutes in the compute dtype; the only stage-psums are parameter
# gradients and the scalar loss numerator.


def _masked(live, g, dtype):
    """``g`` where the event is live, 0 elsewhere — a SELECT, never a
    product.  A dead event pulls a real cotangent back through a stage fed
    all-zero activations; LayerNorm's backward at zero variance scales by
    rsqrt(eps) ~ 316 per layer, and a dozen layers deep that overflows
    bf16: 0 * inf is NaN, where(False, inf, 0) is 0.  (Seen on the chip at
    GPT-345M dp2·pp2: NaN parameter grads in the first four layers of the
    second stage, loss exact — PR 21.)  Cast to the accumulator dtype so a
    bf16 grad accumulator keeps the scan carry's dtype invariant under
    multi_precision=False / main_grad=False."""
    return jnp.where(live, g, jnp.zeros_like(g)).astype(dtype)


def _tree_add_live(acc, new, live):
    return jax.tree.map(lambda a, g: a + _masked(live, g, a.dtype), acc, new)


def _run_1f1b(fns, pcfg: PipelineConfig, mesh, params, batch):
    embed_fn, chunk_fn, head_fn = fns
    S, M, V = pcfg.num_stages, pcfg.num_microbatches, pcfg.num_virtual_stages
    C = S * V
    eparams, layers, hparams = params
    bsz = next(iter(batch.values())).shape[0]
    if bsz % M:
        raise ValueError(f"batch {bsz} not divisible by pipeline microbatches {M}")

    def pipe(eparams, layers, hparams, batch):
        stage = jax.lax.axis_index(AXIS_STAGES)
        # local stage shard of the stacked layers, split into V chunk slots
        local = jax.tree.map(
            lambda a: a.reshape((V, a.shape[0] // V) + a.shape[1:]), layers
        )
        mbs = jax.tree.map(
            lambda a: a.reshape((M, a.shape[0] // M) + a.shape[1:]), batch
        )
        mb0 = jax.tree.map(lambda a: a[0], mbs)
        x_aval = jax.eval_shape(embed_fn, eparams, mb0, jnp.int32(0))
        K = min(2 * C - 1, M)
        zbuf = jnp.zeros((V,) + x_aval.shape, x_aval.dtype)
        stash0 = jnp.zeros((V, K) + x_aval.shape, x_aval.dtype)
        g0 = (
            jax.tree.map(jnp.zeros_like, eparams),
            jax.tree.map(jnp.zeros_like, local),
            jax.tree.map(jnp.zeros_like, hparams),
        )
        T = M + 2 * C - 1

        def tick(carry, t):
            fwd_buf, bwd_buf, stash, (ge, gl, gh), numer = carry
            ys, gxs = [], []
            new_stash = stash
            for v in range(V):
                c = v * S + stage
                # chunk 0 (embedding input) can only live in slot 0, and the
                # last chunk C-1 (head+loss) only in slot V-1: skip the
                # statically-dead embed/head work in the other slots
                can_be_first = v == 0
                can_be_last = v == V - 1
                local_v = jax.tree.map(lambda a: a[v], local)
                # ---- forward event: chunk c runs microbatch t - c --------
                m_f = t - c
                f_live = (m_f >= 0) & (m_f < M)
                mfi = jnp.clip(m_f, 0, M - 1)
                mb_f = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, mfi, 0, keepdims=False),
                    mbs,
                )
                if can_be_first:
                    x0 = embed_fn(eparams, mb_f, mfi)
                    x_in = jnp.where(c == 0, x0, fwd_buf[v])
                else:
                    x_in = fwd_buf[v]
                y = chunk_fn(local_v, x_in, c, mfi)
                ys.append(y)
                slot = jnp.mod(mfi, K)
                old = jax.lax.dynamic_index_in_dim(stash[v], slot, 0, keepdims=False)
                new_stash = new_stash.at[v].set(
                    jax.lax.dynamic_update_index_in_dim(
                        new_stash[v], jnp.where(f_live, x_in, old), slot, 0
                    )
                )
                # ---- backward event: chunk c, microbatch t - (2C-1-c) ----
                m_b = t - (2 * C - 1 - c)
                b_live = (m_b >= 0) & (m_b < M)
                mbi = jnp.clip(m_b, 0, M - 1)
                mb_b = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, mbi, 0, keepdims=False),
                    mbs,
                )
                bslot = jnp.mod(mbi, K)
                # read the PRE-tick stash: a slot is overwritten in the same
                # tick it is read only at c=0 with K=2C-1, where the old
                # value is exactly the one wanted
                x_st = jax.lax.dynamic_index_in_dim(stash[v], bslot, 0, keepdims=False)

                def recomp(ep, lp, hp, xin):
                    if can_be_first:
                        x0r = embed_fn(ep, mb_b, mbi)
                        xr = jnp.where(c == 0, x0r, xin)
                    else:
                        xr = xin
                    yr = chunk_fn(lp, xr, c, mbi)
                    nr = (
                        head_fn(hp, yr, mb_b, mbi)
                        if can_be_last
                        else jnp.zeros((), jnp.float32)
                    )
                    return yr, nr

                (_, nr), vjp = jax.vjp(recomp, eparams, local_v, hparams, x_st)
                is_last = c == C - 1
                gy = jnp.where(is_last, jnp.zeros_like(bwd_buf[v]), bwd_buf[v])
                gn = jnp.where(is_last, 1.0, 0.0).astype(jnp.float32)
                gep, glv, ghp, gx = vjp((gy, gn))
                ge = _tree_add_live(ge, gep, b_live)
                gh = _tree_add_live(gh, ghp, b_live)
                gl = jax.tree.map(
                    lambda a, g, _v=v: a.at[_v].add(_masked(b_live, g, a.dtype)),
                    gl, glv,
                )
                numer = numer + jnp.where(is_last & b_live, nr, 0.0).astype(jnp.float32)
                gxs.append(jnp.where(b_live, gx, jnp.zeros_like(gx)))
            # ---- ring sends -------------------------------------------------
            y_stack = jnp.stack(ys)  # [V, mb, ...]
            recv_f = jax.lax.ppermute(
                y_stack, AXIS_STAGES, [(i, (i + 1) % S) for i in range(S)]
            )
            # wrap on device 0: chunk vS's input is device S-1's slot v-1
            # output; slot 0 is fed by the embedding instead
            shifted_f = jnp.concatenate([jnp.zeros_like(recv_f[:1]), recv_f[:-1]], 0)
            fwd_buf = jnp.where(stage == 0, shifted_f, recv_f)
            gx_stack = jnp.stack(gxs)
            recv_b = jax.lax.ppermute(
                gx_stack, AXIS_STAGES, [(i, (i - 1) % S) for i in range(S)]
            )
            # wrap on device S-1: cotangent for chunk vS+S-1 is device 0's
            # slot v+1 pullback; the last chunk's cotangent is internal
            shifted_b = jnp.concatenate([recv_b[1:], jnp.zeros_like(recv_b[:1])], 0)
            bwd_buf = jnp.where(stage == S - 1, shifted_b, recv_b)
            return (fwd_buf, bwd_buf, new_stash, (ge, gl, gh), numer), None

        carry0 = (zbuf, zbuf, stash0, g0, jnp.zeros((), jnp.float32))
        (_, _, _, (ge, gl, gh), numer), _ = jax.lax.scan(
            tick, carry0, jnp.arange(T)
        )
        # embedding/head grads and the loss numerator are partial per stage
        # (tied-embedding contributions, reference hybrid_model
        # SharedLayerDesc allreduce).  Emitted with a leading stage axis and
        # reduced OUTSIDE the shard_map: an in-schedule psum-over-stages of
        # model-sharded grads trips an XLA partial-manual partitioner CHECK
        # (spmd_partitioner_util.cc device-group mismatch); the outer sum
        # lowers to the same allreduce through full GSPMD instead.
        numer = numer[None]
        ge = jax.tree.map(lambda a: a[None], ge)
        gh = jax.tree.map(lambda a: a[None], gh)
        gl = jax.tree.map(
            lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:]), gl
        )
        return numer, ge, gl, gh

    numer, ge, gl, gh = shard_map_compat.shard_map(
        pipe,
        mesh,
        in_specs=(P(), P(AXIS_STAGES), P(), P()),
        out_specs=(P(AXIS_STAGES), P(AXIS_STAGES), P(AXIS_STAGES), P(AXIS_STAGES)),
        manual_axes={AXIS_STAGES},
    )(eparams, layers, hparams, batch)
    numer = numer.sum(0)
    ge = jax.tree.map(lambda a: a.sum(0), ge)
    gh = jax.tree.map(lambda a: a.sum(0), gh)
    return numer, ge, gl, gh


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def pipeline_loss_1f1b(
    fns,
    pcfg: PipelineConfig,
    mesh,
    params,
    batch: Dict[str, jax.Array],
) -> jax.Array:
    """1F1B pipelined loss numerator: sum over tokens of nll * mask.

    ``fns = (embed_fn, chunk_fn, head_fn)``, pure functions:
      embed_fn(eparams, batch_mb, mb_index) -> x_mb          (chunk 0 input)
      chunk_fn(chunk_params, x_mb, chunk_index, mb_index) -> y_mb
      head_fn(hparams, y_mb, batch_mb, mb_index) -> numer    (scalar, fp32)

    ``params = (eparams, layers_stacked, hparams)`` are differentiable;
    ``batch`` leaves must be float arrays with leading dim = batch (cast int
    ids to float outside; cotangents for them are zero).  Gradients are
    computed during the forward schedule (see module docstring); the custom
    VJP scales them by the incoming cotangent, so this composes with
    ``jax.grad`` / the engine's value_and_grad unchanged.
    """
    numer, _, _, _ = _run_1f1b(fns, pcfg, mesh, params, batch)
    return numer


def _1f1b_fwd(fns, pcfg, mesh, params, batch):
    numer, ge, gl, gh = _run_1f1b(fns, pcfg, mesh, params, batch)
    bzeros = jax.tree.map(jnp.zeros_like, batch)
    return numer, ((ge, gl, gh), bzeros)


def _1f1b_bwd(fns, pcfg, mesh, res, gbar):
    grads, bzeros = res
    # gbar is an fp32 scalar (numer is fp32); keep cotangents in the param
    # dtype so bf16-param runs (multi_precision=False) get bf16 grads that
    # match the engine's bf16 accumulator carry instead of promoting
    return jax.tree.map(lambda g: (gbar * g).astype(g.dtype), grads), bzeros


pipeline_loss_1f1b.defvjp(_1f1b_fwd, _1f1b_bwd)
