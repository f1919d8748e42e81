"""Mixture-of-Experts FFN: gating, capacity, dispatch/combine.

TPU-native consolidation of the reference's TWO MoE stacks
(fastmoe-style ``MoELayer`` models/language_model/moe/ — alltoall
MoEScatter/MoEGather + per-expert loop; deepspeed-style ``moe_exp/``
sharded_moe.py:300-379 — TopKGate with capacity factor, token dropping,
load-balance aux loss): one fixed-capacity dense formulation.

Shape discipline (SURVEY §7.3: "MoE capacity/token-drop numerics under jit
need a fixed-capacity dense formulation"): dispatch/combine are dense
[tokens, experts, capacity] einsum masks — no dynamic shapes; dropped
tokens fall out of the mask.  The expert dim is sharded over the expert
group (``data``×``fsdp``×``sep``, mirroring HybridCommGroupForMoE's fused
dp×mp group, comm_groups.py:149-153), so XLA inserts the alltoall the
reference issues manually in MoEScatter/MoEGather (moe/comm_ops.py:28,74).

Gates: ``naive`` (top-k renormalised, no aux), ``gshard`` (top-2 +
load-balance aux), ``switch`` (top-1 + aux) — reference gate/*.py and
sharded_moe.py TopKGate.

Grad-clip parity note: the reference needs ``ClipGradForMOEByGlobalNorm``
(optims/grad_clip.py:27-156) to allreduce expert-param norms over the moe
group because expert params differ per rank; under GSPMD the param pytree
is global, so plain optax.clip_by_global_norm already computes the same
global norm.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from paddlefleetx_tpu.models.common import ParamSpec, normal_init, zeros_init


def moe_layer_specs(cfg) -> Dict[str, Any]:
    """Expert-parallel FFN param specs (drop-in for the dense 'mlp' subtree)."""
    h, ffn, E = cfg.hidden_size, cfg.ffn_hidden_size, cfg.num_experts
    w = normal_init(cfg.initializer_range)
    return {
        "gate_kernel": ParamSpec((h, E), ("embed", None), w),
        "fc_in_kernel": ParamSpec((E, h, ffn), ("expert", "embed", "mlp"), w),
        "fc_in_bias": ParamSpec((E, ffn), ("expert", "mlp"), zeros_init()),
        "fc_out_kernel": ParamSpec((E, ffn, h), ("expert", "mlp", "embed"), w),
        "fc_out_bias": ParamSpec((E, h), ("expert", "embed"), zeros_init()),
    }


def _top_k_positions(expert_mask: jax.Array) -> jax.Array:
    """Position of each (token, choice) inside its expert's capacity buffer.

    expert_mask: [N, k, E] one-hot.  Rank-0 choices get priority over rank-1
    (GShard policy): positions count down the flattened (k-major) order.
    Returns [N, k, E] int positions (-1 where not dispatched)."""
    n, k, e = expert_mask.shape
    flat = expert_mask.transpose(1, 0, 2).reshape(k * n, e)
    pos_flat = jnp.cumsum(flat, axis=0) * flat - 1  # -1 where mask==0
    return pos_flat.reshape(k, n, e).transpose(1, 0, 2).astype(jnp.int32)


def effective_top_k(gate_type: str, top_k: int) -> int:
    """switch is top-1 and gshard top-2 by definition (reference gate/*.py)."""
    return {"switch": 1, "gshard": 2}.get(gate_type, top_k)


def gate_and_dispatch(
    x: jax.Array,  # [N, h] tokens
    gate_logits: jax.Array,  # [N, E]
    num_experts: int,
    top_k: int,
    capacity: int,
    gate_type: str,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """-> (combine [N, E, C], dispatch bool [N, E, C], aux_loss scalar)."""
    n = x.shape[0]
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    top_k = effective_top_k(gate_type, top_k)

    top_w, top_idx = jax.lax.top_k(probs, top_k)  # [N, k]
    if gate_type in ("gshard", "switch"):
        # load-balance aux (GShard eq.: E * sum_e fraction_tokens_e * mean_prob_e)
        top1_mask = jax.nn.one_hot(top_idx[:, 0], num_experts)
        density = top1_mask.mean(axis=0)
        density_proxy = probs.mean(axis=0)
        aux = num_experts * jnp.sum(density * density_proxy)
    else:
        aux = jnp.zeros((), jnp.float32)

    if top_k > 1:
        # renormalise among chosen experts (GShard top-2)
        top_w = top_w / jnp.maximum(top_w.sum(axis=-1, keepdims=True), 1e-9)
    # top-1 (switch) keeps the RAW gate prob: scaling the expert output by it
    # is the router's only task-loss gradient path (Switch Transformer)

    expert_mask = jax.nn.one_hot(top_idx, num_experts, dtype=jnp.float32)  # [N,k,E]
    pos = _top_k_positions(expert_mask)  # [N,k,E]
    keep = (pos >= 0) & (pos < capacity)
    pos = jnp.where(keep, pos, 0)

    cap_onehot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)  # [N,k,E,C]
    cap_onehot = cap_onehot * keep[..., None] * expert_mask[..., None]
    combine = jnp.einsum("nk,nkec->nec", top_w, cap_onehot)
    dispatch = combine > 0
    return combine, dispatch, aux


def moe_mlp_block(
    p: Dict[str, Any],
    x: jax.Array,  # [b, s, h]
    cfg,
    ctx,
    key,
    train: bool,
) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel FFN.  Returns (out [b,s,h], aux loss scalar)."""
    from paddlefleetx_tpu.models.common import dropout
    from paddlefleetx_tpu.models.gpt.model import _constrain

    dtype = x.dtype
    b, s, h = x.shape
    E = cfg.num_experts
    k = effective_top_k(cfg.moe_gate, cfg.moe_top_k)
    tokens = x.reshape(b * s, h)
    n = b * s
    capacity = max(int(math.ceil(n * k * cfg.moe_capacity_factor / E)), 4)

    gate_logits = tokens.astype(jnp.float32) @ p["gate_kernel"].astype(jnp.float32)
    combine, dispatch, aux = gate_and_dispatch(
        tokens, gate_logits, E, k, capacity, cfg.moe_gate
    )

    # dispatch: [E, C, h] expert inputs (alltoall inserted by XLA when the
    # expert axis sharding differs from the token axis sharding)
    expert_in = jnp.einsum("nec,nh->ech", dispatch.astype(dtype), tokens)
    expert_in = _constrain(ctx, expert_in, ("expert", None, "embed"))

    def ffn(e_in, kern_in, b_in, kern_out, b_out):
        y = e_in @ kern_in.astype(dtype) + b_in.astype(dtype)
        y = jax.nn.gelu(y, approximate=True)
        return y @ kern_out.astype(dtype) + b_out.astype(dtype)

    expert_out = jax.vmap(ffn)(
        expert_in,
        p["fc_in_kernel"],
        p["fc_in_bias"],
        p["fc_out_kernel"],
        p["fc_out_bias"],
    )
    expert_out = _constrain(ctx, expert_out, ("expert", None, "embed"))

    out = jnp.einsum("nec,ech->nh", combine.astype(dtype), expert_out)
    out = out.reshape(b, s, h)
    out = dropout(key, out, cfg.hidden_dropout_prob, train)
    return out, aux.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Dropless expert layer (moe_gate: sigmoid)
#
# The router scores ALL ``num_experts``; this process holds ``experts_held``
# of them (ids ``moe_expert_offset`` ..) and computes the part of the result
# that they give: the (token, expert) pairs that land on held experts are
# sorted by expert into one buffer of static size, three grouped matrix
# products run over the groups, and the rows go back to their tokens
# weighted, accumulated in float32.  Pairs on experts held elsewhere add
# nothing here; nothing stands in for the absent chips.  An expert is
# SwiGLU's three matrices (w1, w3, w2) or, under ``mlp_act: relu2``, two:
# ``W_down relu(W_up m)^2`` (w1, w2).
#
# No token is dropped and no step can fail for its load: the buffer can
# always have the worst case's tokens x top_k rows (it fits the cell's chip,
# tests/test_chip_compile.py).  Gathers, masks and the scatter cost what the
# buffer's rows cost, whatever they hold, and only the grouped products
# follow the load; so where the caller asks for it (``load_ladder``, the
# training step) the buffer follows the step's load too: a short ladder of
# static sizes (:func:`buffer_ladder`: twice the balanced share, then the
# worst case; a compiled copy of the sorted path each) and, per call, the
# smallest that holds the pairs the sort counted on held experts.
# The ladder comes from shapes and the choice from the step's own count:
# there is no size to set and nothing that can overflow.  The sort puts the
# held pairs first, so a rung is the head of the worst case's buffer: per
# pair arithmetic, the order of the float32 sums into a token and the cuts
# of rows past the groups are the same at every rung, value and gradient.
# Under ``grad`` a ``switch`` would hand every rung's residuals out of every
# rung (zeros for those not taken: the worst case's gigabytes again), so
# the laddered path is a ``custom_vjp`` that keeps its inputs alone and
# differentiates the chosen rung INSIDE the backward pass's own branch.
#
# Which grouped product runs, and whether the ladder is engaged, is the
# CALLER's, by call site, never a size's:
# - training (``model.py``, differentiated; thousands of rows a group):
#   ``jax.lax.ragged_dot``, XLA:TPU's own, which also derives the two
#   transposed products of the backward pass and runs all three at its
#   rate; the ladder (one compile of the step holds its rungs);
# - a serving prefill (``generation._block_mlp``, forward only; 10-100 rows
#   a group, bound by the matrices' bytes): ``ops/grouped_matmul.py``'s
#   ``pfx_grouped_matmul``, which walks the held pairs only and reads each
#   matrix once, where the served tree keeps it.  There ``ragged_dot`` took
#   2 ms a call whatever its rows and wanted a copy of the matrices first.
#   One buffer, the bucket's worst case: a prefill is compiled once a
#   bucket and expert layer, each rung would be one more copy of its Mosaic
#   bodies, and set-up is what those cells are short of;
# - a serving decode step sorts nothing (``every_held_expert``).
# ---------------------------------------------------------------------------


def swiglu_specs(h: int, f: int, w, lead=(), lead_axes=()) -> Dict[str, Any]:
    return {
        "w1": ParamSpec(lead + (h, f), lead_axes + ("embed", "mlp"), w),
        "w3": ParamSpec(lead + (h, f), lead_axes + ("embed", "mlp"), w),
        "w2": ParamSpec(lead + (f, h), lead_axes + ("mlp", "embed"), w),
    }


def relu2_specs(h: int, f: int, w, w_out, lead=(), lead_axes=()) -> Dict[str, Any]:
    """The non-gated MLP's two matrices; ``w_out`` draws the down-projection."""
    return {
        "w1": ParamSpec(lead + (h, f), lead_axes + ("embed", "mlp"), w),
        "w2": ParamSpec(lead + (f, h), lead_axes + ("mlp", "embed"), w_out),
    }


def dropless_layer_specs(cfg, w_out=None) -> Dict[str, Any]:
    """``w_out`` (relu2 experts only): the down-projections' draw."""
    h, f = cfg.hidden_size, cfg.moe_ffn_hidden_size or cfg.ffn_hidden_size
    w = normal_init(cfg.initializer_range)
    if cfg.mlp_act == "relu2":
        def mlp_specs(width, *lead):
            return relu2_specs(h, width, w, w_out or w, *lead)
    else:
        def mlp_specs(width, *lead):
            return swiglu_specs(h, width, w, *lead)
    specs = {
        "router_kernel": ParamSpec((h, cfg.num_experts), ("embed", None), w),
        "experts": mlp_specs(f, (cfg.experts_held,), ("expert",)),
    }
    if cfg.moe_shared_experts:
        specs["shared"] = mlp_specs(f * cfg.moe_shared_experts)
    return specs


def swiglu(x: jax.Array, p: Dict[str, Any]) -> jax.Array:
    dtype = x.dtype
    return (jax.nn.silu(x @ p["w1"].astype(dtype)) * (x @ p["w3"].astype(dtype))) @ p[
        "w2"
    ].astype(dtype)


def relu2_mlp(x: jax.Array, p: Dict[str, Any]) -> jax.Array:
    dtype = x.dtype
    return jnp.square(jax.nn.relu(x @ p["w1"].astype(dtype))) @ p["w2"].astype(dtype)


def feed_forward(x: jax.Array, p: Dict[str, Any]) -> jax.Array:
    """The MLP its parameters spell: three matrices SwiGLU, two relu2."""
    return swiglu(x, p) if "w3" in p else relu2_mlp(x, p)


def sigmoid_route(m: jax.Array, router_kernel: jax.Array, bias: jax.Array, cfg):
    """m [N, h] -> (idx [N, k] over all experts, w [N, k] float32).  The
    scores are float32 at full matmul precision: the choice is a
    discontinuity, and a bf16 product would flip it for many tokens.  The
    bias moves the choice only; the weights are the unbiased scores."""
    s = jax.nn.sigmoid(
        jnp.dot(m.astype(jnp.float32), router_kernel.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
    )
    choice = s + jax.lax.stop_gradient(bias)
    if cfg.moe_n_group > 1:
        # group-limited: a group scores the sum of its two best choice
        # scores, and only the best groups' experts can be chosen
        g = cfg.moe_n_group
        grouped = choice.reshape(choice.shape[0], g, -1)
        _, keep = jax.lax.top_k(jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1),
                                cfg.moe_topk_group)
        kept = jnp.any(keep[:, :, None] == jax.lax.broadcasted_iota(jnp.int32, (1, 1, g), 2),
                       axis=1)
        choice = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(choice.shape)
    _, idx = jax.lax.top_k(choice, cfg.moe_top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, cfg.moe_route_scale * w


def softmax_route(m: jax.Array, router_kernel: jax.Array, bias: jax.Array, cfg):
    """m [N, h] -> (idx [N, k] over all experts, w [N, k] float32), the
    second rule of the dropless layer (``moe_gate: softmax``): a softmax over
    ALL the experts in float32 at full matmul precision, the k largest, their
    weights renormalised over the k CHOSEN (of all experts, held here or
    not: a token whose k are all elsewhere adds nothing here).  No bias and
    no scale: ``bias`` is the served tree's zeros and is not read."""
    del bias
    p = jax.nn.softmax(
        jnp.dot(m.astype(jnp.float32), router_kernel.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST), axis=-1)
    w, idx = jax.lax.top_k(p, cfg.moe_top_k)
    return idx, w / jnp.sum(w, axis=-1, keepdims=True)


def route(cfg):
    """The routing rule ``cfg.moe_gate`` names."""
    return softmax_route if cfg.moe_gate == "softmax" else sigmoid_route


def _held_experts_on_every_token(ex, m, idx, w, held: int, offset: int):
    """m [N, h] through EVERY held expert, combined with the routing weights
    (0 for nearly all; float32): the same per-pair arithmetic as the sorted
    path's, at held x N products.  For a decode batch: each expert's
    matrices are read once by a plain batched product, at the rate the dense
    layers' products reach; sorted into groups of one or two rows the
    grouped products read the same 4.2 GB of a DeepSeek-V3 share at 45% of
    the HBM's rate (11.4 ms of a 24.9 ms step at 64 rows; my chip run, PR 31)."""
    dtype = m.dtype
    with jax.named_scope("pfx.moe.dispatch"):
        hit = (idx - offset)[:, :, None] == jax.lax.broadcasted_iota(jnp.int32, (1, 1, held), 2)
        w_te = jnp.sum(jnp.where(hit, w[:, :, None], 0.0), axis=1)  # [N, held]
    with jax.named_scope("pfx.moe.experts"):
        up = jnp.einsum("nh,ehf->enf", m, ex["w1"].astype(dtype))
        if "w3" in ex:
            hidden = jax.nn.silu(up) * jnp.einsum("nh,ehf->enf", m, ex["w3"].astype(dtype))
        else:
            hidden = jnp.square(jax.nn.relu(up))
        ys = jnp.einsum("enf,efh->enh", hidden, ex["w2"].astype(dtype))
    with jax.named_scope("pfx.moe.combine"):
        return jnp.einsum("enh,ne->nh", ys.astype(jnp.float32), w_te).astype(dtype)


_ROW_TILE = 128  # rows; a whole number of any grouped product's row tiles


def buffer_ladder(rows: int, held: int, num_experts: int) -> Tuple[int, ...]:
    """The static sizes the sorted-pair buffer may take, smallest first:
    twice the balanced share of ``rows`` pairs (``held`` of ``num_experts``
    experts are here) rounded up to the row tile, and ``rows`` itself, the
    worst case.  One rung where the first would already be the worst case
    (every expert held, toy shapes).  No rung between the two: each is a
    compiled copy of the sorted path, forward, recompute and backward (77 MB
    of the trinity step's executable and 2-3 s of its set-up with a warm
    compile cache, PERF.md section 6, PR 41), and no pass of that cell held
    more than nine tenths of the first."""
    first = -(-2 * rows * held // (num_experts * _ROW_TILE)) * _ROW_TILE
    return (first, rows) if first < rows else (rows,)


def _sorted_pairs(R: int, k: int, grouped_product, ex, m, w, order, group_sizes, n_held,
                  gather_combine: bool = False):
    """The held pairs through their experts in a buffer of ``R`` rows (``R``
    at least ``n_held``, static): m [N, h], w [N, k] float32, ``order`` the
    stable sort of the N x k pairs that puts the held ones first, by expert
    -> what the held experts give [N, h].  ``gather_combine`` (forward-only
    callers; ``R`` = N x k): the pairs' results go back to their (token,
    choice) places by the INVERSE of the sort, a gather, and a token's k are
    summed where they lie, instead of the float32 scatter-add over the
    buffer's rows (1.40 ms against 0.25 a layer at 2,048 tokens x 8 on the
    v5e: the scatter was 39 of a 113 ms prefill; my chip runs, PR 42).  The
    same products in float32, summed in another order."""
    n, h = m.shape
    dtype = m.dtype
    with jax.named_scope("pfx.moe.dispatch"):
        order = order[:R]
        token = order // k
        live = (jax.lax.iota(jnp.int32, R) < n_held)[:, None]
        w_sorted = w.reshape(-1)[order][:, None]
        xs = jnp.take(m, token, axis=0)
    with jax.named_scope("pfx.moe.experts"):

        def grouped(x, kernel):
            # rows past the held pairs belong to no group, and on the TPU a
            # grouped product leaves them as it found them, forward and
            # backward: cut them going in and coming out, so that neither
            # a value nor a cotangent of such a row ever reaches a token
            y = grouped_product(jnp.where(live, x, 0), kernel.astype(dtype), group_sizes)
            return jnp.where(live, y, 0)

        if "w3" in ex:
            hidden = jax.nn.silu(grouped(xs, ex["w1"])) * grouped(xs, ex["w3"])
        else:
            hidden = jnp.square(jax.nn.relu(grouped(xs, ex["w1"])))
        ys = grouped(hidden, ex["w2"])
    with jax.named_scope("pfx.moe.combine"):
        if gather_combine:
            if R != n * k:
                raise ValueError("gather_combine reads every pair's row: the whole buffer")
            # row i of the buffer is pair order[i]; a dead row holds zeros
            back = jnp.zeros((R,), jnp.int32).at[order].set(jax.lax.iota(jnp.int32, R))
            pairs = jnp.take(ys, back, axis=0).reshape(n, k, h)
            return jnp.einsum("nkh,nk->nh", pairs.astype(jnp.float32), w).astype(dtype)
        out = jnp.zeros((n, h), jnp.float32).at[token].add(ys.astype(jnp.float32) * w_sorted)
        return out.astype(dtype)


def _rung(rungs: Tuple[int, ...], n_held: jax.Array) -> jax.Array:
    """Index of the smallest rung that holds ``n_held`` pairs."""
    return sum((n_held > R).astype(jnp.int32) for R in rungs[:-1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _laddered_pairs(rungs, k, grouped_product, ex, m, w, order, group_sizes, n_held):
    """:func:`_sorted_pairs` at the smallest of ``rungs`` that holds the
    pairs.  Differentiated by its own rule: left to JAX, the forward
    ``switch`` would return every rung's residuals from every rung."""
    return jax.lax.switch(
        _rung(rungs, n_held),
        [functools.partial(_sorted_pairs, R, k, grouped_product) for R in rungs],
        ex, m, w, order, group_sizes, n_held)


def _laddered_pairs_fwd(rungs, k, grouped_product, *args):
    return _laddered_pairs(rungs, k, grouped_product, *args), args


def _laddered_pairs_bwd(rungs, k, grouped_product, args, g):
    ex, m, w, order, group_sizes, n_held = args

    def backward(R):
        def run(ex, m, w, g):  # the chosen rung again, and its transpose, in here
            def rung(ex, m, w):
                return _sorted_pairs(R, k, grouped_product, ex, m, w, order, group_sizes, n_held)
            return jax.vjp(rung, ex, m, w)[1](g)
        return run

    grads = jax.lax.switch(_rung(rungs, n_held), [backward(R) for R in rungs], ex, m, w, g)
    # XLA:TPU otherwise sinks what follows into every branch: under the layer
    # scan that is the padding of each matrix's cotangent to the stack's
    # shape, four times its bytes out of each rung
    grads = jax.lax.optimization_barrier(grads)
    return (*grads, None, None, None)


_laddered_pairs.defvjp(_laddered_pairs_fwd, _laddered_pairs_bwd)


def routed_experts(p: Dict[str, Any], m: jax.Array, bias: jax.Array, cfg, valid=None,
                   every_held_expert: bool = False, grouped_product=jax.lax.ragged_dot,
                   load_ladder: bool = False, gather_combine: bool = False):
    """m [N, h] -> (what the held experts give [N, h], the step's load
    statistics).  ``load`` counts the pairs of every expert, held or not.
    ``valid`` [N] bool (serving: a fixed-shape batch with empty rows, a
    padded prompt) leaves the other tokens' pairs out of the load and of
    the groups: they get zeros and cost no grouped product.
    ``every_held_expert`` (the serving decode step asks for it; static)
    runs each held expert on every token instead of sorting the pairs.
    ``grouped_product(rows [R, k], matrices [held, k, n], group_sizes)``:
    the product over the sorted pairs; the serving prefill hands in its
    forward-only kernel (``ops/grouped_matmul.py``).
    ``load_ladder`` (the training step asks for it; static): the sorted
    pairs' buffer is the smallest rung of :func:`buffer_ladder` that holds
    this call's held pairs, not always the worst case; ``buffer_rows`` in
    the statistics says which ran.
    ``gather_combine`` (the serving prefill asks for it; static, not with
    ``load_ladder``): see :func:`_sorted_pairs`."""
    k, E, held, offset = cfg.moe_top_k, cfg.num_experts, cfg.experts_held, cfg.moe_expert_offset
    rows = m.shape[0] * k  # every pair may land on a held expert
    with jax.named_scope("pfx.moe.route"):
        idx, w = route(cfg)(m, p["router_kernel"], bias, cfg)
        if valid is not None:
            idx = jnp.where(valid[:, None], idx, E)  # no expert's id
        flat_e = idx.reshape(-1)
        ids = jax.lax.broadcasted_iota(jnp.int32, (1, E), 1)
        load = jnp.sum(flat_e[:, None] == ids, axis=0, dtype=jnp.int32)
    if every_held_expert:
        group_sizes = load[offset:offset + held]
        n_held = jnp.sum(group_sizes)
        out = _held_experts_on_every_token(p["experts"], m, idx, w, held, offset)
        return out, {"load": load, "pairs_held": n_held,
                     "load_max_over_mean": jnp.max(group_sizes) * held
                     / jnp.maximum(n_held, 1).astype(jnp.float32)}
    with jax.named_scope("pfx.moe.dispatch"):
        local = flat_e - offset
        is_held = (local >= 0) & (local < held)
        # pairs of experts held elsewhere sort behind every held one
        order = jnp.argsort(jnp.where(is_held, local, held), stable=True)
        group_sizes = load[offset:offset + held]
        n_held = jnp.sum(group_sizes)
    if gather_combine and load_ladder:
        raise ValueError("gather_combine reads the whole buffer and has no transpose: not with "
                         "load_ladder (a training step's short rungs keep the scatter-add)")
    rungs = buffer_ladder(rows, held, E) if load_ladder else (rows,)
    pairs = (m, w, order, group_sizes, n_held)
    if len(rungs) == 1:
        out = _sorted_pairs(rows, k, grouped_product, p["experts"], *pairs,
                            gather_combine=gather_combine)
    else:
        # the matrices are cast out here, so that a rung hands their
        # cotangents back in the products' dtype, as the one buffer does (in
        # float32 the layer scan would hold twice the bytes a layer)
        ex = jax.tree.map(lambda a: a.astype(m.dtype), p["experts"])
        out = _laddered_pairs(rungs, k, grouped_product, ex, *pairs)
    stats = {
        "load": load,
        "pairs_held": n_held,
        "load_max_over_mean": jnp.max(group_sizes) * held
        / jnp.maximum(n_held, 1).astype(jnp.float32),
        "buffer_rows": jnp.asarray(rungs, jnp.int32)[_rung(rungs, n_held)],
    }
    return out, stats


def dropless_moe_block(p: Dict[str, Any], x: jax.Array, cfg, ctx, bias: jax.Array,
                       valid=None, every_held_expert: bool = False,
                       grouped_product=jax.lax.ragged_dot, load_ladder: bool = False,
                       gather_combine: bool = False):
    """x [b, s, h] -> (shared expert + held routed experts [b, s, h], stats).
    ``valid`` [b, s], ``every_held_expert``, ``grouped_product``,
    ``load_ladder`` and ``gather_combine``: see :func:`routed_experts`."""
    if ctx is not None and ctx.mesh.size > 1:
        raise NotImplementedError(
            "the dropless expert layer runs one chip's share per process; the "
            "exchange of pairs across chips (parallel/sharding.py 'expert') is "
            "not written yet")
    b, s, h = x.shape
    m = x.reshape(b * s, h)
    out, stats = routed_experts(
        p, m, bias, cfg, None if valid is None else valid.reshape(b * s), every_held_expert,
        grouped_product, load_ladder, gather_combine)
    if cfg.moe_shared_experts:
        with jax.named_scope("pfx.moe.shared"):
            out = out + feed_forward(m, p["shared"])
    return out.reshape(b, s, h), stats


def next_expert_bias(bias: jax.Array, load: jax.Array, rate: float) -> jax.Array:
    """The balance rule, outside the gradient: an expert with fewer pairs
    than the mean over all experts gains ``rate``, one with more loses it."""
    load = load.astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(load, axis=-1, keepdims=True) - load)
