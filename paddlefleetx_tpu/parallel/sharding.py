"""Sharding rules: logical axis names -> mesh PartitionSpecs.

This module is the TPU-native replacement for the reference's explicit
parallel layers (``ColumnParallelLinear`` / ``RowParallelLinear`` /
``VocabParallelEmbedding`` in hybrid_model.py:153-196,699 and the ZeRO
``group_sharded_parallel`` wrap, eager_engine.py:281-307).  Models annotate
every parameter with *logical* axis names; rules map logical names to mesh
axes; pjit/GSPMD inserts the same collectives the reference issues manually:

    column-parallel matmul  = kernel sharded on output dim over `model`
    row-parallel matmul     = kernel sharded on input dim over `model`
                              (psum of partial products inserted by XLA)
    vocab-parallel embed    = embedding sharded on vocab dim over `model`
    ZeRO-1/2/3              = params/opt-state additionally sharded on `fsdp`
    Megatron SP             = activations sharded on seq dim over `model`

Logical axis vocabulary (model code uses ONLY these names):

    batch      — batch dim of activations
    seq        — sequence dim of activations (sharded over `sep`; over `model`
                 too when Megatron sequence_parallel is on)
    embed      — hidden/residual dim (fsdp-sharded for ZeRO-3)
    mlp        — FFN intermediate dim (model-sharded: column-parallel)
    heads      — attention heads dim (model-sharded)
    kv         — per-head dim (never sharded)
    vocab      — vocabulary dim (model-sharded: vocab-parallel)
    layers     — stacked-layer dim of scanned params (stage-sharded under PP)
    expert     — MoE expert dim (sharded over data×fsdp×sep expert group)
"""

from __future__ import annotations

import math
from typing import Any, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddlefleetx_tpu.parallel.mesh import (
    AXIS_DATA,
    AXIS_FSDP,
    AXIS_MODEL,
    AXIS_SEP,
    AXIS_STAGES,
)

# Each rule: logical name -> mesh axis (or tuple of axes), or None (replicated)
BASE_RULES: Tuple[Tuple[str, Any], ...] = (
    ("batch", (AXIS_DATA, AXIS_FSDP)),
    ("seq", AXIS_SEP),
    ("embed", None),
    ("mlp", AXIS_MODEL),
    # heads spread over model AND sep: with sep>1 this is Ulysses — outside
    # attention the seq dim is sep-sharded, inside attention heads are; the
    # reshard between them is the DAP/Ulysses all-to-all (reference
    # protein_folding/dap.py:244-398), inserted by XLA
    ("heads", (AXIS_MODEL, AXIS_SEP)),
    ("kv", None),
    ("vocab", AXIS_MODEL),
    ("table", None),
    ("layers", AXIS_STAGES),
    ("expert", (AXIS_DATA, AXIS_FSDP, AXIS_SEP)),
)


def make_rules(
    fsdp_enabled: bool = False,
    sequence_parallel: bool = False,
    mesh: Optional[Mesh] = None,
    num_experts: int = 0,
) -> Tuple[Tuple[str, Any], ...]:
    """Build logical->mesh rules for the configured strategies.

    fsdp_enabled: shard the `embed` dim of params over `fsdp` (ZeRO-3-style
    param sharding; ZeRO-1/2 are handled by sharding optimizer states /
    gradients with the same rule set, see optims.build_optimizer).

    sequence_parallel: activations' `seq` dim additionally sharded over
    `model` between attention/MLP blocks (Megatron SP,
    reference sequence_parallel_utils.py) — with GSPMD this is just a
    different activation-sharding rule; all_gather/reduce_scatter fall out.
    """
    rules = dict(BASE_RULES)
    if fsdp_enabled:
        rules["embed"] = AXIS_FSDP
        # lookup tables (word/position/type embeddings) fsdp-shard their
        # TABLE dim, not the feature dim: their backward is a scatter-add
        # from batch-sharded [b,s,h], and a feature-dim-sharded target
        # forces the SPMD partitioner into replicate-then-repartition.
        # Megatron shards embeddings along vocab for the same reason.
        # (logical_to_spec dedups: "embed" then yields fsdp to the table
        # dim on these params and leaves the feature dim whole)
        rules["vocab"] = (AXIS_MODEL, AXIS_FSDP)
        rules["table"] = AXIS_FSDP
    if sequence_parallel:
        rules["seq"] = (AXIS_SEP, AXIS_MODEL)
    if mesh is not None and num_experts > 1:
        # expert-parallel degree must divide num_experts: greedily take
        # expert-group axes whose combined size still divides E (experts
        # replicate over the rest — EP degree <= E, reference moe semantics)
        chosen = []
        prod = 1
        for ax in (AXIS_DATA, AXIS_FSDP, AXIS_SEP):
            size = mesh.shape[ax]
            if size > 1 and num_experts % (prod * size) == 0:
                chosen.append(ax)
                prod *= size
        rules["expert"] = tuple(chosen) if chosen else None
    return tuple(rules.items())


def logical_to_spec(
    logical_axes: Sequence[Optional[str]], rules: Sequence[Tuple[str, Any]]
) -> P:
    """Map a tuple of logical axis names to a PartitionSpec."""
    table = dict(rules)
    used: set = set()
    spec = []
    for name in logical_axes:
        if name is None:
            spec.append(None)
            continue
        axes = table.get(name)
        if axes is None:
            spec.append(None)
            continue
        # one mesh axis may appear at most once in a spec
        if isinstance(axes, str):
            axes = (axes,)
        free = tuple(a for a in axes if a not in used)
        used.update(free)
        spec.append(free if len(free) > 1 else (free[0] if free else None))
    return P(*spec)


def tree_logical_to_sharding(
    logical_tree: Any, mesh: Mesh, rules: Sequence[Tuple[str, Any]]
) -> Any:
    """Map a pytree of logical-axis tuples to NamedShardings."""
    return jax.tree.map(
        lambda axes: NamedSharding(mesh, logical_to_spec(axes, rules)),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(a is None or isinstance(a, str) for a in x),
    )


def drop_small_fsdp(shardings: Any, shapes: Any, min_size: int = 1 << 16) -> Any:
    """Replicate (over `fsdp`) params smaller than ``min_size`` elements.

    Standard FSDP practice (the reference's group_sharded wrap keeps tiny
    tensors whole for the same reason): fsdp-sharding a LayerNorm-sized
    vector saves no memory worth having, and the fsdp-sharded *gradient*
    target forces the SPMD partitioner to reshard batch-sharded backward
    reductions hidden-dim-wise — an involuntary-full-rematerialization
    (replicate-then-repartition) on every layer.  ``shardings`` and
    ``shapes`` are matching pytrees (NamedSharding leaves / ShapeDtypeStruct
    leaves)."""
    import numpy as np

    def fix(sh, shape):
        if not isinstance(sh, NamedSharding):
            return sh
        if int(np.prod(shape.shape)) >= int(min_size):
            return sh
        spec = []
        changed = False
        for entry in sh.spec:
            axes = entry if isinstance(entry, tuple) else (entry,)
            kept = tuple(a for a in axes if a != AXIS_FSDP)
            changed = changed or (len(kept) != len(axes))
            spec.append(kept if len(kept) > 1 else (kept[0] if kept else None))
        return NamedSharding(sh.mesh, P(*spec)) if changed else sh

    return jax.tree.map(fix, shardings, shapes)


def place_on_mesh(tree: Any, mesh: Mesh) -> Any:
    """Fresh (``jnp.zeros``-style) state, placed on ``mesh`` the way a jit
    output under that mesh is: replicated ``NamedSharding``.

    jax types an array by the mesh it lives on, and that type is part of
    jit's tracing-cache key.  State that a jitted step takes AND returns
    (KV pools, decode row state, a donated cache) would otherwise key two
    compiles per shape: one for the first call on the bare fresh arrays,
    one for every later call on the step's own outputs — i.e. a warmup that
    warms nothing traffic uses."""
    return jax.device_put(tree, NamedSharding(mesh, P()))


def _ambient_abstract_mesh():
    """The active abstract mesh, or None when there is none (jax returns an
    empty placeholder then)."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh if mesh.axis_names else None


def _strip_manual_axes(spec: P, manual) -> P:
    """Drop mesh axes in ``manual`` from a PartitionSpec (constraints may
    not name Manual axes inside a shard_map body)."""
    entries = []
    for entry in spec:
        axes = entry if isinstance(entry, tuple) else (entry,)
        kept = tuple(a for a in axes if a is not None and a not in manual)
        entries.append(kept if len(kept) > 1 else (kept[0] if kept else None))
    return P(*entries)


def with_logical_constraint(x: jax.Array, logical_axes, rules, mesh: Mesh):
    """`lax.with_sharding_constraint` via logical names (activation sharding).

    Inside an active mesh context (incl. partially-manual shard_map bodies,
    where some axes are Manual) the bare PartitionSpec form must be used —
    a NamedSharding would pin the all-Auto outer mesh and mismatch.

    Inside a *manual* mapped region (shard_map_compat), axes that are
    Manual must not appear in the constraint at all: they are meaningless
    there (the body already holds the per-shard block).  Such axes are
    stripped; a constraint with nothing left is a no-op — the sharding
    lives at the in_specs/out_specs boundary of the enclosing map
    (docs/parallelism.md)."""
    spec = logical_to_spec(logical_axes, rules)
    from paddlefleetx_tpu.parallel.shard_map_compat import current_manual_axes

    manual = current_manual_axes()
    if manual:
        spec = _strip_manual_axes(spec, manual)
        if all(entry is None for entry in spec):
            return x
        return jax.lax.with_sharding_constraint(x, spec)
    if _ambient_abstract_mesh() is not None:
        return jax.lax.with_sharding_constraint(x, spec)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _kernel_axes(mesh: Mesh, rules, in_logical, shapes) -> dict:
    """logical name -> the mesh axes ``shard_kernel`` splits that dim along:
    of the axes the rules give the name, in order, each that is larger than
    1, not Manual already (an enclosing map's), not taken by an earlier name
    and divides every dim carrying the name (``shard_map`` needs exact
    splits)."""
    from paddlefleetx_tpu.parallel import shard_map_compat

    table = dict(rules)
    ambient = shard_map_compat.current_manual_axes()
    dims: dict = {}
    for logical, shape in zip(in_logical, shapes):
        for name, dim in zip(logical, shape):
            if name is not None:
                dims.setdefault(name, []).append(dim)
    chosen: dict = {}
    used: set = set()
    for name, sizes in dims.items():
        axes = table.get(name) or ()
        take, prod = [], 1
        for ax in (axes,) if isinstance(axes, str) else axes:
            n = mesh.shape[ax]
            if n > 1 and ax not in ambient and ax not in used and all(
                d % (prod * n) == 0 for d in sizes
            ):
                take.append(ax)
                prod *= n
        used.update(take)
        chosen[name] = tuple(take)
    return chosen


def kernel_shard_shape(mesh: Mesh, rules, shape, logical) -> Tuple[int, ...]:
    """The shape of one shard of an argument of ``shape`` as ``shard_kernel``
    would hand it to the kernel (that argument alone naming its dims): what
    a rule that chooses a kernel from its shapes has to read under a mesh."""
    if mesh.size == 1:
        return tuple(shape)
    chosen = _kernel_axes(mesh, rules, (logical,), (shape,))
    return tuple(
        dim // math.prod(mesh.shape[ax] for ax in chosen.get(name, ()))
        for name, dim in zip(logical, shape)
    )


def shard_kernel(fn, mesh: Mesh, rules, in_logical, out_logical):
    """Run ``fn`` — a Pallas kernel — under a mesh: inside a ``shard_map``
    that is Manual over EVERY mesh axis, its arguments split along the axes
    their logical dims are sharded on.

    Mosaic kernels cannot be partitioned by GSPMD: a bare ``pallas_call``
    in a jit over more than one device is refused by the TPU lowering
    ("wrap the call in a shard_map"), and so is one inside a *partially*
    manual map — though the CPU interpreter partitions either like any XLA
    code, which is how this stayed invisible to the CPU-mesh suite.  ``fn``
    must be independent per shard along every named dim (flash attention
    over batch and heads, LayerNorm over batch and seq) — no in-body
    communication; cotangents of replicated arguments (LayerNorm
    scale/bias) are psum'd by shard_map's own transpose.

    ``in_logical`` / ``out_logical`` give each argument's / the result's
    logical axis names (result names must appear among the arguments').
    A mesh axis is named in the specs only where it divides every dim
    carrying the logical name (``_kernel_axes``); along the
    rest the argument is replicated at the boundary and every shard
    computes the same thing.  Inside an enclosing manual map (the 1F1B
    pipeline's ``stages``) the kernel map nests: built on the ambient
    abstract mesh, Manual over the axes still Auto.  On a one-device mesh
    the kernel runs bare."""
    from paddlefleetx_tpu.parallel import shard_map_compat

    def call(*args):
        if mesh.size == 1:
            return fn(*args)
        chosen = _kernel_axes(mesh, rules, in_logical, [a.shape for a in args])
        missing = {n for n in out_logical if n is not None} - set(chosen)
        if missing:
            raise ValueError(
                f"shard_kernel result axes {sorted(missing)} name no argument dim"
            )

        def spec(logical):
            entries = [chosen[n] if n is not None else () for n in logical]
            return P(*[
                (e if len(e) > 1 else e[0]) if e else None for e in entries
            ])

        return shard_map_compat.shard_map(
            fn,
            _ambient_abstract_mesh() or mesh,
            in_specs=tuple(spec(l) for l in in_logical),
            out_specs=spec(out_logical),
            manual_axes=set(mesh.axis_names) - shard_map_compat.current_manual_axes(),
        )(*args)

    return call
