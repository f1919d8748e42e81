"""Shared functional-model machinery.

Models in this framework are pure functions over explicit parameter pytrees.
Each parameter is declared once as a :class:`ParamSpec` carrying its shape,
*logical* sharding axes (see ``parallel.sharding``) and initializer; the same
spec tree yields the init function, the logical-axis tree for pjit, and
abstract shapes for checkpoint restoration.  This replaces the reference's
nn.Layer modules + per-class parallel variants (single_model / hybrid_model /
auto_model triplication) with one definition sharded by annotation.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

Initializer = Callable[[jax.Array, Tuple[int, ...], Any], jax.Array]


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]
    init: Initializer
    dtype: Any = jnp.float32

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


def normal_init(stddev: float) -> Initializer:
    def f(key, shape, dtype):
        return stddev * jax.random.normal(key, shape, dtype)

    return f


# elements of a leaf drawn at once: 1 GiB of float32.  A larger leaf (a
# 261,120 x 5,120 embedding is 5.3 GB in float32) is drawn in slabs of its
# leading axis, so that a server can fold, cast and drop each slab before the
# next exists (models/gpt/generation.py init_serving_params)
SLAB_ELEMENTS = 2 ** 28


def slab_init(init: Initializer, shape: Tuple[int, ...]) -> Initializer:
    """``init`` for a leaf of ``shape`` in slabs of its leading axis: slab i of
    n from ``split(key, n)[i]``, n the fewest equal slabs of at most
    SLAB_ELEMENTS.  One slab (every leaf but a huge one): ``init`` itself,
    to the bit.  The initializer that comes back carries ``slabs`` = (init,
    n) for a caller that wants the slabs one at a time."""
    n = next(n for n in range(1, shape[0] + 1)
             if shape[0] % n == 0 and math.prod(shape) // n <= SLAB_ELEMENTS)
    if n == 1:
        return init

    def f(key, shape, dtype):
        slab = (shape[0] // n,) + tuple(shape[1:])
        return jnp.concatenate([init(k, slab, dtype) for k in jax.random.split(key, n)])

    f.slabs = (init, n)
    return f


def zeros_init() -> Initializer:
    return lambda key, shape, dtype: jnp.zeros(shape, dtype)


def ones_init() -> Initializer:
    return lambda key, shape, dtype: jnp.ones(shape, dtype)


def _is_spec(x: Any) -> bool:
    return isinstance(x, ParamSpec)


def init_params(key: jax.Array, specs: Any) -> Any:
    """Initialize a param pytree from a spec tree (one key fold per leaf)."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    arrs = [s.init(k, s.shape, s.dtype) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, arrs)


def logical_axes(specs: Any) -> Any:
    """Pytree of logical-axis tuples matching the param pytree."""
    return jax.tree.map(lambda s: s.logical, specs, is_leaf=_is_spec)


def abstract_params(specs: Any) -> Any:
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), specs, is_leaf=_is_spec
    )


def stack_specs(spec: ParamSpec, n: int, axis_name: Optional[str] = "layers") -> ParamSpec:
    """Add a leading stacked dim (for lax.scan-over-layers param layout)."""
    return ParamSpec(
        shape=(n,) + spec.shape,
        logical=(axis_name,) + spec.logical,
        init=_vmap_init(spec.init, n),
        dtype=spec.dtype,
    )


def _vmap_init(init: Initializer, n: int) -> Initializer:
    def f(key, shape, dtype):
        keys = jax.random.split(key, n)
        return jax.vmap(lambda k: init(k, shape[1:], dtype))(keys)

    return f


def stack_spec_tree(specs: Any, n: int, axis_name: Optional[str] = "layers") -> Any:
    return jax.tree.map(
        lambda s: stack_specs(s, n, axis_name), specs, is_leaf=_is_spec
    )


def count_params(params: Any) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def cast_floating(tree: Any, dtype: Any) -> Any:
    """Cast floating leaves (activations/compute copies of params)."""
    def c(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x

    return jax.tree.map(c, tree)


def dropout(key: Optional[jax.Array], x: jax.Array, rate: float, train: bool) -> jax.Array:
    if not train or rate == 0.0 or key is None:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


def one_hot_token_nll(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Per-token negative log-likelihood, fp32, via a one-hot contraction.

    NOT take_along_axis: the scatter transpose of a gather over a
    model-sharded vocab dim trips an XLA partial-manual partitioner CHECK
    inside pipelined shard_maps; the one-hot contraction's transpose is a
    plain (psum-able) broadcast-multiply.  Used by the GPT and ERNIE 1F1B
    pipeline heads."""
    lg = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.sum(lg * jax.nn.one_hot(labels, lg.shape[-1], dtype=lg.dtype), -1)
    return lse - picked
