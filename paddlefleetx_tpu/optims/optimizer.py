"""Optimizers (reference ppfleetx/optims/optimizer.py + grad_clip.py).

``FusedAdamW`` (reference optimizer.py:31-56) = optax.adamw: XLA already
fuses the elementwise update chain across the flattened param pytree, which
is what the reference's tensor-fusion helper (utils/tensor_fusion_helper.py)
does manually with 256MB buckets.  Weight-decay exemption by name
(LayerNorm/bias, reference ``multi_precision`` decay-param partition) is a
mask over the param tree.

ZeRO optimizer-state sharding (reference group_sharded_parallel) is NOT done
here: optimizer states inherit param shardings under pjit; the `fsdp` axis
rules in parallel.sharding decide the partitioning.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import optax

from paddlefleetx_tpu.optims.lr_scheduler import Schedule, build_lr_scheduler
from paddlefleetx_tpu.utils.registry import OPTIMIZERS


def _no_decay_mask(params: Any) -> Any:
    """True where weight decay applies: skip 1-D params (biases, LN scales)
    — same partition the reference computes by name suffix."""
    return jax.tree.map(lambda p: p.ndim > 1, params)


def sqsum_f32(x):
    """Sum of squares of one leaf, accumulated in fp32 — THE shared
    reduction rule under both the global grad norm below and the
    per-layer-group statistics (utils/model_stats.py), so the grouped
    and global norms can never disagree on accumulation dtype."""
    return jnp.sum(jnp.square(x.astype(jnp.float32)))


def global_norm_f32(tree: Any):
    """Global L2 norm with the sum-of-squares accumulated in fp32.

    optax.global_norm reduces each leaf in its own dtype; with bf16 grads
    (``mix_precision.main_grad: False``) an 8-mantissa-bit running sum over
    1e8+ elements is garbage.  The convert sits inside the reduction, so
    XLA fuses it — no fp32 copy of any leaf is materialized."""
    leaves = [x for x in jax.tree.leaves(tree) if x is not None]
    return jnp.sqrt(sum(sqsum_f32(x) for x in leaves))


def clip_by_global_norm_f32(clip_norm: float) -> optax.GradientTransformation:
    """Drop-in for optax.clip_by_global_norm with the norm in fp32 (exact
    for fp32 grads, *correct* for bf16 grads; reference ClipGradByGlobalNorm
    always computed the norm on fp32 main grads so never hit this)."""

    def init_fn(params):
        del params
        return optax.EmptyState()

    def update_fn(updates, state, params=None):
        del params
        g_norm = global_norm_f32(updates)
        scale = jnp.minimum(1.0, clip_norm / jnp.maximum(g_norm, 1e-16))
        updates = jax.tree.map(
            lambda u: (u.astype(jnp.float32) * scale).astype(u.dtype), updates
        )
        return updates, state

    return optax.GradientTransformation(init_fn, update_fn)


@OPTIMIZERS.register("AdamW")
@OPTIMIZERS.register("FusedAdamW")
def adamw(
    schedule: Schedule,
    beta1: float = 0.9,
    beta2: float = 0.999,
    epsilon: float = 1e-8,
    weight_decay: float = 0.01,
    grad_clip: Optional[float] = None,
    multi_precision: bool = True,
    moment_dtype: Optional[str] = None,
    **_unused,
) -> optax.GradientTransformation:
    """``moment_dtype: bfloat16`` stores the FIRST moment in bf16 (optax
    mu_dtype), freeing one param-size fp32 buffer of HBM — the lever that
    fits 1.3B-class models on a 16GB chip.  With fp32 masters
    (multi_precision=True, the default) the second moment stays fp32;
    under ``Optimizer.multi_precision: False`` optax inits both moments
    from the bf16 params, so nu is bf16 too — that full-bf16 trade is the
    1.3B single-chip recipe and is engine-gated to
    bfloat16 compute (fp16 nu would underflow)."""
    txs = []
    if grad_clip:
        txs.append(clip_by_global_norm_f32(grad_clip))
    txs.append(
        optax.adamw(
            learning_rate=schedule,
            b1=beta1,
            b2=beta2,
            eps=epsilon,
            weight_decay=weight_decay,
            mask=_no_decay_mask,
            mu_dtype=moment_dtype or None,
        )
    )
    return optax.chain(*txs)


@OPTIMIZERS.register("Adam")
def adam(
    schedule: Schedule,
    beta1: float = 0.9,
    beta2: float = 0.999,
    epsilon: float = 1e-8,
    grad_clip: Optional[float] = None,
    **_unused,
) -> optax.GradientTransformation:
    txs = []
    if grad_clip:
        txs.append(clip_by_global_norm_f32(grad_clip))
    txs.append(optax.adam(learning_rate=schedule, b1=beta1, b2=beta2, eps=epsilon))
    return optax.chain(*txs)


@OPTIMIZERS.register("Momentum")
def momentum(
    schedule: Schedule,
    momentum: float = 0.9,
    weight_decay: float = 0.0,
    grad_clip: Optional[float] = None,
    **_unused,
) -> optax.GradientTransformation:
    txs = []
    if grad_clip:
        txs.append(clip_by_global_norm_f32(grad_clip))
    if weight_decay:
        txs.append(optax.add_decayed_weights(weight_decay, mask=_no_decay_mask))
    txs.append(optax.sgd(learning_rate=schedule, momentum=momentum))
    return optax.chain(*txs)


def build_optimizer(cfg, count_scale: int = 1) -> tuple[optax.GradientTransformation, Schedule]:
    """From the YAML ``Optimizer`` block (reference optims/__init__.py:29-74):

    Optimizer:
      name: FusedAdamW
      weight_decay: 0.01
      beta1/beta2/epsilon: ...
      lr: {name: CosineAnnealingWithWarmupDecay, ..., use_increments: True}
      grad_clip: {name: ClipGradByGlobalNorm, clip_norm: 1.0}

    ``use_increments`` (reference lr_scheduler.py:31-74 + eager_engine.py:
    354-357): the schedule counts *samples*, not steps — the caller passes
    ``count_scale=global_batch_size`` and the schedule optax applies is
    ``schedule(step * count_scale)``.
    """
    cfg = dict(cfg)
    name = cfg.pop("name")
    lr_cfg = dict(cfg.pop("lr", {"name": "Constant", "learning_rate": 1e-4}))
    use_increments = bool(lr_cfg.pop("use_increments", False))
    base_schedule = build_lr_scheduler(lr_cfg)
    if use_increments and count_scale != 1:
        schedule: Schedule = lambda count: base_schedule(count * count_scale)
    else:
        schedule = base_schedule
    clip_cfg = cfg.pop("grad_clip", None) or {}
    if isinstance(clip_cfg, (int, float)):
        # shorthand: `grad_clip: 1.0` == global-norm clip at that norm
        clip_cfg = {"name": "ClipGradByGlobalNorm", "clip_norm": float(clip_cfg)}
    clip_norm = clip_cfg.get("clip_norm") if clip_cfg.get("name") != "None" else None
    tx = OPTIMIZERS.get(name)(schedule=schedule, grad_clip=clip_norm, **cfg)
    return tx, schedule
