"""Module protocol: binds a model family to the engine.

Reference: ``BasicModule`` (ppfleetx/core/module/basic_module.py:29-86, a
Lightning-style protocol) + ``GPTModule`` (language_module.py:148).  Here a
module is the *functional* bundle the engine needs: param specs + loss +
metrics; train/eval stepping lives in the engine (pure jitted functions),
so the protocol is data-flow only — no training_step/backward hooks.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax

from paddlefleetx_tpu.utils.registry import MODULES


def resolve_model_dtype(cfg, model_cfg: Dict[str, Any]) -> None:
    """Fill model_cfg['dtype'] from Engine.mix_precision unless pinned.

    mix disabled == O0: fp32 compute (reference amp levels,
    distributed/apis/amp.py)."""
    if "dtype" not in model_cfg:
        mix = cfg.get("Engine", {}).get("mix_precision", {})
        model_cfg["dtype"] = (
            mix.get("dtype", "bfloat16") if mix.get("enable", True) else "float32"
        )


class BasicModule:
    """Interface consumed by the Engine."""

    def init_params(self, key: jax.Array) -> Any:
        raise NotImplementedError

    def logical_axes(self) -> Any:
        """Pytree of logical sharding-axis tuples matching params."""
        raise NotImplementedError

    def loss_fn(
        self,
        params: Any,
        batch: Dict[str, jax.Array],
        *,
        ctx=None,
        dropout_key: Optional[jax.Array] = None,
        train: bool = True,
    ) -> jax.Array:
        raise NotImplementedError

    def eval_metrics(self, loss: jax.Array) -> Dict[str, jax.Array]:
        return {"loss": loss}

    def export_spec(self):
        """(fwd, example_args): the inference forward and its example inputs
        (reference BasicModule.input_spec, basic_module.py:29-86) — consumed
        by tools/export.py for the StableHLO artifact."""
        raise NotImplementedError(
            f"{type(self).__name__} does not define export_spec(); "
            "add one to export this family"
        )

    # tokens per sample for ips reporting (reference language_module.py:100)
    tokens_per_sample: Optional[int] = None


@MODULES.register("GPTModule")
class GPTModule(BasicModule):
    """GPT pretraining (reference GPTModule language_module.py:148-227).

    Where the reference dispatches to single/hybrid/pipe model classes by
    world size (language_module.py:181-192), parallelism here is carried by
    the sharding rules the engine applies — one model."""

    def __init__(self, cfg):
        from paddlefleetx_tpu.models.gpt.config import GPTConfig

        model_cfg = dict(cfg.Model)
        model_cfg.pop("module", None)
        model_cfg.pop("name", None)
        resolve_model_dtype(cfg, model_cfg)
        dist = cfg.get("Distributed", {})
        if dist.get("sequence_parallel", False):
            model_cfg["sequence_parallel"] = True
        self.config = GPTConfig.from_config(model_cfg)
        self.tokens_per_sample = self.config.max_position_embeddings
        seq_len = cfg.get("Data", {}).get("Train", {}).get("dataset", {}).get("max_seq_len")
        if seq_len:
            self.tokens_per_sample = int(seq_len)
        # the dropless expert layer's routing bias and counters: state the
        # optimizer never sees (models/gpt/model.py init_extra)
        self.has_extra_state = self.config.moe_dropless
        self.warm_start_steps = (
            self.config.moe_bias_warm_start_steps if self.has_extra_state else 0)

    def init_params(self, key):
        from paddlefleetx_tpu.models.gpt import model as gpt

        return gpt.init(self.config, key)

    def init_extra(self, key, params):
        from paddlefleetx_tpu.models.gpt import model as gpt

        return gpt.init_extra(self.config)

    def extra_logical_axes(self):
        import jax.numpy as jnp

        from paddlefleetx_tpu.models.gpt import model as gpt

        return jax.tree.map(lambda a: (None,) * jnp.ndim(a), gpt.init_extra(self.config))

    def extra_scalars(self, extra):
        """Small arrays of ``extra`` that ride each step's metrics fetch."""
        from paddlefleetx_tpu.models.gpt import model as gpt

        return gpt.extra_scalars(extra)

    def extra_record(self, vals):
        """Fetched ``extra_scalars`` -> keys of the step record (host)."""
        from paddlefleetx_tpu.models.gpt import model as gpt

        return gpt.extra_record(vals)

    def warm_start_step(self, params, extra, batch, i, *, ctx=None):
        """Pass ``i`` of ``warm_start_steps`` (traced) -> (extra, report)."""
        from paddlefleetx_tpu.models.gpt import model as gpt

        return gpt.warm_start_step(params, extra, batch["tokens"], i, self.config, ctx=ctx)

    def publish_record(self, registry, record):
        """The expert layer's keys of a step record -> its ``pfx_moe_*``
        families (a record of a model without the layer has none)."""
        if "moe_pairs_total" in record:
            for key, family in (("moe_pairs_total", "pfx_moe_pairs_total"),
                                ("moe_pairs_held", "pfx_moe_pairs_held_total"),
                                ("moe_buffer_rows", "pfx_moe_buffer_rows_total"),
                                ("moe_load_max_over_mean_sum", "pfx_moe_load_max_over_mean_sum")):
                registry.counter(family).set(record[key])
            registry.gauge("pfx_moe_bias_abs_max").set(record["moe_bias_abs_max"])

    def logical_axes(self):
        from paddlefleetx_tpu.models.gpt import model as gpt

        return gpt.gpt_logical_axes(self.config)

    def loss_fn(self, params, batch, *, ctx=None, dropout_key=None, train=True,
                extra=None):
        from paddlefleetx_tpu.models.gpt import model as gpt

        return gpt.loss_fn(
            params, batch, self.config, ctx=ctx, dropout_key=dropout_key, train=train,
            extra=extra,
        )

    def export_spec(self):
        import jax.numpy as jnp

        from paddlefleetx_tpu.models.gpt import model as gpt

        cfg = self.config
        tokens = jnp.zeros((1, self.tokens_per_sample), jnp.int32)

        def fwd(params, tokens):
            return gpt.forward(params, tokens, cfg, train=False)

        return fwd, (tokens,)


@MODULES.register("GeneralClsModule")
@MODULES.register("ViTModule")
class ViTModule(BasicModule):
    """ViT / general image classification (reference
    GeneralClsModule general_classification_module.py + vit modules)."""

    def __init__(self, cfg):
        from paddlefleetx_tpu.models.vit.model import ViTConfig

        model_cfg = dict(cfg.Model)
        model_cfg.pop("module", None)
        model_cfg.pop("name", None)
        resolve_model_dtype(cfg, model_cfg)
        self.config = ViTConfig.from_config(model_cfg)
        self.label_smoothing = float(model_cfg.get("label_smoothing", 0.0))
        self.tokens_per_sample = self.config.num_patches + 1  # ips = patches/s

    def init_params(self, key):
        from paddlefleetx_tpu.models import vit

        return vit.init(self.config, key)

    def logical_axes(self):
        from paddlefleetx_tpu.models import vit

        return vit.vit_logical_axes(self.config)

    def loss_fn(self, params, batch, *, ctx=None, dropout_key=None, train=True):
        from paddlefleetx_tpu.models import vit

        logits = vit.forward(
            params,
            batch["images"],
            self.config,
            ctx=ctx,
            dropout_key=dropout_key,
            train=train,
        )
        return vit.cls_loss(logits, batch["labels"], self.label_smoothing)

    def export_spec(self):
        import jax.numpy as jnp

        from paddlefleetx_tpu.models import vit

        cfg = self.config
        images = jnp.zeros(
            (1, cfg.image_size, cfg.image_size, cfg.in_channels), jnp.float32
        )

        def fwd(params, images):
            return vit.forward(params, images, cfg, train=False)

        return fwd, (images,)


def build_module(cfg) -> BasicModule:
    """Name-dispatched module construction (reference models/__init__.py:30,
    minus the eval())."""
    _register_family_modules()
    name = cfg.Model.get("module", "GPTModule")
    return MODULES.get(name)(cfg)


def _register_family_modules():
    """Import model-family module adapters so their @MODULES.register run.

    Lazy (not at package import) to keep `import paddlefleetx_tpu` light;
    idempotent because Registry rejects double registration only on distinct
    functions and imports are cached."""
    import paddlefleetx_tpu.models.debertav2.module  # noqa: F401
    import paddlefleetx_tpu.models.ernie.module  # noqa: F401
    import paddlefleetx_tpu.models.gpt.evaluation  # noqa: F401
    import paddlefleetx_tpu.models.multimodal.module  # noqa: F401
    import paddlefleetx_tpu.models.gpt.finetune  # noqa: F401
    import paddlefleetx_tpu.models.protein.module  # noqa: F401
    import paddlefleetx_tpu.models.t5.module  # noqa: F401
    import paddlefleetx_tpu.models.vision.module  # noqa: F401
