"""Engine: sharded train/eval loops, checkpointing, metrics.

TPU-native re-design of the reference ``EagerEngine``
(ppfleetx/core/engine/eager_engine.py:53-926).  What the reference does with
fleet wrapping + manual micro-batching + AMP scaler + pipeline scheduling is
here ONE jitted train step:

  - grad accumulation  = ``lax.scan`` over a leading microbatch dim
    (reference ``_model_forward_backward`` :522-531)
  - DP grad allreduce  = psum implied by the batch sharding (:483-506)
  - TP/SP collectives  = param/activation shardings (hybrid_model.py)
  - ZeRO               = `fsdp` axis in param/opt-state shardings (:281-307)
  - AMP O2 main-grad   = params+opt fp32, compute bf16 casts inside the
    model; grads land fp32 because params are fp32 (apis/amp.py:30-234 —
    loss scaling unneeded in bf16, kept for the fp16 parity path)
  - found_inf skip     = jnp.isfinite check on grad norm; step skipped
    lockstep on all ranks (amp.py:219-225 semantics for free under SPMD)

Checkpoint layout follows the reference contract (eager_engine.py:717-825):
orbax sharded params/opt-state + meta{step, consumed_samples} with resume.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
import time
from typing import Any, Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddlefleetx_tpu.core.module import BasicModule
from paddlefleetx_tpu.models.gpt.model import ShardingCtx
from paddlefleetx_tpu.optims.optimizer import build_optimizer, global_norm_f32
from paddlefleetx_tpu.parallel.sharding import (
    drop_small_fsdp,
    logical_to_spec,
    make_rules,
    tree_logical_to_sharding,
)
from paddlefleetx_tpu.parallel.seed import get_seed_tracker
from paddlefleetx_tpu.utils.log import logger


@dataclasses.dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any
    # non-gradient mutable state (BN running stats, MoCo queue/momentum
    # params — the reference carries these as buffers/stop-gradient params,
    # e.g. moco.py:130-159); None for stateless modules
    extra: Any = None
    # fp16 DynamicLossScaler state {scale, good_steps} (reference
    # apis/amp.py:193-234); None on the bf16/fp32 paths
    scaler: Any = None

    def tree_flatten(self):
        return (self.step, self.params, self.opt_state, self.extra, self.scaler), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten
)


def opt_state_shardings(
    opt_state_shapes, params, moment_shardings, mesh: Mesh, memory_kind=None
):
    """Sharding tree for an optax state: subtrees structurally identical to
    the param tree (mu/nu/...) get ``moment_shardings``; everything else
    (step counts, empty states) is replicated.

    This is the ZeRO move (reference group_sharded_parallel 'os_g',
    eager_engine.py:281-307): the moments shard over `fsdp` from stage 1
    on, independently of whether the params do (stage 3).  With
    ``memory_kind='pinned_host'`` the moments live in host memory — the
    reference's ``offload=True`` option."""
    params_def = jax.tree.structure(params)
    replicated = NamedSharding(mesh, P())
    if memory_kind is not None:
        moment_shardings = jax.tree.map(
            lambda s: s.with_memory_kind(memory_kind), moment_shardings
        )

    def rec(node):
        if jax.tree.structure(node) == params_def:
            return moment_shardings
        if isinstance(node, tuple) and hasattr(node, "_fields"):  # namedtuple
            return type(node)(*[rec(c) for c in node])
        if isinstance(node, (list, tuple)):
            return type(node)(rec(c) for c in node)
        if isinstance(node, dict):
            return {k: rec(v) for k, v in node.items()}
        return jax.tree.map(lambda _: replicated, node)

    return rec(opt_state_shapes)


def _cast_fp32_leaves(tree: Any, dtype) -> Any:
    """Cast fp32 leaves to `dtype`, passing every other dtype through —
    the one rule behind both low-precision param storage
    (multi_precision=False) and low-precision grads (main_grad=False);
    keep the two paths on this single definition so they cannot diverge."""
    return jax.tree.map(
        lambda x: x.astype(dtype) if x.dtype == jnp.float32 else x, tree
    )


def _host_offload_supported(mesh: Mesh) -> bool:
    """Probe whether this backend can COMPILE pinned_host placements over
    the mesh (having the memory space is not enough: XLA CPU's SPMD
    partitioner rejects the placement custom-calls that TPU accepts)."""
    try:
        host = NamedSharding(mesh, P(), memory_kind="pinned_host")
        jax.jit(lambda x: x + 1.0, out_shardings=host)(jnp.zeros(()))
        return True
    except Exception:
        return False


class Engine:
    """Train/eval engine over one mesh (reference EagerEngine + AutoEngine
    collapse into this: pjit IS the auto-parallel path)."""

    def __init__(self, cfg, module: BasicModule, mesh: Mesh, mode: str = "train",
                 abstract_init: bool = False):
        """abstract_init=True builds the engine WITHOUT materializing any
        state: params/opt-state become ShapeDtypeStructs carrying their
        shardings, and only ``memory_report`` (AOT compile + per-device
        memory analysis) is usable.  This is the fit-check path for
        layouts larger than the local machine — e.g. validating the
        reference's 6.7B recipe (projects/gpt/docs/hybrid_parallel.md:
        47-54) against a per-chip HBM budget on a virtual mesh."""
        self.abstract_init = abstract_init
        self.cfg = cfg
        self.module = module
        self.mesh = mesh
        eng = cfg.Engine
        self.max_steps = int(eng.max_steps)
        self.eval_freq = int(eng.get("eval_freq", 0) or 0)
        self.eval_iters = int(eng.get("eval_iters", 10))
        self.logging_freq = int(eng.get("logging_freq", 10))
        self.accumulate_steps = int(eng.get("accumulate_steps", 1))
        # cross-host replica verification cadence (reference `check` fused
        # comm group, comm_groups.py:64; parallel/check.py) — 0 disables
        self.consistency_check_freq = int(eng.get("consistency_check_freq", 0) or 0)
        self.save_steps = int(eng.get("save_load", {}).get("save_steps", 0) or 0)
        self.output_dir = eng.get("save_load", {}).get("output_dir", "./output")
        # async_save: array write proceeds in background (orbax async) and
        # meta.json — the completeness marker — lands only once the write
        # is durable, so resume never sees a half-written checkpoint
        self.async_save = bool(eng.get("save_load", {}).get("async_save", False))
        self._async_ckptr = None
        self._save_thread = None
        self._save_error = None
        self._atexit_registered = False
        # retention GC: keep only the newest N complete checkpoints
        # (0 = keep everything); the last verified-good one — the anomaly
        # rollback target — is never deleted regardless of age
        self.keep_last_n = int(eng.get("save_load", {}).get("keep_last_n", 0) or 0)
        self._last_good_ckpt: Optional[str] = None
        # preemption contract (utils/resilience.py): SIGTERM/SIGINT during
        # fit finishes the in-flight step, saves with a `preempted` marker,
        # and fit returns with this flag set so the launcher exits 0
        self.preempted = False
        # exit_after_save (tools/train.py --exit-after-save): stop cleanly
        # right after the next periodic checkpoint completes — bounds a
        # preemptible-slice run to checkpoint-aligned work units
        self.exit_after_save = bool(eng.get("exit_after_save", False))
        # anomaly guard budgets (Engine.resilience block): past them the
        # engine rolls back to the last checkpoint instead of skipping or
        # diverging forever; see utils/resilience.AnomalyGuard
        res = eng.get("resilience", {}) or {}
        self.res_enable = bool(res.get("enable", True))
        self.res_max_skip_streak = int(res.get("max_skip_streak", 10))
        self.res_spike_zscore = float(res.get("loss_spike_zscore", 0.0))
        self.res_spike_streak = int(res.get("loss_spike_streak", 5))
        self.res_loss_window = int(res.get("loss_window", 64))
        self.res_max_rollbacks = int(res.get("max_rollbacks", 2))
        # QAT (reference Compress.Quantization, compression_helper.py:19-79):
        # fake-quantized weights in the forward, fp32 masters updated
        from paddlefleetx_tpu.utils.compression import build_qat_transform

        self.qat_transform = build_qat_transform(cfg.get("Compress"))
        if self.qat_transform is not None:
            logger.info("QAT enabled: int8 fake-quant weights in fwd/eval")
        self.global_batch_size = int(cfg.Global.global_batch_size)
        # machine-readable metrics stream: one JSON line per logging step
        # (the TIPC-style harness and dashboards parse this instead of
        # regexing the console log; "" disables)
        self.metrics_file = eng.get("metrics_file", "")
        # training observatory (utils/model_stats.py): per-layer-group
        # grad/param/update statistics computed IN-GRAPH every
        # ``model_stats_every`` steps (Engine.logging.model_stats_every,
        # default = logging cadence) behind a lax.cond, riding the
        # existing step-record device fetch — no new per-step host syncs.
        # 0 disables: the train step graph is then identical to the
        # stats-less one (tests/test_model_stats.py asserts the dispatch
        # and host-sync counts match the pre-observatory loop exactly).
        log_cfg = eng.get("logging", {}) or {}
        raw_every = log_cfg.get(
            "model_stats_every", eng.get("model_stats_every")
        )
        self.model_stats_every = (
            int(raw_every) if raw_every is not None else self.logging_freq
        )
        self._group_spec = None
        self._pending_stats = None  # (step, device refs) until next log
        self._fit_peak_bytes = None  # memory watermark peak, per fit
        self._headroom_warned = False
        # unified telemetry (utils/telemetry.py): every record written to
        # the metrics stream ALSO lands in the crash flight recorder (so a
        # postmortem never depends on metrics_file being set) and the
        # logged throughput feeds the process-wide registry.  MFU: the
        # analytic GPT-family estimator (6·N per token) against the
        # per-device-kind peak (PFX_PEAK_FLOPS override); None for
        # non-GPT modules — no MFU column rather than a wrong one.
        from paddlefleetx_tpu.utils.telemetry import (
            StallWatch,
            get_flight_recorder,
            get_registry,
            model_flops_per_token,
            peak_flops,
        )

        self._registry = get_registry()
        self._recorder = get_flight_recorder()
        # the slow-iteration watcher (docs/observability.md "Slow
        # iterations"): the fit loop hands it every step's stamps and its
        # own seconds by bucket; a step that ran far past the median of
        # its kind leaves a pfx.stall event
        self._stall = StallWatch(
            "train.step",
            ("data_wait", "put_dispatch", "log_fetch", "log_write", "other"),
            device_wait=("log_fetch",), data_wait=("data_wait",),
        )
        # trace windows over training steps: the config block's
        # (reference Profiler block, eager_engine.py:250-272 +
        # profiler.step :419) or one armed at run time with
        # ``engine.profiler.arm(log_dir, steps)`` from the fit thread
        from paddlefleetx_tpu.utils.profiler import ProfilerHook

        self.profiler = ProfilerHook(cfg.get("Profiler"))
        # retrace attribution (utils/model_stats.py): structured compile
        # events (fn, aval diff vs the previous key, elapsed) into the
        # flight ring + pfx_compile_* — installed before the first jit so
        # the train step's own compile is attributed too.  Process-wide
        # and idempotent; PFX_COMPILE_LOG=0 disables.
        from paddlefleetx_tpu.utils.model_stats import install_compile_watcher

        install_compile_watcher()
        self._flops_per_token = model_flops_per_token(
            getattr(module, "config", None)
        )
        self._peak_flops = peak_flops() if self._flops_per_token else None
        # first-dispatch trace+compile seconds (emitted once as compile_s
        # in the step record and EXCLUDED from the ips/mfu window — the
        # old first window understated early throughput wildly)
        self._compile_s: Optional[float] = None
        self._compile_emitted = False

        # fp16 parity path: dynamic loss scaling (reference DynamicLossScaler
        # apis/amp.py:193-234).  bf16 (the TPU default) needs no scaler —
        # same exponent range as fp32.
        mix = eng.get("mix_precision", {})
        # enable defaults True to match resolve_model_dtype (core/module.py),
        # and a pinned Model.dtype=float16 counts too: fp16 compute must get
        # the scaler in every spelling, never one without the other
        model_dtype = str(getattr(getattr(module, "config", None), "dtype", ""))
        self.use_loss_scaling = (
            bool(mix.get("enable", True))
            and str(mix.get("dtype", "bfloat16")) in ("float16", "fp16")
        ) or model_dtype in ("float16", "fp16")
        scale_loss = mix.get("scale_loss", 32768.0)
        scale_cfg = scale_loss if isinstance(scale_loss, dict) else {"init": scale_loss}
        self.init_loss_scaling = float(scale_cfg.get("init", 32768.0))
        self.scale_incr_every = int(scale_cfg.get("incr_every_n_steps", 1000))
        self.scale_incr_ratio = float(scale_cfg.get("incr_ratio", 2.0))
        self.scale_decr_ratio = float(scale_cfg.get("decr_ratio", 0.5))
        # main_grad=False (reference AMP O2 without main-grad, apis/amp.py):
        # differentiate w.r.t. the compute-dtype cast of the params, so the
        # gradient tree — and its per-microbatch accumulators — lives in
        # bf16/fp16 instead of fp32.  Halves grad HBM (the lever that fits
        # GPT-1.3B + AdamW on one 16G chip); costs grad-accumulation
        # precision, so it defaults to True (fp32 main grads) like the
        # reference.  The optimizer update still runs on fp32 masters; the
        # global-norm clip upcasts inside its reduction (optims/optimizer.py
        # global_norm_f32) so clipping stays exact.
        self.main_grad = bool(mix.get("main_grad", True))
        if not bool(mix.get("enable", True)):
            if not self.main_grad and "main_grad" in mix:
                # contradictory: main_grad=False is an AMP knob (it casts
                # fwd params/grads to the compute dtype); with AMP off it
                # would silently bf16-cast a nominally-fp32 run
                raise ValueError(
                    "mix_precision.main_grad=False requires "
                    "mix_precision.enable=True (main_grad only controls "
                    "the AMP gradient dtype)"
                )
            self.main_grad = True
        if (
            bool(mix.get("enable", True))
            and "dtype" in mix
            and model_dtype
            and model_dtype != str(mix["dtype"])
        ):
            # a pinned Model.dtype silently overrides the AMP dtype
            # (compute_dtype = model_dtype first), which turns an
            # explicitly-requested mix_precision.dtype into a mislabeled
            # run — r4's ZeRO-3 dryrun logged "main_grad=False: float32
            # gradients" for exactly this; fail loudly in every spelling
            raise ValueError(
                f"Model.dtype={model_dtype} contradicts "
                f"mix_precision.dtype={mix['dtype']}: pin one or make them "
                "agree (the model dtype wins, so the AMP request would be "
                "silently ignored)"
            )
        self.compute_dtype = model_dtype or str(mix.get("dtype", "bfloat16"))
        if not self.main_grad:
            logger.info(
                "AMP main_grad=False: %s gradients", self.compute_dtype
            )
        # Optimizer.multi_precision=False (reference FusedAdamW
        # multi_precision flag, optims/optimizer.py:31-56): NO fp32 master
        # weights — params live in the compute dtype and the Adam moments
        # follow it.  Frees 3 param-size fp32 buffers (masters + nu), the
        # difference between GPT-1.3B fitting one 16G chip and not; costs
        # update precision (bf16 weight updates round away ~1e-3-relative
        # deltas), so it defaults to True like the reference.
        self.multi_precision = bool(
            cfg.get("Optimizer", {}).get("multi_precision", True)
        )
        self._param_cast = None
        if not self.multi_precision and self.compute_dtype not in ("", "float32"):
            if self.compute_dtype in ("float16", "fp16"):
                # fp16 moments are unusable: typical g^2 ~1e-8 sits below
                # fp16's subnormal floor (6e-8), so nu flushes to zero and
                # the update explodes.  bf16 has the fp32 exponent range
                # and is the measured-safe pairing.
                raise ValueError(
                    "Optimizer.multi_precision=False requires bfloat16 "
                    "compute (fp16 Adam moments underflow); use "
                    "mix_precision.dtype=bfloat16 or multi_precision=True"
                )
            self._param_cast = jnp.dtype(self.compute_dtype)
            logger.info(
                "multi_precision=False: %s params, no fp32 masters",
                self.compute_dtype,
            )

        dist = cfg.get("Distributed", {})
        sharding_cfg = dist.get("sharding", {})
        sharding_degree = int(sharding_cfg.get("sharding_degree", 1))
        # default stage when a degree is configured but no stage: ZeRO-1
        # (process_dist_config normalizes this for config-file paths; the
        # fallback here covers hand-built cfg dicts)
        self.sharding_stage = int(
            sharding_cfg.get("sharding_stage", 1 if sharding_degree > 1 else 0)
        )
        self.sharding_offload = bool(
            sharding_cfg.get("sharding_offload", sharding_cfg.get("offload", False))
        )
        # params below this many elements stay whole on the fsdp axis
        # (see drop_small_fsdp) — configurable for tiny-model tests
        self.min_shard_size = int(sharding_cfg.get("min_shard_size", 1 << 16))
        num_experts = int(
            getattr(getattr(module, "config", None), "num_experts", 0) or 0
        )
        # ZeRO stage semantics (reference group_sharded_parallel
        # eager_engine.py:281-307): stage 1 = optimizer state sharded,
        # stage 2 = +gradients (reduce-scatter constraint in the train
        # step), stage 3 = +parameters.  Param rules use `fsdp` only at
        # stage 3; the moment rules use it from stage 1 on.
        self.rules = make_rules(
            fsdp_enabled=self.sharding_stage >= 3,
            sequence_parallel=bool(dist.get("sequence_parallel", False)),
            mesh=mesh,
            num_experts=num_experts,
        )
        self.moment_rules = make_rules(
            fsdp_enabled=self.sharding_stage >= 1,
            sequence_parallel=bool(dist.get("sequence_parallel", False)),
            mesh=mesh,
            num_experts=num_experts,
        )
        # Activation constraints NEVER use the fsdp mapping: ZeRO-3 shards
        # params' `embed` dim over fsdp (gathered at use), but the residual
        # stream stays batch-sharded — constraining activations' hidden dim
        # to fsdp would fight the (data,fsdp)-sharded batch inputs and trips
        # XLA's "involuntary full rematerialization" resharding path.
        self.act_rules = make_rules(
            fsdp_enabled=False,
            sequence_parallel=bool(dist.get("sequence_parallel", False)),
            mesh=mesh,
            num_experts=num_experts,
        )
        # balanced causal context parallelism: feed sequences in the zigzag
        # block order (parallel/ring_attention.zigzag_permutation) so ring
        # attention's causal masking wastes the same work on every device
        self.sep_zigzag = bool(dist.get("sep_zigzag", False)) and (
            mesh.shape.get("sep", 1) > 1
        )
        self._zigzag_perm = None
        self._zigzag_inv = None
        self._zigzag_seq = None
        pp_degree = int(dist.get("pp_degree", 1))
        if self.sep_zigzag:
            # only ring attention masks by explicit positions; any other
            # attention would silently attend across the permuted order
            attn_impl = str(getattr(getattr(module, "config", None), "attn_impl", ""))
            if attn_impl != "ring":
                raise ValueError(
                    f"sep_zigzag requires Model.attn_impl=ring, got {attn_impl!r}"
                )
            # pp composes: ctx.attn_positions rides into the 1F1B chunk
            # fns as a stage-replicated constant, and ring attention's
            # inner shard_map nests against the ambient abstract mesh
            # (parallel/ring_attention.py) — parity-tested pp2 x sep2 in
            # tests/test_long_context.py
        pipeline = None
        if pp_degree > 1:
            from paddlefleetx_tpu.parallel.pipeline import PipelineConfig

            # pipeline microbatches default to the stage count (reference
            # accumulate_steps >= pp semantics); batch must divide
            pipeline = PipelineConfig(
                num_stages=pp_degree,
                num_microbatches=int(
                    dist.get("pipeline", {}).get("micro_batches", pp_degree)
                ),
                # reference num_virtual_pipeline_stages (hybrid_model.py:1206)
                num_virtual_stages=int(
                    dist.get("pipeline", {}).get("virtual_pp_degree", 1)
                ),
            )
        self.ctx = ShardingCtx(mesh, self.act_rules, pipeline=pipeline)

        # token/sample-counted schedules (use_increments) are scaled inside
        # build_optimizer so optax's per-step count yields the right lr
        self.tx, self.schedule = build_optimizer(
            cfg.Optimizer, count_scale=self.global_batch_size
        )

        # ---- sharded state construction -------------------------------
        logical = module.logical_axes()
        self.param_shardings = tree_logical_to_sharding(logical, mesh, self.rules)
        self.batch_spec = NamedSharding(mesh, logical_to_spec(("batch",), self.rules))
        self.replicated = NamedSharding(mesh, P())

        self._consumed_samples = 0
        self._step = 0  # host mirror of state.step (avoids device sync in fit)
        self._warm_started = False
        self._train_loader = None  # held during fit: ckpt meta + rollback rewind
        self._loader_state = None  # loader state from a restored ckpt meta
        self.state = self._init_state()
        if self.model_stats_every > 0:
            # deterministic path -> layer-group mapping (embed / block_<i>
            # / head), total over every model in the zoo; built from the
            # state tree so abstract_init fit-checks get it too
            from paddlefleetx_tpu.utils.model_stats import build_group_spec

            self._group_spec = build_group_spec(self.state.params)
        # install zigzag positions EAGERLY for the configured sequence
        # length: a caller that resolves the step attribute before placing
        # the first batch must not run a positions-less (wrong-mask) graph
        zig_seq = int(
            getattr(getattr(module, "config", None), "max_position_embeddings", 0) or 0
        )
        # the config seq can be zigzag-incompatible (not divisible by
        # 2*sep) while the loader's actual batches are padded to a length
        # that is — fall back to the lazy per-batch install for those
        if self.sep_zigzag and zig_seq > 0 and zig_seq % (
            2 * self.mesh.shape["sep"]
        ) == 0:
            self._install_zigzag(zig_seq)  # builds the steps itself
        else:
            self._train_step = self._build_train_step()
            self._eval_step = self._build_eval_step()

    # ------------------------------------------------------------------
    def train_step(self, state, dev_batch):
        """Run one jitted train step on an already-placed batch.

        Always dispatches to the CURRENT compiled step: `_put_batch` may
        rebuild the jitted steps (first-seen zigzag sequence length), so
        callers must not hold `_train_step` across a `_put_batch` call —
        this indirection makes that mistake impossible.
        """
        return self._train_step(state, dev_batch)

    def eval_step(self, state, dev_batch, it):
        """Dispatcher for the current jitted eval step (see train_step)."""
        return self._eval_step(state, dev_batch, it)

    # ------------------------------------------------------------------
    def memory_report(self, batch_shapes: Dict[str, Any]) -> Dict[str, int]:
        """AOT-compile the train step and return PER-DEVICE memory stats.

        ``batch_shapes`` maps batch names to (shape, dtype) pairs (or any
        objects with .shape/.dtype).  Works with ``abstract_init=True`` to
        fit-check layouts bigger than this machine: XLA's SPMD program is
        identical on every device, so the compiled executable's memory
        analysis IS the per-device HBM budget (reference counterpart: the
        published 6.7B recipe sizing, projects/gpt/docs/
        hybrid_parallel.md:47-54, which is validated only by running it)."""
        import numpy as _np

        def _abs(v):
            if hasattr(v, "shape") and hasattr(v, "dtype"):
                shape, dtype = v.shape, v.dtype
            else:
                shape, dtype = v
            return jax.ShapeDtypeStruct(
                tuple(shape), jnp.dtype(dtype), sharding=self.batch_spec
            )

        batch_abs = {k: _abs(v) for k, v in batch_shapes.items()}
        compiled = self._train_step.lower(self.state, batch_abs).compile()
        ma = compiled.memory_analysis()
        required = ("argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "alias_size_in_bytes")
        if ma is None or not all(hasattr(ma, n) for n in required):
            # memory_analysis() is backend-dependent and may return None:
            # a silent 0-byte peak would report every layout as fitting
            # every budget — the exact wrong answer for this tool
            raise RuntimeError(
                "compiled.memory_analysis() unavailable on this backend; "
                "memory_report cannot produce a trustworthy byte budget"
            )
        stats = {n: int(getattr(ma, n)) for n in required}
        if hasattr(ma, "generated_code_size_in_bytes"):
            stats["generated_code_size_in_bytes"] = int(
                ma.generated_code_size_in_bytes
            )

        def shard_bytes(tree):
            total = 0
            for leaf in jax.tree.leaves(tree):
                shape = leaf.sharding.shard_shape(leaf.shape)
                total += int(_np.prod(shape, dtype=_np.int64)) * leaf.dtype.itemsize
            return total

        stats["params_bytes_per_device"] = shard_bytes(self.state.params)
        stats["opt_state_bytes_per_device"] = shard_bytes(self.state.opt_state)
        # donated state aliases its output; peak live ~= args + out - alias
        # + temps (XLA's own accounting, conservative for CPU/TPU alike)
        stats["peak_bytes_per_device_est"] = (
            stats.get("argument_size_in_bytes", 0)
            + stats.get("output_size_in_bytes", 0)
            - stats.get("alias_size_in_bytes", 0)
            + stats.get("temp_size_in_bytes", 0)
        )
        return stats

    # ------------------------------------------------------------------
    def _init_state(self) -> TrainState:
        key = get_seed_tracker().params_key()

        params_shapes = jax.eval_shape(self.module.init_params, key)
        opt_shapes = jax.eval_shape(self.tx.init, params_shapes)
        moment_shardings = tree_logical_to_sharding(
            self.module.logical_axes(), self.mesh, self.moment_rules
        )
        if self.sharding_stage >= 1:
            self.param_shardings = drop_small_fsdp(
                self.param_shardings, params_shapes, self.min_shard_size
            )
            moment_shardings = drop_small_fsdp(
                moment_shardings, params_shapes, self.min_shard_size
            )
        self.offload_active = self.sharding_offload and _host_offload_supported(
            self.mesh
        )
        if self.sharding_offload and not self.offload_active:
            logger.warning(
                "sharding.offload requested but this backend cannot compile "
                "pinned_host placements; optimizer state stays on device"
            )
        # device-memory shardings drive compute; the host variants are where
        # the state LIVES between steps when offload is active
        self._opt_shardings_device = opt_state_shardings(
            opt_shapes, params_shapes, moment_shardings, self.mesh, None
        )
        self.opt_shardings = (
            opt_state_shardings(
                opt_shapes, params_shapes, moment_shardings, self.mesh, "pinned_host"
            )
            if self.offload_active
            else self._opt_shardings_device
        )
        self._grad_shardings = moment_shardings if self.sharding_stage >= 2 else None

        has_extra = getattr(self.module, "has_extra_state", False)
        if has_extra:
            extra_logical = self.module.extra_logical_axes()
            self.extra_shardings = tree_logical_to_sharding(
                extra_logical, self.mesh, self.rules
            )
            if self.sharding_stage >= 1:
                # same small-param exemption as params/moments: extra state
                # (momentum encoders, queues, running stats) holds LN-sized
                # vectors with the same pathological-reshard backward
                extra_shapes = jax.eval_shape(
                    self.module.init_extra, key, params_shapes
                )
                self.extra_shardings = drop_small_fsdp(
                    self.extra_shardings, extra_shapes, self.min_shard_size
                )
        else:
            self.extra_shardings = None

        # ONE sharding tree for the whole TrainState, shared by make_state
        # and the train step's out_shardings: with the step's output left
        # to sharding propagation (out_shardings=None), XLA under a
        # model-parallel mesh may pick a DIFFERENT output sharding than
        # the input state carries — the donated buffers then cannot alias
        # ("Some donated buffers were not usable" on every step, and a
        # silent reshard of the whole state).  Pinning output == input
        # sharding makes donation always usable.
        self.state_shardings = TrainState(
            step=self.replicated,
            params=self.param_shardings,
            # host-placed directly when offload is active: materializing
            # on device first would OOM exactly the models offload serves
            opt_state=self.opt_shardings,
            extra=self.extra_shardings,
            scaler={"scale": self.replicated, "good_steps": self.replicated}
            if self.use_loss_scaling
            else None,
        )

        @functools.partial(jax.jit, out_shardings=self.state_shardings)
        def make_state(key):
            params = self.module.init_params(key)
            if self._param_cast is not None:
                # multi_precision=False: params (and the optax moments
                # init'd from them) live in the compute dtype
                params = _cast_fp32_leaves(params, self._param_cast)
            return TrainState(
                step=jnp.zeros((), jnp.int32),
                params=params,
                opt_state=self.tx.init(params),
                extra=self.module.init_extra(key, params) if has_extra else None,
                scaler={
                    "scale": jnp.float32(self.init_loss_scaling),
                    "good_steps": jnp.int32(0),
                }
                if self.use_loss_scaling
                else None,
            )

        if self.abstract_init:
            # fit-check path: the state is its shapes + shardings, nothing
            # is allocated (make_state.eval_shape reuses the jit's
            # out_shardings, so the abstract tree matches the real one
            # leaf-for-leaf, pinned-host placements included)
            shapes = make_state.eval_shape(key)
            n_params = sum(
                x.size for x in jax.tree.leaves(shapes.params)
            )
            logger.info(
                f"abstract init: {n_params/1e6:.1f}M params (no allocation) "
                f"over {self.mesh.size} devices"
            )
            return shapes

        t0 = time.time()
        state = make_state(key)
        pretrained = self.cfg.Engine.get("save_load", {}).get("pretrained_params")
        if pretrained and self.cfg.Engine.get("save_load", {}).get("ckpt_dir"):
            # every entry point follows Engine() with engine.load(ckpt_dir),
            # which replaces params wholesale — skip the redundant (possibly
            # multi-GB) warm-start restore.  auto_resume resolution happens
            # in tools/train.py, which nulls pretrained_params itself.
            logger.info("pretrained_params skipped: ckpt_dir load takes over")
            pretrained = None
        if pretrained:
            # params-only warm start (e.g. tools/convert_hf_gpt2.py output):
            # optimizer state stays fresh, unlike ckpt_dir full-state resume
            from paddlefleetx_tpu.utils.checkpoint import restore_params

            loaded = restore_params(pretrained)
            ref, got = jax.tree.structure(state.params), jax.tree.structure(loaded)
            if ref != got:
                raise ValueError(
                    f"pretrained_params tree mismatch: model {ref} vs ckpt {got}"
                )
            mismatched = [
                f"{jax.tree_util.keystr(kp)}: model {t.shape} vs ckpt {np.shape(n)}"
                for (kp, t), n in zip(
                    jax.tree_util.tree_leaves_with_path(state.params),
                    jax.tree.leaves(loaded),
                )
                if tuple(t.shape) != tuple(np.shape(n))
            ]
            if mismatched:
                raise ValueError(
                    "pretrained_params shape mismatch (hint: --pad-vocab-to "
                    "in tools/convert_hf_gpt2.py must match Model.vocab_size):\n  "
                    + "\n  ".join(mismatched)
                )
            # .copy(): device_put of a host numpy array can be zero-copy on
            # CPU; these params are later DONATED by the train step, so they
            # must live in XLA-owned buffers (same hazard as load(), below)
            loaded = jax.tree.map(
                lambda t, n: jax.device_put(np.asarray(n, t.dtype), t.sharding).copy(),
                state.params,
                loaded,
            )
            state = dataclasses.replace(state, params=loaded)
            logger.info(f"pretrained params loaded from {pretrained}")
        if hasattr(self.module, "post_init_state"):
            # module hook for installing pretrained weights into fresh state
            # (e.g. MOCOClsModule's frozen backbone, moco_module.py:160-180)
            state = self.module.post_init_state(self, state)
        n_params = sum(x.size for x in jax.tree.leaves(state.params))
        logger.info(
            f"init: {n_params/1e6:.1f}M params sharded over {self.mesh.size} devices "
            f"({time.time()-t0:.1f}s)"
        )
        return state

    # ------------------------------------------------------------------
    def _build_train_step(self):
        module, ctx, tx = self.module, self.ctx, self.tx
        accum = self.accumulate_steps
        has_extra = getattr(module, "has_extra_state", False)
        grad_shardings = self._grad_shardings
        offload = self.offload_active
        opt_dev_shardings = self._opt_shardings_device
        opt_host_shardings = self.opt_shardings
        use_scaling = self.use_loss_scaling
        incr_every = self.scale_incr_every
        incr_ratio = self.scale_incr_ratio
        decr_ratio = self.scale_decr_ratio
        qat = self.qat_transform
        grad_dtype = None if self.main_grad else jnp.dtype(self.compute_dtype)
        group_spec = self._group_spec
        stats_every = self.model_stats_every

        @functools.partial(
            jax.jit,
            donate_argnums=(0,),
            in_shardings=(None, self.batch_spec),
            # the state output is PINNED to the input state's sharding tree
            # (built at init): letting propagation choose (None) can pick a
            # different sharding for the new params/moments under a
            # model-parallel mesh, which both breaks donation ("donated
            # buffers were not usable" every step) and resharding-copies
            # the whole state each step
            out_shardings=(self.state_shardings, self.replicated),
        )
        def train_step(state: TrainState, batch: Dict[str, jax.Array]):
            # per-step dropout stream: 'global' stream folded with the step
            # counter (reference RNG-tracker semantics, env.py:34-98)
            base_key = get_seed_tracker().key("global")
            step_key = jax.random.fold_in(base_key, state.step)

            # fp16 dynamic loss scaling: multiply the loss by the current
            # scale before differentiation, unscale the grads after
            # (reference DynamicLossScaler apis/amp.py:193-234)
            loss_scale = (
                state.scaler["scale"] if use_scaling else jnp.float32(1.0)
            )

            def run_loss(p, mb, extra):
                if has_extra:
                    loss, new_extra = module.loss_fn(
                        p, mb, ctx=ctx, extra=extra, dropout_key=step_key, train=True
                    )
                else:
                    loss = module.loss_fn(
                        p, mb, ctx=ctx, dropout_key=step_key, train=True
                    )
                    new_extra = None
                return loss * loss_scale, (loss, new_extra)

            def micro_batches(b):
                return jax.tree.map(
                    lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]), b
                )

            # QAT: quantize ONCE per step, outside the microbatch scan —
            # fake_quant's straight-through VJP makes d/d(quantized) equal
            # d/d(master), so differentiating from the quantized tree gives
            # the master-weight grads without re-quantizing per microbatch
            fwd_params = qat(state.params) if qat is not None else state.params
            if grad_dtype is not None:
                # main_grad=False: differentiate w.r.t. the compute-dtype
                # cast, so grads (and the scan accumulator below) are bf16.
                # The model's per-use .astype(dtype) then no-ops; non-fp32
                # leaves (int tables, already-low-precision) pass through.
                fwd_params = _cast_fp32_leaves(fwd_params, grad_dtype)

            def micro(carry, mb):
                gacc, lacc, extra = carry
                (_, (loss, new_extra)), grads = jax.value_and_grad(
                    run_loss, has_aux=True
                )(fwd_params, mb, extra)
                return (jax.tree.map(jnp.add, gacc, grads), lacc + loss, new_extra), None

            zeros = jax.tree.map(jnp.zeros_like, fwd_params)
            if accum > 1:
                (gsum, lsum, new_extra), _ = jax.lax.scan(
                    micro,
                    (zeros, jnp.zeros((), jnp.float32), state.extra),
                    micro_batches(batch),
                )
                grads = jax.tree.map(lambda g: g / accum, gsum)
                loss = lsum / accum
            else:
                (_, (loss, new_extra)), grads = jax.value_and_grad(
                    run_loss, has_aux=True
                )(fwd_params, batch, state.extra)

            if use_scaling:
                # unscale in fp32 and STAY fp32: casting back to fp16 would
                # flush exactly the small gradients loss scaling exists to
                # keep representable (they were only representable scaled).
                # main_grad=False still bought fp16 accumulators inside the
                # microbatch scan, where grads are scaled; from the unscale
                # boundary on, the clip/Adam path is fp32 anyway.
                grads = jax.tree.map(
                    lambda g: g.astype(jnp.float32) / loss_scale, grads
                )

            if grad_shardings is not None:
                # ZeRO-2: the dp grad-sum lands fsdp-sharded (XLA lowers
                # the psum + constraint to a reduce-scatter); the sharded
                # optimizer update then all-gathers only the param updates
                grads = jax.lax.with_sharding_constraint(grads, grad_shardings)

            if group_spec is not None:
                # per-layer-group sum of squares feeds BOTH the global
                # grad norm (sum of the group sums — same fp32 rule as
                # global_norm_f32, one pass over the gradients) and the
                # per-group finiteness vector the non-finite-provenance
                # contract needs on every step
                from paddlefleetx_tpu.utils import model_stats as _ms

                grad_gsq = _ms.group_sqsum(group_spec, grads)
                gnorm = jnp.sqrt(jnp.sum(grad_gsq))
            else:
                gnorm = global_norm_f32(grads)
            finite = jnp.isfinite(gnorm)
            safe = jax.tree.map(lambda g: jnp.where(finite, g, 0.0), grads)
            # host offload: stage the moments onto device for the update,
            # park the new state back in pinned host memory afterwards
            opt_in = (
                jax.device_put(state.opt_state, opt_dev_shardings)
                if offload
                else state.opt_state
            )
            updates, new_opt = tx.update(safe, opt_in, state.params)
            new_params = optax.apply_updates(state.params, updates)
            # skip non-finite steps in lockstep (reference found_inf contract)
            new_params = jax.tree.map(
                lambda n, o: jnp.where(finite, n, o), new_params, state.params
            )
            new_opt = jax.tree.map(
                lambda n, o: jnp.where(finite, n, o), new_opt, opt_in
            )
            if offload:
                new_opt = jax.device_put(new_opt, opt_host_shardings)
            # extra (queue/BN/EMA) must revert too: a NaN forward would
            # otherwise poison enqueued keys / running stats permanently
            new_extra = jax.tree.map(
                lambda n, o: jnp.where(finite, n, o), new_extra, state.extra
            )
            new_scaler = state.scaler
            if use_scaling:
                # grow after incr_every consecutive finite steps, shrink on
                # overflow (reference update :219-234); never below 1.0
                good = jnp.where(finite, state.scaler["good_steps"] + 1, 0)
                grow = good >= incr_every
                scale = jnp.where(
                    finite,
                    jnp.where(grow, state.scaler["scale"] * incr_ratio,
                              state.scaler["scale"]),
                    jnp.maximum(state.scaler["scale"] * decr_ratio, 1.0),
                )
                new_scaler = {
                    "scale": scale,
                    "good_steps": jnp.where(grow, 0, good),
                }
            new_state = TrainState(
                state.step + 1, new_params, new_opt, new_extra, new_scaler
            )
            metrics = {
                "loss": loss,
                "grad_norm": gnorm,
                "lr": self.schedule(state.step),
                "found_inf": (~finite).astype(jnp.float32),
            }
            if use_scaling:
                metrics["loss_scale"] = new_scaler["scale"]
            if has_extra and hasattr(module, "extra_scalars"):
                # e.g. the expert layer's cumulative load counters: small
                # arrays of the new extra state, fetched with the loss
                metrics["extra"] = module.extra_scalars(new_extra)
            if group_spec is not None:
                from paddlefleetx_tpu.utils import model_stats as _ms

                # every step (free: isfinite of the sums the norm needed):
                # which groups went non-finite — rides the anomaly guard's
                # existing prev-metrics fetch, so rollback postmortems can
                # name the first offending group with no extra sync
                metrics["group_nonfinite"] = (
                    ~jnp.isfinite(grad_gsq)
                ).astype(jnp.int32)

                # cadence steps only (lax.cond: the untaken branch costs
                # nothing off-cadence): the full per-group statistic set.
                # (state.step + 1) is the 1-based step number this
                # dispatch computes — the same numbering the host loop and
                # step records use.
                def _stats_on(args):
                    g_sq, p, u, g = args
                    return _ms.group_stats(
                        group_spec, grad_sqsum=g_sq, params=p, updates=u,
                        grads=g,
                    )

                def _stats_off(args):
                    zeros = jnp.zeros(
                        (group_spec.num_groups,), jnp.float32
                    )
                    return {
                        k: zeros
                        for k in ("grad_norm", "param_norm", "update_norm",
                                  "update_ratio", "nonfinite_frac")
                    }

                metrics["model_stats"] = jax.lax.cond(
                    (state.step + 1) % stats_every == 0,
                    _stats_on,
                    _stats_off,
                    (grad_gsq, state.params, updates, grads),
                )
            return new_state, metrics

        return train_step

    def _get_predict_step(self):
        """Jitted module.predict_fn, built once (recompiling per evaluate()
        call would retrace every eval round)."""
        if getattr(self, "_predict_step", None) is None:
            module, ctx = self.module, self.ctx
            qat = self.qat_transform

            def predict(state, batch):
                # metrics must measure the same quantized weights the eval
                # loss and the exported model use
                p = qat(state.params) if qat is not None else state.params
                return module.predict_fn(p, batch, ctx=ctx)

            self._predict_step = jax.jit(
                predict,
                in_shardings=(None, self.batch_spec),
                out_shardings=self.replicated,
            )
        return self._predict_step

    def _build_eval_step(self):
        module, ctx = self.module, self.ctx

        has_extra = getattr(module, "has_extra_state", False)
        qat = self.qat_transform

        @functools.partial(
            jax.jit,
            in_shardings=(None, self.batch_spec, None),
            out_shardings=self.replicated,
        )
        def eval_step(state: TrainState, batch, eval_it):
            # per-eval-batch key (folded with step AND batch index): modules
            # that sample stochastic quantities at eval time — e.g. Imagen's
            # diffusion timesteps — must not see a constant key, or eval
            # loss becomes a low-variance biased estimate
            ekey = jax.random.fold_in(
                jax.random.fold_in(get_seed_tracker().key("global"), state.step), eval_it
            )
            # eval sees the same quantized weights training optimizes for
            p = qat(state.params) if qat is not None else state.params
            if has_extra:
                loss, _ = module.loss_fn(
                    p,
                    batch,
                    ctx=ctx,
                    extra=state.extra,
                    dropout_key=ekey,
                    train=False,
                )
                return loss
            return module.loss_fn(
                p, batch, ctx=ctx, dropout_key=ekey, train=False
            )

        return eval_step

    # ------------------------------------------------------------------
    # sequence-dim keys reordered under the zigzag context-parallel layout
    _SEQ_KEYS = ("tokens", "labels", "loss_mask", "position_ids", "input_ids")

    def _install_zigzag(self, seq: int) -> None:
        """Install the zigzag permutation + attn_positions for sequence
        length `seq` and rebuild the jitted steps against it.

        The positions ride the sharding ctx as a CONSTANT: ring attention
        masks by TRUE token order.  Called eagerly at init (config seq) and
        again from _put_batch only if a different seq shows up.
        """
        import dataclasses as _dc

        from paddlefleetx_tpu.parallel.ring_attention import zigzag_permutation

        self._zigzag_perm = np.asarray(
            zigzag_permutation(seq, self.mesh.shape["sep"])
        )
        self._zigzag_inv = np.argsort(self._zigzag_perm)
        self._zigzag_seq = seq
        self.ctx = _dc.replace(
            self.ctx, attn_positions=jnp.asarray(self._zigzag_perm, jnp.int32)
        )
        self._train_step = self._build_train_step()
        self._eval_step = self._build_eval_step()
        self._predict_step = None

    def _put_batch(self, batch: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
        if self.sep_zigzag:
            seq = next(
                (v.shape[1] for k, v in batch.items()
                 if k in self._SEQ_KEYS and getattr(v, "ndim", 0) >= 2),
                None,
            )
            if seq is not None:
                if self._zigzag_seq != seq:
                    self._install_zigzag(seq)
                perm = self._zigzag_perm
                inv = self._zigzag_inv
                batch = {
                    k: (v[:, perm] if k in self._SEQ_KEYS and getattr(v, "ndim", 0) >= 2 else v)
                    for k, v in batch.items()
                }
                # per-sample indices INTO the sequence must follow the
                # token they point at (e.g. finetune cls_position)
                for key in ("cls_position",):
                    if batch.get(key) is not None:
                        batch[key] = inv[np.asarray(batch[key])]
                if batch.get("position_ids") is None:
                    # loaders that omit position_ids would otherwise embed
                    # (and mask) in permuted index order
                    b = next(
                        v.shape[0] for k, v in batch.items()
                        if k in self._SEQ_KEYS and getattr(v, "ndim", 0) >= 2
                    )
                    batch["position_ids"] = np.tile(perm, (b, 1))
        return jax.tree.map(lambda x: jax.device_put(x, self.batch_spec), batch)

    def _write_metrics(self, record: Dict) -> None:
        # EVERY record (step, data_skip, rollback, preempt_save) also
        # enters the flight recorder ring — before the metrics_file gates,
        # so a crash postmortem exists even when no stream is configured
        rec = dict(record)
        rec.setdefault("event", "step")
        self._recorder.record(rec)
        if not self.metrics_file:
            return
        if jax.process_index() != 0:
            # multi-host: one writer, or a shared-storage file double-counts
            return
        # fresh runs truncate (a retry would otherwise interleave two step
        # sequences); checkpoint-resumed runs append to the prior stream
        mode = getattr(self, "_metrics_mode", None)
        if mode is None:
            mode = "a" if getattr(self, "_resumed", False) else "w"
        try:
            os.makedirs(os.path.dirname(os.path.abspath(self.metrics_file)), exist_ok=True)
            with open(self.metrics_file, mode) as f:
                f.write(json.dumps(record) + "\n")
            self._metrics_mode = "a"
        except OSError as e:
            logger.warning(f"metrics_file write failed (disabling): {e}")
            self.metrics_file = ""

    def _dump_flight(self, reason: str) -> None:
        """Training-side flight-recorder dump: lands next to the
        checkpoints (output_dir) so the postmortem travels with the run's
        artifacts; PFX_FLIGHT_RECORDER still overrides inside dump()."""
        if jax.process_index() != 0:
            # multi-host: one writer to the shared output_dir, same
            # convention as _write_metrics (rollback/preempt fire on
            # every process; host 0's ring is the canonical postmortem)
            return
        self._recorder.dump(
            path=os.path.join(self.output_dir, "flight_recorder.jsonl"),
            reason=reason,
        )

    def _update_registry(self, record: Dict, ips: float) -> None:
        """Mirror the logged step record onto the process-wide telemetry
        registry (scraped by any /metrics surface this process hosts).
        Cumulative values are ``set`` from the engine's own counters —
        exporter style — so a resumed run reports monotonic totals."""
        reg = self._registry
        reg.counter("pfx_train_steps_total").set(self._step)
        reg.counter("pfx_train_tokens_total").set(
            self._consumed_samples * (self.module.tokens_per_sample or 1)
        )
        reg.gauge("pfx_train_loss").set(record["loss"])
        reg.gauge("pfx_train_tokens_per_second").set(round(ips, 1))
        reg.counter("pfx_train_data_wait_seconds_total").set(
            record.get("data_wait_s", 0.0)
        )
        reg.counter("pfx_train_host_seconds_total").set(
            record.get("host_s", 0.0)
        )
        if self._compile_s is not None:
            reg.gauge("pfx_train_compile_seconds").set(round(self._compile_s, 3))
        if "model_flops" in record:
            reg.gauge("pfx_train_model_flops_per_second").set(
                record["model_flops"]
            )
        if "mfu" in record:
            reg.gauge("pfx_train_mfu").set(record["mfu"])
        publish = getattr(self.module, "publish_record", None)
        if publish is not None:
            # the module's own families, from its keys of the record
            publish(reg, record)

    def _format_model_stats(self, stats_step: int, vals: Dict) -> Dict:
        """Shape one fetched per-group statistic set for the step record
        (and mirror it onto the pfx_train_group_* gauges): group names in
        canonical order plus parallel value lists — compact enough for
        JSONL, self-describing enough for tools/report.py."""
        names = list(self._group_spec.names)
        out: Dict[str, Any] = {"step": int(stats_step), "groups": names}
        reg = self._registry
        gauge_of = {
            "grad_norm": "pfx_train_group_grad_norm",
            "param_norm": "pfx_train_group_param_norm",
            "update_ratio": "pfx_train_group_update_ratio",
            "nonfinite_frac": "pfx_train_group_nonfinite_frac",
        }
        for key in ("grad_norm", "param_norm", "update_norm",
                    "update_ratio", "nonfinite_frac"):
            row = [round(float(v), 6) for v in np.asarray(vals[key])]
            out[key] = row
            metric = gauge_of.get(key)
            if metric:
                for name, v in zip(names, row):
                    if math.isfinite(v):
                        reg.gauge(metric, group=name).set(v)
        return out

    def _sample_memory(self, record: Dict) -> None:
        """Attach a memory-watermark block to a step record and mirror it
        onto the pfx_mem_* gauges.  ``fit_peak_bytes`` is the highest
        SAMPLED in-use watermark THIS fit (worst device bytes_in_use
        where the backend reports it, host RSS otherwise, sampled at
        logging cadence) — the allocator's own ``device_peak_bytes`` is
        reported alongside but is process-lifetime (the backend never
        resets it, so it cannot be per-fit).  A loud warning fires once
        per fit when device headroom drops under
        PFX_MEM_WARN_HEADROOM."""
        from paddlefleetx_tpu.utils import model_stats as _ms

        wm = _ms.memory_watermarks()
        mem = {
            k: wm[k]
            for k in ("host_rss_bytes", "device_in_use_bytes",
                      "device_peak_bytes", "headroom_frac")
            if wm.get(k) is not None
        }
        watermark = wm.get("device_in_use_bytes") or wm.get("host_rss_bytes")
        if watermark:
            self._fit_peak_bytes = max(self._fit_peak_bytes or 0, watermark)
        if self._fit_peak_bytes:
            mem["fit_peak_bytes"] = self._fit_peak_bytes
        if mem:
            record["mem"] = mem
        _ms.export_memory_gauges(self._registry, wm)
        if not self._headroom_warned and _ms.warn_headroom(wm):
            self._headroom_warned = True

    def _drain_skip_events(self, loader) -> None:
        """Move the loader's structured ``data_skip`` events (appended by
        the skip budget, data/batch_sampler.py) into the metrics stream,
        stamped with the upcoming step."""
        events = getattr(loader, "skip_events", None)
        if not events:
            return
        while events:
            ev = dict(events.pop(0))
            ev.setdefault("step", self._step + 1)
            self._write_metrics(ev)

    def _require_concrete(self, op: str) -> None:
        if self.abstract_init:
            raise RuntimeError(
                f"Engine was built with abstract_init=True (fit-check "
                f"mode): state holds shapes, not arrays, so {op} is "
                "unavailable — only memory_report() works; rebuild the "
                "Engine without abstract_init to train"
            )

    def warm_start(self, batches: Iterable) -> Optional[list]:
        """What the module wants settled before the first optimizer step:
        ``module.warm_start_steps`` forward-only passes of
        ``module.warm_start_step`` over the next batches of ``batches`` (the
        expert layer's routing bias, docs/trinity_mini.md).  Once, and only
        in a run that starts at step 0: ``fit`` calls it with the train
        loader's iterator; a caller that wants the settled state earlier
        calls it first with batches of its own.  Returns what each pass
        reported, fetched (None where there was nothing to do)."""
        steps = getattr(self.module, "warm_start_steps", 0)
        if not steps or self._step > 0 or self._warm_started:
            return None
        self._require_concrete("warm_start")
        self._warm_started = True
        t0 = time.monotonic()
        one = jax.jit(
            lambda p, e, b, i: self.module.warm_start_step(p, e, b, i, ctx=self.ctx),
            out_shardings=(self.extra_shardings, None),
        )
        seen = []
        for i, batch in zip(range(steps), batches):
            st = self.state
            extra, info = one(st.params, st.extra, self._put_batch(batch), jnp.float32(i))
            self.state = TrainState(st.step, st.params, st.opt_state, extra, st.scaler)
            self._consumed_samples += self.global_batch_size
            seen.append(info)
        seen = jax.device_get(seen)
        self._write_metrics({"event": "warm_start", "passes": len(seen),
                             "seconds": round(time.monotonic() - t0, 3),
                             "reported": jax.tree.map(lambda a: np.asarray(a).tolist(), seen)})
        return seen

    def fit(self, train_loader: Iterable, eval_loader: Optional[Iterable] = None):
        """Training loop (reference fit/_fit_impl eager_engine.py:422-520).

        Preemption-aware: SIGTERM/SIGINT finishes the in-flight step, joins
        any async save, writes a final checkpoint with a ``preempted``
        marker, and returns with ``self.preempted`` set — the launcher
        (tools/train.py) then exits 0 so a relaunch auto-resumes.

        Data-pipeline contract (docs/data_pipeline.md): the engine holds
        the train loader for checkpoint meta (stream position + skip
        budget), rewinds it on anomaly rollback when it supports
        ``rewind``, drains its structured ``data_skip`` events into the
        metrics stream, and CLOSES both loaders on the way out so
        prefetch threads / worker pools never outlive the loop."""
        self._require_concrete("fit")
        t_last = time.time()
        window_tokens = 0
        eval_iter = iter(eval_loader) if eval_loader is not None else None
        tokens_per_sample = self.module.tokens_per_sample or 1
        self._train_loader = train_loader
        # resumed checkpoints carry loader state (skip budget spent so a
        # rotten shard cannot earn a fresh budget every crash-loop lap);
        # the stream position itself was already applied when the loader
        # was built from this engine's _consumed_samples
        # consumed unconditionally: a stale entry must never leak into a
        # later fit() with a different loader
        loader_state, self._loader_state = self._loader_state, None
        if loader_state and hasattr(train_loader, "load_state"):
            train_loader.load_state(loader_state)

        from paddlefleetx_tpu.utils.resilience import PreemptionGuard

        self.preempted = False
        # per-fit observatory state: stats stashed for the next logging
        # fetch, the memory watermark peak, and the once-per-fit headroom
        # warning latch
        self._pending_stats = None
        self._fit_peak_bytes = None
        self._headroom_warned = False
        preempt = PreemptionGuard().install()
        try:
            return self._fit_loop(
                train_loader, eval_iter, tokens_per_sample, t_last,
                window_tokens, preempt
            )
        finally:
            preempt.uninstall()
            # flush an in-flight trace even when a step raises
            self.profiler.close()
            # reclaim loader machinery (prefetch thread, worker pool)
            # before returning: an abandoned daemon thread blocked on a
            # fetch is a leak the interpreter drags to shutdown
            for ldr in (train_loader, eval_loader):
                close = getattr(ldr, "close", None)
                if callable(close):
                    try:
                        close()
                    except Exception as e:  # noqa: BLE001 — best-effort
                        logger.warning(f"loader close failed: {e}")
            # a checkpoint still writing in background must become durable
            # before fit returns (callers may exit the process right after)
            self.wait_for_save()

    def _build_anomaly_guard(self):
        from paddlefleetx_tpu.utils.resilience import AnomalyGuard

        if not self.res_enable or (
            self.res_max_skip_streak <= 0 and self.res_spike_zscore <= 0
        ):
            return None
        return AnomalyGuard(
            max_skip_streak=self.res_max_skip_streak,
            spike_zscore=self.res_spike_zscore,
            spike_streak=self.res_spike_streak,
            window=self.res_loss_window,
        )

    def _rollback(self, step: int, reason: str, rollbacks: int,
                  nonfinite_groups: Optional[list] = None) -> bool:
        """Anomaly response: restore params+opt-state from the last good
        checkpoint and let the loop re-enter from there.  Bounded: past
        ``resilience.max_rollbacks`` (or with no checkpoint to return to)
        the run fails loudly instead of thrashing.

        ``nonfinite_groups`` is the non-finite-provenance list (canonical
        group order, first entry = first offending layer group) observed
        on the step that tripped the guard; it rides the ``rollback``
        event and the flight postmortem so the postmortem names a
        culprit layer, not just "found_inf fired".

        Returns True when the data stream was REWOUND to the checkpoint
        position (loader supports ``rewind``): the caller must re-iter()
        the loader, and the replayed loss stream is then a token-for-token
        repeat of what an uninterrupted run would have produced.  False =
        legacy behavior (stream keeps its live position, same contract as
        a process restart mid-epoch without loader state)."""
        # an async save may be seconds from durable: join it first so its
        # checkpoint counts as the rollback target (the finisher thread is
        # what records _last_good_ckpt)
        self.wait_for_save()
        if self._last_good_ckpt is None:
            raise RuntimeError(
                f"anomaly budget exceeded at step {step} ({reason}) and no "
                "checkpoint exists to roll back to — enable periodic saves "
                "(Engine.save_load.save_steps) or disable the guard "
                "(Engine.resilience.enable=False)"
            )
        if rollbacks >= self.res_max_rollbacks:
            raise RuntimeError(
                f"anomaly budget exceeded at step {step} ({reason}) after "
                f"{rollbacks} rollback(s) — max_rollbacks="
                f"{self.res_max_rollbacks} exhausted; the run is not "
                "recovering, stopping instead of thrashing"
            )
        loader = self._train_loader
        rewindable = loader is not None and hasattr(loader, "rewind")
        culprit = (
            f" (first non-finite group(s): {', '.join(nonfinite_groups[:3])})"
            if nonfinite_groups else ""
        )
        logger.error(
            f"ANOMALY at step {step}: {reason}{culprit}; rolling back to "
            f"{self._last_good_ckpt} (rollback {rollbacks + 1}/"
            f"{self.res_max_rollbacks})"
        )
        event = {
            "event": "rollback",
            "step": step,
            "reason": reason,
            "ckpt": self._last_good_ckpt,
            "rollback_index": rollbacks + 1,
            "rewound": bool(rewindable),
        }
        if nonfinite_groups:
            event["nonfinite_groups"] = list(nonfinite_groups)
        self._write_metrics(event)
        # postmortem dump: the ring (recent step records, the rollback
        # event, any data_skips) hits disk NOW — if the post-rollback
        # replay diverges again and max_rollbacks kills the run, the
        # window that tripped the guard is already preserved
        self._registry.counter("pfx_train_rollbacks_total").inc()
        self._dump_flight(f"anomaly_rollback: {reason}")
        # the LIVE data-stream position: every step served so far plus the
        # just-dispatched (discarded) batch — needed only on the legacy
        # (non-rewindable) path, where load() resets the counter to the
        # checkpoint's value but the stream cannot rewind; leaving the
        # stale count would make the next save record a consumed_samples
        # behind the true stream, and a later crash+auto_resume would then
        # re-serve batches, breaking the resume-parity contract.
        live_consumed = self._consumed_samples + self.global_batch_size
        self.load(self._last_good_ckpt)
        # load() parked the ckpt's loader state for the NEXT fit(); this
        # fit applies it here (or discards it on the legacy path — it must
        # not leak into a later fit() against a different loader)
        loader_state, self._loader_state = self._loader_state, None
        if rewindable:
            # rewindable loader: put the stream back at the checkpoint
            # position so the post-rollback run REPLAYS the failed window
            # token-for-token (the replay is what proves the rollback
            # recovered — a diverging replay re-trips the guard).  The
            # ckpt's full loader state also restores the skip budget to
            # its checkpoint value: the replayed window re-hits any
            # corrupt sample, and keeping the live count would charge
            # max_skips twice for the same record
            if loader_state and hasattr(loader, "load_state"):
                loader.load_state(loader_state)
            else:
                loader.rewind(self._consumed_samples)
            logger.warning(
                f"data stream rewound to consumed_samples="
                f"{self._consumed_samples} for a token-for-token replay"
            )
            return True
        self._consumed_samples = live_consumed
        return False

    def _preempt_save(self, step: int, cause: str) -> None:
        """Final checkpoint on the clean-exit path (signal or
        exit_after_save): join any in-flight async write first so the two
        saves can't interleave, then save with the ``preempted`` marker.

        When the periodic save already wrote this exact step (signal
        landing on a save boundary), only the meta marker is re-stamped —
        re-writing multi-GB arrays inside the preemption grace window for
        a flag would be the worst possible use of that window."""
        logger.warning(
            f"{cause} at step {step}: writing final checkpoint, then "
            "exiting cleanly for auto-resume"
        )
        self.wait_for_save()
        expected = os.path.abspath(os.path.join(self.output_dir, f"step_{step}"))
        if self._last_good_ckpt == expected:
            try:
                with open(os.path.join(expected, "meta.json")) as f:
                    meta = json.load(f)
            except (OSError, json.JSONDecodeError):
                meta = {"step": step, "consumed_samples": self._consumed_samples}
            meta["preempted"] = True
            self._write_meta(expected, meta)
            path = expected
            logger.info(f"preempt marker stamped on existing {expected}")
        else:
            path = self.save(preempted=True)
            self.wait_for_save()
        self._write_metrics(
            {"event": "preempt_save", "step": step, "cause": cause, "ckpt": path}
        )
        # the process is about to exit (or be killed if the grace window
        # runs out): leave the flight ring on disk alongside the ckpt
        self._registry.counter("pfx_train_preempt_saves_total").inc()
        self._dump_flight(f"preempt_save: {cause}")
        self.preempted = True

    def _fit_loop(self, train_loader, eval_iter, tokens_per_sample,
                  t_last, window_tokens, preempt=None):
        from paddlefleetx_tpu.utils import resilience
        from paddlefleetx_tpu.utils.telemetry import ledger_span
        from paddlefleetx_tpu.utils.tracing import get_trace_buffer

        guard = self._build_anomaly_guard()
        # deep-dive tracing (sampled, docs/observability.md): one trace
        # per fit; each logged window appends a span mirroring the step
        # record's phase fields, and the record carries the trace_id so
        # a JSONL row links to its timeline.  None at PFX_TRACE_SAMPLE=0
        # — the loop then does zero tracing work.
        fit_trace = get_trace_buffer().maybe_start("train")
        window_t0 = time.monotonic()
        # goodput time ledger (docs/observability.md "Goodput ledger"):
        # everything since loop_t0 is attributed to one of
        # compile/data_wait/host/eval, and the unattributed remainder is
        # device_step — dispatched device compute the async-dispatch loop
        # never blocks on.  Buckets are exhaustive by construction.
        loop_t0 = time.monotonic()
        # metrics of the previous step, observed AFTER the next step has
        # been dispatched: step N-1 necessarily finished before step N
        # runs on device, so the fetch resolves while step N computes and
        # the guard never idles the device (async dispatch stays ahead)
        prev_metrics = None
        rollbacks = 0
        # per-phase accounting (docs/observability.md): cumulative seconds
        # this fit spent blocked on data (consumer-side next()), on the
        # host path (batch placement + step dispatch), in eval, in the
        # blocking fetch of a logged step's metrics and in building and
        # writing its record.  Each stamp pair is one ``ledger_span``, so
        # the same interval is a ``pfx.train.*`` span in a profiler trace.
        # Pure monotonic host clocks — no device sync is added to the hot
        # path.  ("compile" only parks the first dispatch's seconds.)
        ledger = {
            "compile": 0.0, "data_wait": 0.0, "host": 0.0, "eval": 0.0,
            "log_fetch": 0.0, "log_write": 0.0,
        }
        # host_gap: seconds from a blocking log fetch returning (the
        # device has drained) to the next step's dispatch having returned
        host_gap_total = 0.0
        t_fetched = None
        steps_in_window = 0
        data_iter = iter(train_loader)
        self.warm_start(data_iter)
        self._stall.stamp()
        while True:
            with jax.profiler.StepTraceAnnotation(
                "pfx.train.step", step_num=self._step + 1
            ):
                # the slow-iteration watcher's view of this pass: its start,
                # the ledger as it stood, and what beside a step it did
                t_pass = time.monotonic()
                led0 = (ledger["data_wait"], ledger["host"] + ledger["compile"],
                        ledger["log_fetch"], ledger["log_write"])
                kind = "step"
                try:
                    with ledger_span("pfx.train.data_wait", ledger, "data_wait"):
                        batch = next(data_iter)
                except StopIteration:
                    break
                if self._step >= self.max_steps:
                    break
                self._drain_skip_events(train_loader)
                if resilience.maybe_fire("nan_grads", self._step + 1):
                    batch = resilience.poison_batch(batch)
                first = self._compile_s is None
                with ledger_span("pfx.train.put_dispatch", ledger,
                                 "compile" if first else "host") as disp:
                    dev_batch = self._put_batch(batch)
                    self.state, metrics = self._train_step(self.state, dev_batch)
                if t_fetched is not None:
                    host_gap_total += disp.t1 - t_fetched
                    t_fetched = None
                if (
                    self._group_spec is not None
                    and (self._step + 1) % self.model_stats_every == 0
                ):
                    # device REFS only (no sync): the stats branch just ran
                    # in-graph; the arrays are fetched with the next logging
                    # fetch and attached to that record
                    self._pending_stats = (self._step + 1, metrics["model_stats"])
                if first:
                    # the first dispatch traces + compiles synchronously inside
                    # the jit call: time it separately (compile_s) and restart
                    # the throughput window so ips/mfu never average the
                    # compile into the first window
                    self._compile_s = disp.seconds
                    t_last = time.time()
                if guard is not None and prev_metrics is not None:
                    pm = jax.device_get(prev_metrics)
                    reason = guard.observe(
                        float(pm["loss"]), float(pm["found_inf"]) > 0
                    )
                    if reason is not None:
                        # the step just dispatched is discarded along with the
                        # anomalous state: load() replaces self.state and
                        # restores the step/consumed counters from the meta.
                        # A rewindable loader is rewound to the checkpoint
                        # position (token-for-token replay); otherwise the
                        # stream keeps its live position — same contract as a
                        # process restart mid-epoch.
                        culprits = None
                        if self._group_spec is not None and "group_nonfinite" in pm:
                            from paddlefleetx_tpu.utils.model_stats import (
                                nonfinite_group_names,
                            )

                            culprits = nonfinite_group_names(
                                self._group_spec, pm["group_nonfinite"]
                            ) or None
                        rewound = self._rollback(
                            self._step, reason, rollbacks,
                            nonfinite_groups=culprits,
                        )
                        rollbacks += 1
                        guard.reset()
                        prev_metrics = None
                        # stats stashed from the discarded window must not
                        # label a post-rollback record
                        self._pending_stats = None
                        if rewound:
                            # position is read at iter() time: restart the
                            # iteration so the replay starts AT the checkpoint
                            data_iter = iter(train_loader)
                        continue
                if guard is not None:
                    prev_metrics = {
                        "loss": metrics["loss"], "found_inf": metrics["found_inf"]
                    }
                    if self._group_spec is not None:
                        # provenance rides the guard's existing step-behind
                        # fetch: [G] int32, no extra sync
                        prev_metrics["group_nonfinite"] = metrics["group_nonfinite"]
                self._consumed_samples += self.global_batch_size
                window_tokens += self.global_batch_size * tokens_per_sample
                steps_in_window += 1
                self._step += 1
                step = self._step
                tracing = self.profiler.active
                self.profiler.step(step)
                if self.profiler.active != tracing:
                    kind = "step:profile"  # a trace started or was written

                if step % self.logging_freq == 0:
                    # ONE host fetch: the step metrics plus any pending
                    # model-stats arrays stashed at the last cadence step —
                    # the observatory's "stats ride the existing step-record
                    # device fetch" contract
                    pending_stats, self._pending_stats = self._pending_stats, None
                    with ledger_span("pfx.train.log_fetch", ledger,
                                     "log_fetch") as fetch:
                        if pending_stats is not None:
                            metrics, stats_vals = jax.device_get(
                                (metrics, pending_stats[1])
                            )
                        else:
                            metrics = jax.device_get(metrics)
                    t_fetched = fetch.t1
                    with ledger_span("pfx.train.log_write", ledger,
                                     "log_write"):
                        dt = time.time() - t_last
                        ips = window_tokens / dt
                        logger.info(
                            f"step {step}/{self.max_steps} loss: {float(metrics['loss']):.5f} "
                            f"lr: {float(metrics['lr']):.3e} grad_norm: {float(metrics['grad_norm']):.3f} "
                            f"ips: {ips:,.0f} tokens/s ({ips/self.mesh.size:,.0f}/device)"
                        )
                        record = {
                            "step": step,
                            "loss": float(metrics["loss"]),
                            "lr": float(metrics["lr"]),
                            "grad_norm": float(metrics["grad_norm"]),
                            "ips": round(ips, 1),
                            "consumed_samples": self._consumed_samples,
                            # phase breakdown: cumulative consumer-side data wait
                            # and host-side (placement+dispatch) seconds, plus the
                            # average wall seconds per step over this window —
                            # wall minus data/host is dispatched-device time
                            "tokens_per_sec": round(ips, 1),
                            "data_wait_s": round(ledger["data_wait"], 3),
                            "host_s": round(ledger["host"], 3),
                            "step_s": round(dt / max(1, steps_in_window), 4),
                            # cumulative, like the two above: the blocking fetch
                            # of logged steps' metrics, building and writing their
                            # records (through the previous record), and the
                            # seconds the drained device then waited for its next
                            # dispatch
                            "log_fetch_s": round(ledger["log_fetch"], 4),
                            "log_write_s": round(ledger["log_write"], 4),
                            "host_gap_s": round(host_gap_total, 4),
                            # slow steps before this one (StallWatch): the
                            # seconds they ran past their median, and how many
                            "stall_s": round(self._stall.seconds, 4),
                            "stall_events": self._stall.events,
                        }
                        if "extra" in metrics:
                            record.update(self.module.extra_record(metrics["extra"]))
                        if self._compile_s is not None and not self._compile_emitted:
                            # first logged window: trace+compile seconds, timed at
                            # the first dispatch and excluded from the ips window
                            record["compile_s"] = round(self._compile_s, 3)
                            self._compile_emitted = True
                        if self._flops_per_token:
                            model_fps = ips * self._flops_per_token
                            record["model_flops"] = round(model_fps, 1)
                            if self._peak_flops:
                                record["mfu"] = round(
                                    model_fps / (self._peak_flops * self.mesh.size), 6
                                )
                        # data-pipeline health (prefetch depth, cumulative seconds
                        # the loop sat starved, skip budget spent) rides the same
                        # stream so dashboards see starvation next to throughput.
                        # The loader's own data_wait_s (producer-side, sees stalls
                        # the prefetch buffer hides from the loop) overrides the
                        # engine's consumer-side measurement when available.
                        stats_fn = getattr(train_loader, "stats", None)
                        if callable(stats_fn):
                            record.update(
                                (k, v) for k, v in stats_fn().items()
                                if k in ("data_wait_s", "prefetch_depth",
                                         "stall_warnings", "skips")
                            )
                        if pending_stats is not None:
                            record["model_stats"] = self._format_model_stats(
                                pending_stats[0], stats_vals
                            )
                        if (
                            self._group_spec is not None
                            and float(metrics.get("found_inf", 0.0)) > 0
                        ):
                            # non-finite provenance: this logged step was skipped;
                            # name the offending group(s) right on the record
                            from paddlefleetx_tpu.utils.model_stats import (
                                nonfinite_group_names,
                            )

                            record["found_inf"] = 1
                            record["nonfinite_groups"] = nonfinite_group_names(
                                self._group_spec, metrics["group_nonfinite"]
                            )
                        # memory watermarks: host-side accounting only (device
                        # memory_stats where the backend has it, host RSS always)
                        self._sample_memory(record)
                        if fit_trace is not None:
                            # mirror the record's phase fields as a trace span:
                            # the step-record JSONL and the Perfetto timeline
                            # describe the SAME window, linked by trace_id
                            now_mono = time.monotonic()
                            fit_trace.span(
                                "step_window", t0=window_t0, t1=now_mono,
                                step=step, loss=record["loss"],
                                tokens_per_sec=record["tokens_per_sec"],
                                data_wait_s=record["data_wait_s"],
                                host_s=record["host_s"],
                                step_s=record["step_s"],
                            )
                            window_t0 = now_mono
                            record["trace_id"] = fit_trace.trace_id
                        self._update_registry(record, ips)
                        # time ledger: attribute the whole fit's wall clock from
                        # the loop's OWN accumulators (not the record — a loader
                        # stats() override swaps in producer-side data_wait_s,
                        # which would break closure against this thread's wall).
                        # Exporter-style .set(): totals stay monotonic per fit.
                        buckets = {
                            "compile": self._compile_s or 0.0,
                            "data_wait": ledger["data_wait"],
                            "host": ledger["host"],
                            "eval": ledger["eval"],
                        }
                        buckets["device_step"] = max(
                            0.0,
                            (time.monotonic() - loop_t0) - sum(buckets.values()),
                        )
                        reg = self._registry
                        for bname, bval in sorted(buckets.items()):
                            reg.counter(
                                "pfx_train_time_seconds_total", bucket=bname
                            ).set(round(bval, 4))
                        reg.counter("pfx_train_host_gap_seconds_total").set(
                            round(host_gap_total, 4)
                        )
                        # the record carries the same ledger so tools/report.py
                        # renders the stacked breakdown from artifacts alone
                        record["time_ledger"] = {
                            k: round(v, 3) for k, v in buckets.items()
                        }
                        self._write_metrics(record)
                    t_last = time.time()
                    window_tokens = 0
                    steps_in_window = 0
                    if self.logging_freq > 1:
                        kind = "step:log"  # the fetch drains the steps queued

                if self.consistency_check_freq and step % self.consistency_check_freq == 0:
                    from paddlefleetx_tpu.parallel.check import check_replica_consistency

                    kind = "step:check"
                    fp = check_replica_consistency(self.state.params)
                    logger.info(f"consistency check OK @ step {step}: params fp {fp:#010x}")
                    t_last = time.time()
                    window_tokens = 0
                    steps_in_window = 0
                    t_fetched = None  # not a bare log-to-dispatch gap

                if self.eval_freq and eval_iter is not None and step % self.eval_freq == 0:
                    # on_empty="event": a finite eval stream exhausting mid-fit
                    # logs loudly + emits a structured event instead of either
                    # nan-poisoning silently or killing the training run
                    kind = "step:eval"
                    with ledger_span("pfx.train.eval", ledger, "eval"):
                        self.evaluate(
                            eval_iter, iters=self.eval_iters, on_empty="event"
                        )
                    t_last = time.time()
                    window_tokens = 0
                    steps_in_window = 0
                    t_fetched = None  # not a bare log-to-dispatch gap

                if self.save_steps and step % self.save_steps == 0:
                    kind = "step:save"
                    self.save()
                    # a save landing while the guard sees a healthy stream is
                    # proof of recovery: the budget guards against rollback
                    # THRASH, not against independent anomalies days apart in
                    # a long run.  The streak check matters — saves fire on
                    # skipped steps too, and resetting mid-streak would let a
                    # persistent anomaly roll back forever.
                    if guard is None or (
                        guard.skip_streak == 0 and guard.spike_streak == 0
                    ):
                        rollbacks = 0
                    t_last = time.time()
                    window_tokens = 0
                    steps_in_window = 0
                    t_fetched = None  # not a bare log-to-dispatch gap
                    if self.exit_after_save:
                        # checkpoint-aligned clean exit: the save above is
                        # durable once wait_for_save joins (fit's finally);
                        # reuse the preempted flag so the launcher exits 0
                        logger.info(
                            f"exit_after_save: checkpoint at step {step} "
                            "complete, exiting cleanly"
                        )
                        self.wait_for_save()
                        self.preempted = True
                        break

                # every pass against the others of its kind (a plain step; one
                # that also drained a log window, checked, evaluated or saved)
                t_end = time.monotonic()
                dw = ledger["data_wait"] - led0[0]
                pd = ledger["host"] + ledger["compile"] - led0[1]
                lf = ledger["log_fetch"] - led0[2]
                lw = ledger["log_write"] - led0[3]
                ev = self._stall.observe(
                    kind, t_pass, t_end,
                    (dw, pd, lf, lw, max(0.0, t_end - t_pass - (dw + pd + lf + lw))),
                )
                if ev is not None:
                    self._stall.publish(
                        ev, step=step, consumed_samples=self._consumed_samples
                    )

                # fault injection: deliver a real SIGTERM to this process so
                # the handler path itself is what the test exercises
                sig_fired = resilience.maybe_fire("sigterm", step)
                if (preempt is not None and preempt.requested) or sig_fired:
                    self._preempt_save(step, "preemption signal")
                    break

        if fit_trace is not None:
            # finished cleanly; a crashed fit deliberately stays
            # done=false in the buffer — that IS the postmortem signal
            fit_trace.finish()
        return self.state

    def evaluate(self, loader: Iterable, iters: Optional[int] = None,
                 on_empty: str = "raise") -> float:
        """Average eval loss over up to ``iters`` batches.

        An empty/exhausted loader used to return ``float("nan")``
        silently, poisoning every downstream consumer of the value.  Now
        ``on_empty`` decides: ``"raise"`` (default — a CLI eval against
        no data is a config error and must be loud) or ``"event"``
        (ERROR log + structured ``eval_empty`` metrics/flight event +
        nan return — the in-fit periodic path uses this, where a finite
        eval stream legitimately exhausts mid-run and must not kill the
        training loop)."""
        self._require_concrete("evaluate")
        if on_empty not in ("raise", "event"):
            raise ValueError(
                f"on_empty={on_empty!r}: use 'raise' or 'event'"
            )
        # loaders iterate forever (epoch-looping sampler): always bound
        iters = iters if iters is not None else self.eval_iters
        losses = []
        # modules exposing predict_fn + build_metric (finetune) stream
        # predictions into a host-side metric accumulator (reference
        # GPTFinetuneModule validation_step, language_module.py:370-420)
        metric = None
        if hasattr(self.module, "build_metric") and hasattr(self.module, "predict_fn"):
            metric = self.module.build_metric()
        it = iter(loader)
        try:
            for i, batch in enumerate(it):
                if i >= iters:
                    break
                dev_batch = self._put_batch(batch)
                losses.append(float(self._eval_step(self.state, dev_batch, jnp.int32(i))))
                if metric is not None:
                    # fetched per-iteration: _put_batch may retrace the steps
                    # (zigzag positions install) and a stale closure would
                    # predict with the wrong causal mask
                    predict = self._get_predict_step()
                    preds = np.asarray(jax.device_get(predict(self.state, dev_batch)))
                    metric.update(preds, np.asarray(batch["labels"]))
        finally:
            # a fresh stream created from a loader (it is not loader) is
            # OURS to reclaim: abandoning a live prefetch iterator leaves
            # its producer thread spinning forever.  When the CALLER owns
            # the stream (fit passes its long-lived eval_iter, which
            # iter() returns unchanged), it stays live.
            if it is not loader:
                close = getattr(loader, "close", None)
                if callable(close):
                    close()
        if not losses:
            msg = (
                f"evaluate saw ZERO batches (iters={iters}): the eval "
                "loader is empty or exhausted — the old behavior returned "
                "nan and silently poisoned downstream records"
            )
            if on_empty == "raise":
                raise RuntimeError(msg)
            logger.error(msg)
            self._write_metrics(
                {"event": "eval_empty", "step": self._step, "iters": iters}
            )
            return float("nan")
        avg = float(np.mean(losses))
        if metric is not None:
            from paddlefleetx_tpu.models.metrics import format_metric

            vals = " ".join(f"{k}: {v:.4f}" for k, v in format_metric(metric).items())
            logger.info(f"eval loss: {avg:.5f} {vals}")
        else:
            logger.info(f"eval loss: {avg:.5f} (ppl {np.exp(min(avg, 20.0)):.2f})")
        return avg

    # ------------------------------------------------------------------
    # Checkpoint (reference save/load eager_engine.py:717-825 + apis/io.py)
    def _write_meta(self, path: str, meta: Dict[str, Any]) -> None:
        # meta.json is the checkpoint's completeness marker (written last,
        # checked by latest_checkpoint): write atomically so a crash can
        # never leave a truncated marker that wedges the restart loop.
        # Multi-host: one writer — concurrent os.replace from N processes
        # on shared storage is a needless race (reference: only dp_rank0
        # saves, apis/io.py:28-151)
        if jax.process_index() != 0:
            return
        tmp = os.path.join(path, "meta.json.tmp")
        with open(tmp, "w") as f:
            json.dump(meta, f)
        os.replace(tmp, os.path.join(path, "meta.json"))

    def wait_for_save(self) -> None:
        """Join an in-flight async save (no-op when none is pending).
        Re-raises any error the background write hit — a swallowed storage
        failure would let training run for hours believing checkpoints
        exist."""
        t = self._save_thread
        if t is not None:
            t.join()
            self._save_thread = None
            err = self._save_error
            self._save_error = None
            if err is not None:
                raise err

    def _finish_save(self, path: str, step: int) -> None:
        """Post-save bookkeeping shared by the sync and async paths: record
        the rollback target, run the fault-injection bit-rot hook, then the
        retention GC (which never deletes the recorded last-good dir).

        Known limit: "good" here means "saved and durable", not "loss
        verified healthy" — a save landing within the spike detector's
        observation window of a finite divergence can record diverging
        state as the rollback target; max_rollbacks then stops the thrash
        and older checkpoints stay on disk for a manual resume
        (docs/fault_tolerance.md)."""
        from paddlefleetx_tpu.utils import resilience

        self._last_good_ckpt = path
        resilience.maybe_fire("ckpt_truncate", step, path=path)
        if self.keep_last_n and jax.process_index() == 0:
            from paddlefleetx_tpu.utils.checkpoint import gc_checkpoints

            try:
                gc_checkpoints(
                    self.output_dir, self.keep_last_n, protect=self._last_good_ckpt
                )
            except OSError as e:
                # GC is best-effort housekeeping: a failed delete must not
                # take down the save (the checkpoint itself is durable)
                logger.warning(f"checkpoint retention GC failed: {e}")

    def _atexit_join(self) -> None:
        """Interpreter-exit safety net (registered once, first async save):
        a SIGTERM-driven sys.exit while ``_save_thread`` is in flight must
        not strand a meta-less directory — join the write so it either
        completes (meta.json lands) or its error is logged.  Errors are
        logged, not raised: atexit swallows exceptions anyway."""
        try:
            self.wait_for_save()
        except BaseException as e:  # noqa: BLE001 — last-chance reporting
            logger.error(f"async checkpoint write failed during exit: {e}")

    def save(self, path: Optional[str] = None, preempted: bool = False):
        """Checkpoint the full train state.  ``preempted=True`` stamps the
        meta (written by the preemption path) so operators and tooling can
        distinguish a scheduled save from a SIGTERM final save."""
        self._require_concrete("save")
        import orbax.checkpoint as ocp

        from paddlefleetx_tpu.utils import resilience

        step = int(self.state.step)
        path = os.path.abspath(path or os.path.join(self.output_dir, f"step_{step}"))
        payload = {"params": self.state.params, "opt_state": self.state.opt_state}
        if self.state.extra is not None:
            payload["extra"] = self.state.extra
        meta = {"step": step, "consumed_samples": self._consumed_samples}
        loader = self._train_loader
        if loader is not None and hasattr(loader, "state_dict"):
            # loader state rides the meta (docs/data_pipeline.md).  The
            # position is overwritten with the ENGINE's counter: the
            # sampler's own count runs ahead by the prefetch lookahead
            # (batches buffered but not yet trained on), and resuming
            # from it would silently drop those batches.
            loader_state = dict(loader.state_dict())
            loader_state["consumed_samples"] = self._consumed_samples
            # same lookahead correction for the skip budget: the live
            # count includes prefetched-but-untrained batches, and the
            # resumed replay of those batches re-spends it
            skips_at = getattr(loader, "skips_at", None)
            if callable(skips_at):
                skips = skips_at(self._consumed_samples)
                if skips is not None:
                    loader_state["skips"] = skips
            meta["loader"] = loader_state
        if preempted:
            meta["preempted"] = True
        if self.state.scaler is not None:
            meta["loss_scale"] = float(self.state.scaler["scale"])
            meta["scaler_good_steps"] = int(self.state.scaler["good_steps"])

        if self.async_save:
            # one in-flight save at a time: a second save against the same
            # checkpointer must wait for the first write to finish anyway
            self.wait_for_save()
            if self._async_ckptr is None:
                self._async_ckptr = ocp.AsyncCheckpointer(
                    ocp.StandardCheckpointHandler()
                )
            if not self._atexit_registered:
                # interpreter exit (sys.exit, end of main) must join the
                # background write: without this a clean exit right after
                # save() could leave a forever-incomplete directory when
                # the finisher thread loses the shutdown race.  Registered
                # over a weakref so atexit does not pin the Engine (and
                # its params/opt-state trees) for the process lifetime in
                # multi-Engine processes (test suites, notebooks).
                import atexit
                import weakref

                ref = weakref.ref(self)

                def _join_at_exit(ref=ref):
                    eng = ref()
                    if eng is not None:
                        eng._atexit_join()

                atexit.register(_join_at_exit)
                self._atexit_registered = True
            # returns once arrays are snapshotted to host — the training
            # loop may donate the live buffers immediately after; the
            # directory write continues in background
            self._async_ckptr.save(
                os.path.join(path, "state"),
                args=ocp.args.StandardSave(payload),
                force=True,
            )

            def finish(ckptr=self._async_ckptr, path=path, meta=meta, step=step):
                try:
                    ckptr.wait_until_finished()
                    resilience.maybe_fire("save_crash", step)
                    self._write_meta(path, meta)
                    logger.info(f"saved checkpoint (async): {path}")
                    self._finish_save(path, step)
                except BaseException as e:  # noqa: BLE001 — surfaced by
                    # wait_for_save; meta.json is never written, so resume
                    # correctly skips the incomplete directory
                    self._save_error = e

            import threading

            # non-daemon: a final save() right before process exit must not
            # be killed mid-write (interpreter joins non-daemon threads)
            self._save_thread = threading.Thread(target=finish, daemon=False)
            self._save_thread.start()
            return path

        from paddlefleetx_tpu.utils.resilience import retry

        ckptr = ocp.StandardCheckpointer()

        def write():
            ckptr.save(os.path.join(path, "state"), payload, force=True)
            ckptr.wait_until_finished()

        retry(write, desc=f"checkpoint save {path}")
        resilience.maybe_fire("save_crash", step)
        self._write_meta(path, meta)
        logger.info(f"saved checkpoint: {path}")
        self._finish_save(path, step)
        return path

    def load(self, path: str):
        self._require_concrete("load")
        import orbax.checkpoint as ocp

        from paddlefleetx_tpu.utils.resilience import retry

        self.wait_for_save()  # never restore over a half-written save
        path = os.path.abspath(path)
        ckptr = ocp.StandardCheckpointer()
        target = {
            "params": jax.tree.map(
                lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
                self.state.params,
                self.param_shardings,
            ),
            "opt_state": jax.tree.map(
                lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
                self.state.opt_state,
                self.opt_shardings,
            ),
        }
        if self.state.extra is not None:
            target["extra"] = jax.tree.map(
                lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
                self.state.extra,
                self.extra_shardings,
            )
        # transient-storage retry only: corruption raises ValueError from
        # the tensorstore layer and propagates immediately so the caller
        # (checkpoint.resume_with_fallback) can quarantine + fall back
        restored = retry(
            lambda: ckptr.restore(os.path.join(path, "state"), target),
            desc=f"checkpoint restore {path}",
        )
        # Deep-copy into XLA-owned buffers.  Orbax/tensorstore-born arrays
        # can be zero-copy views of host memory the restore pipeline still
        # owns; the train step DONATES its state (donate_argnums=0), and
        # donating such a view corrupts the first post-resume update
        # (non-finite params, occasionally a shutdown segfault) once the
        # persistent compile cache makes the executable available before
        # the restore buffers settle.  Found by the crash-resume parity
        # tests (tests/test_fault_injection.py); the copy is one-time load
        # cost and makes every restored leaf donation-safe.
        restored = jax.tree.map(lambda x: x.copy(), restored)
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
        self._consumed_samples = int(meta.get("consumed_samples", 0))
        self._step = int(meta["step"])
        # loader state (skip budget spent, …) applied to the train loader
        # at the next fit(); the position itself flows through
        # _consumed_samples -> build_dataloader
        self._loader_state = meta.get("loader")
        self._resumed = True  # metrics stream appends instead of truncating
        scaler = None
        if self.use_loss_scaling:
            scaler = {
                "scale": jnp.float32(meta.get("loss_scale", self.init_loss_scaling)),
                "good_steps": jnp.int32(meta.get("scaler_good_steps", 0)),
            }
        self.state = TrainState(
            step=jnp.asarray(meta["step"], jnp.int32),
            params=restored["params"],
            opt_state=restored["opt_state"],
            extra=restored.get("extra"),
            scaler=scaler,
        )
        # a checkpoint that restored IS verified-good: it becomes the
        # anomaly-rollback target until the next successful save
        self._last_good_ckpt = path
        logger.info(f"loaded checkpoint: {path} (step {meta['step']})")
