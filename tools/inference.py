"""Inference entry point (reference tools/inference.py:37-59): load the
exported artifact (or build the module live), compile over the configured
mesh, run a batch, report latency."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddlefleetx_tpu.utils.device import apply_platform_env

apply_platform_env()  # tpu unless a CPU pin is set; before backend init

import numpy as np

from paddlefleetx_tpu.core.inference_engine import CompileConfig, InferenceEngine
from paddlefleetx_tpu.core.module import build_module
from paddlefleetx_tpu.parallel.env import init_dist_env
from paddlefleetx_tpu.parallel.seed import get_seed_tracker
from paddlefleetx_tpu.utils.config import get_config, parse_args
from paddlefleetx_tpu.utils.log import logger


def main(argv=None):
    args = parse_args(argv)
    cfg = get_config(args.config, overrides=args.override)
    mesh = init_dist_env(cfg)

    inf_cfg = cfg.get("Inference", {})
    compile_cfg = CompileConfig.from_config(inf_cfg)
    model_dir = inf_cfg.get("model_dir")

    if model_dir:
        engine = InferenceEngine.from_export(model_dir, compile_cfg=compile_cfg)
        seq = int(inf_cfg.get("max_seq_len", 128))
        tokens = np.zeros((int(inf_cfg.get("batch_size", 1)), seq), np.int32)
        out = engine.predict(tokens)
    else:
        # live-module path (no export artifact): TP-shard params over mesh
        from jax.sharding import NamedSharding, PartitionSpec as P

        from paddlefleetx_tpu.models.gpt import model as gpt
        from paddlefleetx_tpu.parallel.sharding import (
            make_rules,
            tree_logical_to_sharding,
        )

        if cfg.Model.get("module", "GPTModule") not in ("GPTModule", "GPTGenerationModule"):
            raise ValueError(
                "live-module inference currently serves the GPT forward; "
                f"got module={cfg.Model.get('module')} — export it first and "
                "set Inference.model_dir"
            )
        module = build_module(cfg)
        from paddlefleetx_tpu.utils.checkpoint import load_pretrained_params

        params = load_pretrained_params(cfg)
        if params is None:
            params = module.init_params(get_seed_tracker().params_key())
        rules = make_rules()
        shardings = tree_logical_to_sharding(module.logical_axes(), mesh, rules)
        mcfg = module.config
        seq = int(inf_cfg.get("max_seq_len", mcfg.max_position_embeddings))
        tokens = np.zeros((int(inf_cfg.get("batch_size", 1)), seq), np.int32)

        engine = InferenceEngine(
            lambda p, t: gpt.forward(p, t, mcfg, train=False),
            params,
            mesh=mesh,
            param_shardings=shardings,
            batch_spec=NamedSharding(mesh, P("data")),
            compile_cfg=compile_cfg,
        )
        out = engine.predict(tokens)

    stats = engine.benchmark(tokens, iters=int(inf_cfg.get("bench_iters", 5)))
    logger.info(
        f"inference ok: output {np.asarray(out).shape} "
        f"latency {stats['latency_ms']:.1f}ms qps {stats['qps']:.1f}"
    )


if __name__ == "__main__":
    main()
