"""Evaluation entry point (reference tools/eval.py:34-54)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddlefleetx_tpu.utils.device import apply_platform_env

apply_platform_env()  # tpu unless a CPU pin is set; before backend init

from paddlefleetx_tpu.core.engine import Engine
from paddlefleetx_tpu.core.module import build_module
from paddlefleetx_tpu.data.builders import build_dataloader
from paddlefleetx_tpu.parallel.env import init_dist_env
from paddlefleetx_tpu.utils.config import get_config, parse_args


def main(argv=None):
    args = parse_args(argv)
    cfg = get_config(args.config, overrides=args.override)
    mesh = init_dist_env(cfg)
    module = build_module(cfg)
    with mesh:
        engine = Engine(cfg, module, mesh)
        ckpt_dir = cfg.Engine.save_load.get("ckpt_dir")
        if ckpt_dir:
            engine.load(ckpt_dir)
        loader = build_dataloader(cfg, "Eval")
        engine.evaluate(loader, iters=int(cfg.Engine.get("eval_iters", 10)))


if __name__ == "__main__":
    main()
