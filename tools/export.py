"""Export entry point (reference tools/export.py:33-50): stage the model's
forward to a serialized StableHLO artifact + params checkpoint."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddlefleetx_tpu.utils.device import apply_platform_env

apply_platform_env()  # tpu unless a CPU pin is set; before backend init


from paddlefleetx_tpu.core.module import build_module
from paddlefleetx_tpu.parallel.env import init_dist_env
from paddlefleetx_tpu.parallel.seed import get_seed_tracker
from paddlefleetx_tpu.utils.config import get_config, parse_args
from paddlefleetx_tpu.utils.export import export_inference_model


def main(argv=None):
    args = parse_args(argv)
    cfg = get_config(args.config, overrides=args.override)
    init_dist_env(cfg)
    module = build_module(cfg)

    from paddlefleetx_tpu.utils.checkpoint import load_pretrained_params

    params = load_pretrained_params(cfg)
    if params is None:
        params = module.init_params(get_seed_tracker().params_key())

    # family-generic: each module declares its inference forward + example
    # inputs (reference input_spec contract, basic_module.py:29-86)
    fwd, example_args = module.export_spec()

    out_dir = cfg.Engine.save_load.get("output_dir", "./output")
    export_inference_model(fwd, example_args, params, os.path.join(out_dir, "inference"))


if __name__ == "__main__":
    main()
