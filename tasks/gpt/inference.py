"""Engine-mode inference demo (reference tasks/gpt/inference.py:36-61):
build the module, wrap it in the serving engine, generate a completion
for a prompt — the deploy-path counterpart of tasks/gpt/generation.py.

  python tasks/gpt/inference.py -c configs/gpt/pretrain_gpt_345M_single.yaml \
      [-o Generation.prompt='...'] [-o Generation.tokenizer_dir=out/gpt2]

For serving an exported StableHLO artifact (tools/export.py output) use
``tools/inference.py`` — that path executes the serialized graph itself.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from paddlefleetx_tpu.utils.device import apply_platform_env

apply_platform_env()  # tpu unless a CPU pin is set; before backend init

from paddlefleetx_tpu.core.module import build_module
from paddlefleetx_tpu.core.serving import GenerationServer
from paddlefleetx_tpu.parallel.env import init_dist_env
from paddlefleetx_tpu.utils.config import get_config, parse_args
from paddlefleetx_tpu.utils.log import logger


def main(argv=None):
    args = parse_args(argv)
    cfg = get_config(args.config, overrides=args.override)
    mesh = init_dist_env(cfg)
    module = build_module(cfg)

    gen_cfg = cfg.get("Generation", {})
    tokenizer_dir = gen_cfg.get("tokenizer_dir")
    tok = None
    if tokenizer_dir:
        from paddlefleetx_tpu.data.tokenizers.gpt_tokenizer import GPTTokenizer

        tok = GPTTokenizer.from_pretrained(tokenizer_dir)

    server = GenerationServer(cfg, mesh, module, tokenizer=tok)

    prompt_text = gen_cfg.get("prompt", "Hi, GPT2. Tell me who Jack Ma is.")
    if tok is not None:
        out = server.generate_text([prompt_text])[0]
        logger.info(f"Prompt: {prompt_text!r}")
        logger.info(f"Generation: {(prompt_text + out)!r}")
    else:
        ids = [1, 2, 3, 4]
        outs = server.generate_ids([ids])
        logger.info(f"Prompt ids: {ids}")
        logger.info(f"Generated ids: {outs[0]}")


if __name__ == "__main__":
    main()
