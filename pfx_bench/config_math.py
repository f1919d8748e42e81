"""The arithmetic file a configuration names under ``math`` (for example
``pfx_bench/math/afmoe.py``), loaded by path.  Not a reader itself: the
readers that need a configuration's own FLOP counts share it."""

import importlib.util
import os

import common


def load(ctx):
    rel = ctx.get("math")
    if not rel:
        return None
    path = os.path.join(common.ROOT, rel)
    if not os.path.isfile(path):
        return None
    spec = importlib.util.spec_from_file_location("pfx_bench_config_math", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
