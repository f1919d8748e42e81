"""``runners/serve_arch.py`` for a configuration whose residual stream is
several copies mixed by maps (``runners/serve_hc.md``): the same ``Server``,
``run`` and ``judge``, loaded by path as ``serve_paged.py`` loads them, with
the child ``serve_hc_child.py``, which runs ``serve_arch_child.py`` under the
limits the configuration's file states (``reference_limits``), keeps its
routing bias, hands the reference's own controls through and adds the maps'
part of the check."""

import importlib.util
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


arch = _load("pfx_bench_runners_serve_arch", os.path.join(BENCH, "runners", "serve_arch.py"))
arch.CHILD = os.path.join(BENCH, "runners", "serve_hc_child.py")  # what its Server starts
Server, run, judge = arch.Server, arch.run, arch.judge
