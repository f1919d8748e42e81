"""The cells' programs, as text: each program of ``tools/program_text.py`` (the
decode step and a prefill of the five served configurations, and the two train
steps) is lowered for a described v5e and its hash compared with
``tests/program_text.json``.

A PR that adds a block family BESIDE them leaves that file alone, and this test
is the proof that the older cells run the programs they ran; a PR that means to
change one of them rewrites the file (``python tools/program_text.py --write``)
and says so.  The older cells' lines were written from PR 38's commit, before the
Falcon-H1 block (a ``P`` layer, rotation at given positions, the fold) was added
beside them: PR 40 left them as they were and added its own two.  PR 45 added
the Mellum2 pair and the 345M train step from its parent's code, before it took
anything away beside them.

That hash masks every Mosaic kernel's body (line numbers ride in it), so it does
not see what a kernel COMPUTES.  ``tests/kernel_bodies.json`` (PR 56) keeps that
beside it: each program's kernels as hashes of their modules printed without
locations.  PR 56 wrote it from its own tree once every line of it but the 345M
step's two flash kernels (the ones it replaced) was what its parent's tree
printed."""

import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
import program_text  # noqa: E402

with open(program_text.GOLDEN) as _f:
    KEPT = json.load(_f)
with open(program_text.KERNELS) as _f:
    KEPT_KERNELS = json.load(_f)
_LOWERED = {}  # program name -> (its hash, its kernels): each program is lowered once for both tests


@pytest.fixture(scope="module")
def described_chip():
    from jax.experimental import topologies

    try:
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu / unknown topology on this install
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {exc!r}")


@pytest.fixture(autouse=True)
def _chip_programs(monkeypatch):
    """What ``program_text.lowered`` switches for its process, put back after each test."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from paddlefleetx_tpu.utils import device as device_mod

    monkeypatch.setattr(device_mod, "pallas_interpret", device_mod.pallas_interpret)
    prev = jax.config.jax_enable_compilation_cache
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _lowered(name):
    if name not in _LOWERED:
        (_, program), = program_text.lowered(os.path.dirname(program_text.HERE), (name,))
        _LOWERED[name] = program_text.digest(program), program_text.kernel_bodies(program)
    return _LOWERED[name]


@pytest.mark.parametrize("kept", [KEPT, KEPT_KERNELS], ids=["programs", "kernels"])
def test_the_kept_file_names_every_program(kept):
    assert tuple(kept) == program_text.PROGRAMS


@pytest.mark.parametrize("name", program_text.PROGRAMS)
def test_an_older_cell_s_kernels_are_the_kept_ones(described_chip, name):
    got = _lowered(name)[1]
    assert got == KEPT_KERNELS[name], (
        f"a kernel of {name} is not the one tests/kernel_bodies.json keeps (its operations, their order or "
        "its tile changed): if that is meant, run `python tools/program_text.py --write` and say so in "
        "CHANGES.md; if a rewrite was meant to leave it alone, `--bodies` on both trees shows which moved")
    assert all(re.fullmatch(r"pfx_\w+:[0-9a-f]{20}", k) for k in got) and len(set(got)) == len(got)


def test_a_kernel_s_hash_ignores_where_its_lines_stand_and_sees_what_it_computes(described_chip):
    """The same kernel called from two source lines hashes the same (its program's
    masked text would too); one operation more does not."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    x = jax.ShapeDtypeStruct((8, 128), jnp.float32, sharding=one)

    def double(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def double_here_instead(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def double_and_one(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0 + 1.0

    def bodies(kernel):
        call = pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32), name="pfx_probe")
        return program_text.kernel_bodies(jax.jit(call).lower(x))

    a, b, c = bodies(double), bodies(double_here_instead), bodies(double_and_one)
    assert len(a) == 1 and a[0].startswith("pfx_probe:")
    assert a == b and a != c


@pytest.mark.parametrize("name", program_text.PROGRAMS)
def test_an_older_cell_s_program_text_is_the_kept_one(described_chip, name):
    assert _lowered(name)[0] == KEPT[name], (
        f"{name} lowers to another program than tests/program_text.json keeps: if that is meant, "
        "run `python tools/program_text.py --write` and say so in CHANGES.md")
