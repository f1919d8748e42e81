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
anything away beside them."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
import program_text  # noqa: E402

with open(program_text.GOLDEN) as _f:
    KEPT = json.load(_f)


@pytest.fixture(scope="module")
def described_chip():
    from jax.experimental import topologies

    try:
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu / unknown topology on this install
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {exc!r}")


@pytest.fixture(autouse=True)
def _chip_programs(monkeypatch):
    """What ``program_text.lower`` switches for its process, put back after each test."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from paddlefleetx_tpu.utils import device as device_mod

    monkeypatch.setattr(device_mod, "pallas_interpret", device_mod.pallas_interpret)
    prev = jax.config.jax_enable_compilation_cache
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def test_the_kept_file_names_every_program():
    assert tuple(KEPT) == program_text.PROGRAMS


@pytest.mark.parametrize("name", program_text.PROGRAMS)
def test_an_older_cell_s_program_text_is_the_kept_one(described_chip, name):
    got = program_text.lower(os.path.dirname(program_text.HERE), (name,))
    assert got[name] == KEPT[name], (
        f"{name} lowers to another program than tests/program_text.json keeps: if that is meant, "
        "run `python tools/program_text.py --write` and say so in CHANGES.md")
