"""Device-mesh construction from ``Distributed`` config degrees.

TPU-native replacement for the reference's hybrid communicate group (HCG)
bootstrap (``ppfleetx/distributed/apis/env.py:121-151`` and
``comm_groups.py:27-153``): instead of building NCCL process groups for
dp / mp / pp / sharding / moe, we build ONE ``jax.sharding.Mesh`` with named
axes and let pjit/GSPMD insert collectives.

Axis names (fixed vocabulary, see SURVEY.md §5.8):

    data    — data parallel (reference dp_degree)
    fsdp    — ZeRO/sharding axis (reference sharding_degree; params/opt states
              sharded here, gradients reduce-scattered)
    stages  — pipeline axis (reference pp_degree)
    sep     — sequence/expert alltoall axis (Ulysses / DAP generalization)
    model   — tensor-model-parallel axis (reference mp_degree)

The MoE expert axis reuses ``data``×``fsdp``×``sep`` (reference
HybridCommGroupForMoE fuses dp×mp, comm_groups.py:149-153; we keep experts
off the ``model`` axis so TP still shards each expert's FFN).

Axis order puts ``model`` innermost so TP collectives ride the
fastest ICI links, then ``sep``, then ``stages``; ``data``/``fsdp`` outermost
(can span DCN for multi-slice).  Multi-host: call
``jax.distributed.initialize()`` before ``build_mesh`` (see
``paddlefleetx_tpu.parallel.env.init_dist_env``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from paddlefleetx_tpu.utils.log import logger

AXIS_DATA = "data"
AXIS_FSDP = "fsdp"
AXIS_STAGES = "stages"
AXIS_SEP = "sep"
AXIS_MODEL = "model"

# Outer→inner device-assignment order: model innermost (highest-bandwidth
# neighbours), data outermost (DCN-tolerant).
MESH_AXES = (AXIS_DATA, AXIS_FSDP, AXIS_STAGES, AXIS_SEP, AXIS_MODEL)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    dp_degree: int = 1
    sharding_degree: int = 1
    pp_degree: int = 1
    sep_degree: int = 1
    mp_degree: int = 1

    @property
    def world_size(self) -> int:
        return (
            self.dp_degree
            * self.sharding_degree
            * self.pp_degree
            * self.sep_degree
            * self.mp_degree
        )

    @staticmethod
    def from_config(cfg) -> "MeshConfig":
        dist = cfg.get("Distributed", {})
        sharding = dist.get("sharding", {})
        return MeshConfig(
            dp_degree=int(dist.get("dp_degree", 1)),
            sharding_degree=int(sharding.get("sharding_degree", 1)),
            pp_degree=int(dist.get("pp_degree", 1)),
            sep_degree=int(dist.get("sep_degree", 1)),
            mp_degree=int(dist.get("mp_degree", 1)),
        )


_GLOBAL_MESH: Optional[Mesh] = None


def _dcn_shape(shape: Sequence[int], num_hosts: int) -> Optional[Sequence[int]]:
    """Factor the host count across the OUTER axes (data, fsdp, stages) so
    cross-host (DCN) hops carry only dp/fsdp/pp traffic while mp/sep stay
    on intra-host ICI — the layout the reference achieves by rank order in
    its HCG topology (comm_groups.py:27-80) and the scaling-book recipe."""
    dcn = [1, 1, 1, 1, 1]
    remaining = num_hosts
    for i in range(3):  # data, fsdp, stages may span hosts
        if remaining == 1:
            break
        take = int(np.gcd(shape[i], remaining))
        dcn[i] = take
        remaining //= take
    return dcn if remaining == 1 else None


def build_mesh(
    mesh_cfg: MeshConfig, devices: Optional[Sequence[jax.Device]] = None
) -> Mesh:
    """Build the global 5-axis mesh from parallel degrees.

    On TPU the device assignment is topology-aware: single-slice meshes go
    through ``mesh_utils.create_device_mesh`` (ICI-nearest-neighbour
    placement for the inner axes) and multi-host/multi-slice meshes through
    ``create_hybrid_device_mesh`` with the host factor on the outer
    (DCN-tolerant) axes.  Non-TPU backends and odd shapes fall back to
    plain row-major assignment."""
    if devices is None:
        devices = jax.devices()
    if len(devices) != mesh_cfg.world_size:
        raise ValueError(
            f"mesh degrees {dataclasses.asdict(mesh_cfg)} need "
            f"{mesh_cfg.world_size} devices, have {len(devices)}"
        )
    shape = (
        mesh_cfg.dp_degree,
        mesh_cfg.sharding_degree,
        mesh_cfg.pp_degree,
        mesh_cfg.sep_degree,
        mesh_cfg.mp_degree,
    )
    devices = list(devices)
    if devices and devices[0].platform == "tpu":
        try:
            from jax.experimental import mesh_utils

            # DCN granule = slice (create_hybrid_device_mesh's default
            # grouping); multi-host single-slice pods stay on the pure-ICI
            # path, which handles them correctly
            num_slices = len({getattr(d, "slice_index", 0) for d in devices})
            if num_slices > 1:
                dcn = _dcn_shape(shape, num_slices)
                if dcn is not None:
                    ici = tuple(s // d for s, d in zip(shape, dcn))
                    arr = mesh_utils.create_hybrid_device_mesh(
                        ici, dcn, devices=devices
                    )
                    logger.info("mesh placement: topology-aware (hybrid ICI x DCN)")
                    return Mesh(arr, MESH_AXES)
            else:
                arr = mesh_utils.create_device_mesh(shape, devices=devices)
                logger.info("mesh placement: topology-aware (create_device_mesh)")
                return Mesh(arr, MESH_AXES)
        except Exception as e:  # topology helper rejected the shape
            logger.warning(
                f"topology-aware mesh placement failed ({e!r}); "
                "falling back to row-major device assignment"
            )
    logger.info("mesh placement: row-major")
    arr = np.asarray(devices, dtype=object).reshape(shape)
    return Mesh(arr, MESH_AXES)


def set_mesh(mesh: Mesh) -> None:
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh


def get_mesh() -> Mesh:
    if _GLOBAL_MESH is None:
        raise RuntimeError("mesh not initialised; call init_dist_env / build_mesh first")
    return _GLOBAL_MESH


def mesh_axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis]


def data_parallel_world(mesh: Mesh) -> int:
    """Batch-sharding world = data x fsdp (reference env.py:158-178: the
    'data world' spans dp and sharding ranks for batch slicing)."""
    return mesh.shape[AXIS_DATA] * mesh.shape[AXIS_FSDP]
