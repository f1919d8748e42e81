"""Distributed environment bootstrap.

TPU-native replacement for the reference's ``init_dist_env``
(ppfleetx/distributed/apis/env.py:121-151): where the reference builds a
fleet DistributedStrategy + NCCL hybrid groups, we initialise multi-host JAX
(if needed), build the global mesh from the ``Distributed`` config block, and
seed the PRNG streams.
"""

from __future__ import annotations

import os

import jax

from paddlefleetx_tpu.parallel.mesh import MeshConfig, build_mesh, set_mesh
from paddlefleetx_tpu.parallel.seed import init_seed
from paddlefleetx_tpu.utils.device import device_identity
from paddlefleetx_tpu.utils.log import logger


def init_dist_env(cfg, devices=None) -> jax.sharding.Mesh:
    """Initialise mesh + seeds from a processed config.

    Multi-host: controlled by standard JAX env vars; ``jax.distributed.
    initialize`` is invoked when a coordinator address is configured
    (the ``paddle.distributed.launch --master`` analogue).
    """
    # _dist_initialized inspects the coordination client without touching
    # the backend: jax.process_count() here would initialise XLA and make
    # the subsequent initialize() call an error
    coord = os.environ.get("PFX_COORDINATOR_ADDRESS")
    if coord and not _dist_initialized():
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=int(os.environ["PFX_NUM_PROCESSES"]),
            process_id=int(os.environ["PFX_PROCESS_ID"]),
        )
        logger.info(
            f"jax.distributed initialised: process {jax.process_index()}/{jax.process_count()}"
        )

    # the one line that says which device this run is on: every entry point
    # (train / serve / eval / export) comes through here first
    ident = device_identity()
    logger.info(
        f"device: platform={ident['platform']} "
        f"device_kind={ident['device_kind']!r} "
        f"device_count={ident['device_count']}"
    )
    mesh_cfg = MeshConfig.from_config(cfg)
    mesh = build_mesh(mesh_cfg, devices)
    set_mesh(mesh)
    seed = int(cfg.get("Global", {}).get("seed", 1024))
    init_seed(seed)
    logger.info(f"mesh axes {dict(mesh.shape)} over {mesh.size} devices; seed {seed}")
    return mesh


def _dist_initialized() -> bool:
    try:
        from jax._src import distributed

        return distributed.global_state.client is not None
    except Exception:
        return False
