"""Train a GPT model WITHOUT the Engine: the public API below it.

Counterpart of the reference's examples layer
(examples/transformer/utils/components.py:32-191), which demonstrates
assembling dataset/sampler/loader/lr/optimizer/model by hand instead of
through the Engine.  Here the same tour is the TPU-native one: every piece
is a plain function you can compose inside your own jitted step —

    config      utils.config.get_config (+ -o overrides)
    mesh        parallel.env.init_dist_env -> jax.sharding.Mesh
    data        data.build_dataset / DistributedBatchSampler / DataLoader
    model       models.gpt.model (init / loss_fn + ShardingCtx)
    optimizer   optims.build_optimizer -> optax GradientTransformation
    step        YOUR code: jax.jit(value_and_grad + optax update)

Run (virtual 8-device CPU mesh):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 PFX_PLATFORM=cpu \
    python examples/transformer/train_no_engine.py
"""

import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)

from paddlefleetx_tpu.utils.device import apply_platform_env

apply_platform_env()  # tpu unless a CPU pin is set; before backend init

import jax
import jax.numpy as jnp
import optax

from paddlefleetx_tpu.data.batch_sampler import (
    DataLoader,
    DistributedBatchSampler,
    collate_stack,
)
from paddlefleetx_tpu.data.gpt_dataset import GPTDataset, write_synthetic_corpus
from paddlefleetx_tpu.models.gpt import model as gpt
from paddlefleetx_tpu.models.gpt.config import GPTConfig
from paddlefleetx_tpu.optims.optimizer import build_optimizer
from paddlefleetx_tpu.parallel.mesh import MeshConfig, build_mesh
from paddlefleetx_tpu.parallel.sharding import make_rules, tree_logical_to_sharding
from paddlefleetx_tpu.utils.config import AttrDict


def main():
    # --- mesh: dp over however many devices exist --------------------------
    devices = jax.devices()
    mesh = build_mesh(MeshConfig(dp_degree=len(devices)), devices)
    rules = make_rules(mesh=mesh)
    ctx = gpt.ShardingCtx(mesh, rules)

    # --- data: synthetic corpus -> dataset -> sampler -> loader ------------
    data_dir = "/tmp/pfx_example_data"
    os.makedirs(data_dir, exist_ok=True)
    prefix = write_synthetic_corpus(
        os.path.join(data_dir, "corpus"), vocab_size=128, num_docs=32
    )
    batch_size, seq_len, steps = 8, 32, 10
    dataset = GPTDataset(
        data_prefix=prefix, max_seq_len=seq_len,
        num_samples=batch_size * steps, split=[1, 0, 0],
    )
    sampler = DistributedBatchSampler(
        dataset_len=len(dataset), batch_size=batch_size, shuffle=True, seed=0
    )
    loader = DataLoader(dataset, sampler, collate_stack)

    # --- model + sharded params -------------------------------------------
    cfg = GPTConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_attention_heads=8,
        max_position_embeddings=seq_len, dtype="float32",
    )
    params = gpt.init(cfg, jax.random.key(0))
    shardings = tree_logical_to_sharding(gpt.gpt_logical_axes(cfg), mesh, rules)
    params = jax.device_put(params, shardings)

    # --- optimizer from the same config vocabulary the Engine uses ---------
    tx, schedule = build_optimizer(
        AttrDict.from_nested(
            {
                "name": "FusedAdamW",
                "weight_decay": 0.01,
                "lr": {"name": "Constant", "learning_rate": 3e-3},
                "grad_clip": {"name": "ClipGradByGlobalNorm", "clip_norm": 1.0},
            }
        )
    )
    opt_state = jax.jit(tx.init)(params)

    # --- YOUR train step: the Engine writes this for you; without it, it is
    # four lines of jax -----------------------------------------------------
    @jax.jit
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: gpt.loss_fn(p, batch, cfg, ctx=ctx, train=True)
        )(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    with mesh:
        it = iter(loader)
        for i in range(steps):
            host_batch = next(it)
            batch = jax.tree.map(jnp.asarray, host_batch)
            params, opt_state, loss = step(params, opt_state, batch)
            print(f"step {i + 1}/{steps} loss {float(loss):.5f}")

    print("no-engine training loop done")


if __name__ == "__main__":
    main()
