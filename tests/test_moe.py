"""MoE tests: gating/capacity mechanics, aux loss, expert-parallel parity."""

import jax
import jax.numpy as jnp
import numpy as np

from paddlefleetx_tpu.models.gpt import model as gpt
from paddlefleetx_tpu.models.gpt.config import GPTConfig
from paddlefleetx_tpu.models.gpt.moe import gate_and_dispatch
from paddlefleetx_tpu.parallel.mesh import MeshConfig, build_mesh
from paddlefleetx_tpu.parallel.sharding import make_rules, tree_logical_to_sharding

MOE = GPTConfig(
    vocab_size=128,
    hidden_size=64,
    num_layers=2,
    num_attention_heads=8,
    max_position_embeddings=32,
    hidden_dropout_prob=0.0,
    attention_probs_dropout_prob=0.0,
    dtype="float32",
    num_experts=4,
    moe_gate="gshard",
)


def test_dispatch_respects_capacity():
    n, e, c = 16, 2, 3
    x = jnp.ones((n, 8))
    # all tokens prefer expert 0
    logits = jnp.tile(jnp.asarray([[5.0, 0.0]]), (n, 1))
    combine, dispatch, aux = gate_and_dispatch(x, logits, e, 1, c, "switch")
    # expert 0 gets exactly capacity tokens, rest dropped
    assert int(dispatch[:, 0, :].sum()) == c
    assert float(aux) > 1.0  # heavily imbalanced -> aux above uniform value


def test_aux_loss_uniform_is_one():
    n, e = 1024, 4
    key = jax.random.key(0)
    logits = jax.random.normal(key, (n, e)) * 0.01  # ~uniform gating
    _, _, aux = gate_and_dispatch(jnp.ones((n, 8)), logits, e, 1, n, "switch")
    assert abs(float(aux) - 1.0) < 0.1


def test_combine_weights_sum_to_one_when_kept():
    n, e, c = 32, 4, 32
    key = jax.random.key(1)
    logits = jax.random.normal(key, (n, e))
    combine, dispatch, _ = gate_and_dispatch(jnp.ones((n, 8)), logits, e, 2, c, "gshard")
    sums = np.asarray(combine.sum(axis=(1, 2)))
    np.testing.assert_allclose(sums, 1.0, atol=1e-5)


def test_moe_model_trains():
    params = gpt.init(MOE, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (4, 16), 0, MOE.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}
    loss, grads = jax.value_and_grad(lambda p: gpt.loss_fn(p, batch, MOE, train=False))(params)
    assert np.isfinite(float(loss))
    # expert + gate params receive gradient
    gnorm = jnp.sqrt(
        sum(jnp.sum(g**2) for g in jax.tree.leaves(grads["layers"]["mlp"]))
    )
    assert float(gnorm) > 0


def test_moe_expert_parallel_parity(devices8):
    """Expert-sharded loss == single-device loss."""
    params = gpt.init(MOE, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, MOE.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}
    ref = float(gpt.loss_fn(params, batch, MOE, train=False))

    for mesh_cfg in [MeshConfig(dp_degree=4, mp_degree=2), MeshConfig(dp_degree=8)]:
        mesh = build_mesh(mesh_cfg, devices8)
        rules = make_rules(mesh=mesh, num_experts=MOE.num_experts)
        shardings = tree_logical_to_sharding(gpt.gpt_logical_axes(MOE), mesh, rules)
        p_sharded = jax.device_put(params, shardings)
        ctx = gpt.ShardingCtx(mesh, rules)
        with mesh:
            got = float(
                jax.jit(lambda p, b: gpt.loss_fn(p, b, MOE, ctx=ctx, train=False))(
                    p_sharded, batch
                )
            )
        np.testing.assert_allclose(got, ref, rtol=2e-5, err_msg=str(mesh_cfg))


def test_naive_gate_no_aux():
    cfg = GPTConfig(**{**MOE.__dict__, "moe_gate": "naive"})
    params = gpt.init(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}
    loss = gpt.loss_fn(params, batch, cfg, train=False)
    assert np.isfinite(float(loss))


def test_gather_combine_is_the_scatter_combine_in_another_order():
    """The forward-only combine of the dropless layer's sorted path (a gather
    by the sort's inverse, then a sum over a token's k: the serving prefill's,
    PR 42) gives what the float32 scatter-add gives, to float32 rounding, with
    held experts, an offset, invalid tokens and dead rows in the buffer; and it
    refuses a buffer that is not the whole of the pairs, and the ladder by name."""
    import pytest

    from paddlefleetx_tpu.models.gpt import moe
    from paddlefleetx_tpu.models.gpt.config import GPTConfig

    cfg = GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_attention_heads=4, ffn_hidden_size=48,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0, norm="rmsnorm", position="rope",
        use_bias=False, mlp_act="swiglu", tie_embeddings=False, dtype="float32",
        num_experts=8, moe_gate="softmax", moe_top_k=3, moe_ffn_hidden_size=16,
        moe_experts_held=3, moe_expert_offset=2)
    rng = np.random.default_rng(0)
    p = {"router_kernel": jnp.asarray(rng.normal(size=(32, 8)), jnp.float32),
         "experts": {n: jnp.asarray(rng.normal(size=shape) * 0.2, jnp.float32)
                     for n, shape in (("w1", (3, 32, 16)), ("w3", (3, 32, 16)), ("w2", (3, 16, 32)))}}
    m = jnp.asarray(rng.normal(size=(40, 32)), jnp.float32)
    valid = jnp.asarray(rng.random(40) < 0.8)
    bias = jnp.zeros((8,), jnp.float32)
    want, st_a = moe.routed_experts(p, m, bias, cfg, valid)
    got, st_b = moe.routed_experts(p, m, bias, cfg, valid, gather_combine=True)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 and float(jnp.max(jnp.abs(want))) > 0.1
    assert int(st_a["pairs_held"]) == int(st_b["pairs_held"]) > 0
    assert float(jnp.max(jnp.abs(got[~valid]))) == 0.0  # an invalid token gets zeros
    with pytest.raises(ValueError, match="not with load_ladder"):  # never dropped in silence
        moe.routed_experts(p, m, bias, cfg, valid, load_ladder=True, gather_combine=True)
    with pytest.raises(ValueError, match="whole buffer"):
        moe._sorted_pairs(8, 3, jax.lax.ragged_dot, p["experts"], m, jnp.ones((40, 3)),
                          jnp.arange(120), jnp.asarray([1, 1, 1]), jnp.int32(3), gather_combine=True)
