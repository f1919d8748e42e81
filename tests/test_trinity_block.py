"""The described block (models/gpt/config.py "block vocabulary") in its
Trinity-Mini spelling, held to the benchmark's plain reference
(pfx_bench/reference/afmoe.py) on the CPU at tiny widths with seeded
weights: logits, loss and gradient of the 5-layer pattern; the share test
that ties one chip's experts to the whole layer; flash attention with a
window and shared KV heads against plain attention; the dropless layer
under imbalance; the routing bias's rule through the engine.

Everything runs in float32, where system and reference differ by
accumulation order only: the tolerances are a few float32 roundings of
values of order 1 (1e-5 .. 1e-4), and each says so where it is used."""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlefleetx_tpu.models.gpt import model as gpt
from paddlefleetx_tpu.models.gpt import moe
from paddlefleetx_tpu.models.gpt.config import GPTConfig
from paddlefleetx_tpu.ops.attention import xla_attention
from paddlefleetx_tpu.ops.flash_attention import flash_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    bench = os.path.join(ROOT, "pfx_bench")  # noqa: E10 — a directory, not a metric
    spec = importlib.util.spec_from_file_location(
        "afmoe_reference", os.path.join(bench, "reference", "afmoe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()

# 1 dense + one period (window, window, full, window) of expert layers;
# 8 experts of which ids 2..5 are held; window 32 at 128 tokens
TOY = dict(
    vocab_size=256, hidden_size=64, num_layers=5, num_attention_heads=8, num_kv_heads=2,
    attn_head_dim=16, ffn_hidden_size=96, moe_ffn_hidden_size=32, max_position_embeddings=128,
    hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0, norm="rmsnorm", post_norms=True,
    position="rope", qk_norm=True, attn_gate=True, use_bias=False, mlp_act="swiglu",
    tie_embeddings=False, embed_scale_sqrt_hidden=True, sliding_window=32, global_attn_every=4,
    num_dense_layers=1, num_experts=8, moe_gate="sigmoid", moe_top_k=2, moe_experts_held=4,
    moe_expert_offset=2, moe_shared_experts=1, moe_route_scale=2.826, dtype="float32",
    attn_impl="xla",
)
SIZES = dict(TOY, norm_eps=1e-5, rope_theta=10000.0)  # what the reference reads


@pytest.fixture(scope="module")
def toy():
    cfg = GPTConfig(**TOY)
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    seq = rng.integers(1, TOY["vocab_size"], size=(2, 129))
    batch = {"tokens": jnp.asarray(seq[:, :-1]), "labels": jnp.asarray(seq[:, 1:]),
             "loss_mask": jnp.ones((2, 128), jnp.float32)}
    # a bias that changes the choice for some tokens, so that "the bias
    # moves the choice and not the weights" is part of what is compared
    bias = jnp.asarray(rng.normal(size=(4, 8)) * 0.05, jnp.float32)
    return cfg, params, batch, bias


def test_parameter_tree_is_what_the_reference_reads(toy):
    cfg, params, _, _ = toy
    assert set(params) == {"embeddings", "dense_layers", "layers", "final_ln", "head"}
    assert set(params["layers"]["mlp"]) == {"router_kernel", "shared", "experts"}
    assert params["layers"]["mlp"]["experts"]["w1"].shape == (4, 4, 64, 32)  # held, not 8
    assert params["layers"]["mlp"]["router_kernel"].shape == (4, 64, 8)  # published width
    assert params["layers"]["attn"]["k_kernel"].shape == (4, 64, 2, 16)
    assert "position" not in params["embeddings"] and "bias" not in params["final_ln"]
    assert [cfg.layer_kind(l) for l in range(5)] == [
        (32, True), (32, True), (32, True), (0, False), (32, True)]


def test_gpt2_defaults_keep_their_tree_and_path():
    """The vocabulary's defaults are the GPT-2 block: same tree as before."""
    cfg = GPTConfig(hidden_size=32, num_layers=2, num_attention_heads=4, vocab_size=64,
                    max_position_embeddings=16)
    assert cfg.classic_block
    shapes = jax.eval_shape(lambda k: gpt.init(cfg, k), jax.random.PRNGKey(0))
    assert set(shapes) == {"embeddings", "layers", "final_ln"}
    assert set(shapes["layers"]["attn"]) == {"qkv_kernel", "qkv_bias", "out_kernel", "out_bias"}
    assert set(shapes["embeddings"]) == {"word", "position"}


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_logits_loss_and_gradient_match_the_reference(toy, impl):
    cfg, params, batch, bias = toy
    cfg = GPTConfig(**dict(TOY, attn_impl=impl, use_recompute=(impl == "flash")))
    got = gpt.forward(params, batch["tokens"], cfg, expert_bias=bias)
    want = ref.logits(params, batch["tokens"], SIZES, bias)
    # float32 on both sides: logits of spread 0.16 agree to a few 1e-7;
    # 2e-5 leaves two orders of room and is still 1/1000 of what any
    # missing piece moves them by (test_a_missing_piece_fails below)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=0)

    extra = dict(gpt.init_extra(cfg), expert_bias=bias)
    (loss, _), grads = jax.value_and_grad(
        lambda p: gpt.loss_fn(p, batch, cfg, extra=extra, train=True), has_aux=True)(params)
    want_loss, want_grads = jax.value_and_grad(
        lambda p: ref.loss(p, batch["tokens"], batch["labels"], batch["loss_mask"],
                           SIZES, bias))(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5  # a mean of 256 float32 terms
    flat_g, flat_w = jax.tree.leaves(grads), jax.tree.leaves(want_grads)
    assert jax.tree.structure(grads) == jax.tree.structure(want_grads)
    for g, w in zip(flat_g, flat_w):
        # every leaf, relative to that leaf's largest entry: gradients run
        # through ~40 float32 matmuls, 1e-4 of the leaf's scale is 100
        # roundings; a wrong dq/dk/dv or router path is off by 1e-1
        scale = float(jnp.max(jnp.abs(w))) + 1e-12
        assert float(jnp.max(jnp.abs(g - w))) / scale < 1e-4


@pytest.mark.parametrize("name,system,reference", [
    ("qk_norm", dict(qk_norm=False), {}),
    ("attn_gate", dict(attn_gate=False), {}),
    ("post_norms", dict(post_norms=False), {}),
    ("route_scale", dict(moe_route_scale=1.0), {}),
    ("window", dict(sliding_window=0), {}),
    ("embed_scale", dict(embed_scale_sqrt_hidden=False), {}),
    # rotation on the global layer too, windows off on both sides so that
    # the rotation is the one difference
    ("rope_on_global_layer", dict(global_attn_every=0, sliding_window=0),
     dict(sliding_window=0)),
])
def test_a_missing_piece_fails(toy, name, system, reference):
    """The tolerance above is tight enough: leave one piece of the block
    out of the system and the logits move by at least 100 x it."""
    _, params, batch, bias = toy
    got = gpt.forward(params, batch["tokens"], GPTConfig(**dict(TOY, **system)), expert_bias=bias)
    want = ref.logits(params, batch["tokens"], dict(SIZES, **reference), bias)
    assert float(jnp.max(jnp.abs(got - want))) > 2e-3, name


@pytest.mark.parametrize("name,change", [
    ("no_qk_norm", dict(qk_norm=False)),
    ("no_attn_gate", dict(attn_gate=False)),
    ("no_post_norms", dict(post_norms=False)),
    ("no_embed_scale", dict(embed_scale_sqrt_hidden=False)),
    ("equal_head_counts", dict(num_kv_heads=0, attn_head_dim=0)),  # 8 heads of 64 / 8
    ("every_layer_alike", dict(global_attn_every=0)),  # window and rotation on all five
    ("no_window", dict(sliding_window=0)),  # the rotation's 3:1 pattern alone
    ("no_leading_dense_layer", dict(num_dense_layers=0)),
    ("no_shared_expert", dict(moe_shared_experts=0)),
    ("all_experts_held", dict(moe_experts_held=0, moe_expert_offset=0)),
    ("no_expert_layers", dict(num_experts=1, moe_gate="gshard")),  # a dense GQA block
])
def test_each_option_of_the_block_is_held_to_the_reference(toy, name, change):
    """The block's options one at a time, on both sides: what the
    configuration accepts beside Trinity-Mini's own spelling computes what
    the plain reference computes (float32; tolerance as above)."""
    _, _, batch, bias = toy
    cfg = GPTConfig(**dict(TOY, **change))
    params = gpt.init(cfg, jax.random.PRNGKey(1))
    rows = cfg.num_layers - cfg.leading_dense_layers
    bias = jnp.resize(bias, (rows, cfg.num_experts)) if cfg.moe_dropless else None
    got = gpt.forward(params, batch["tokens"], cfg, expert_bias=bias)
    want = ref.logits(params, batch["tokens"], dict(SIZES, **change), bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=0)


@pytest.mark.parametrize("change", [
    dict(norm="layernorm"), dict(position="learned"), dict(use_bias=True),
    dict(mlp_act="gelu"), dict(tie_embeddings=True),
    dict(moe_bias_warm_start_steps=4),  # without a first rate
    dict(moe_bias_warm_start_steps=4, moe_bias_warm_start_rate=0.01, num_experts=1,
         moe_gate="gshard"),  # without the expert layer
], ids=lambda c: "+".join(c))
def test_a_mixed_block_is_refused(change):
    """norm, position, use_bias, mlp_act and tie_embeddings move together
    (no test holds a mixture to anything), and a warm start needs the
    expert layer and its two rates."""
    with pytest.raises(ValueError):
        GPTConfig(**dict(TOY, **change))


def test_shares_add_up_to_the_uncut_layer(toy):
    """Expert parallel 4 at toy size: the routed parts of the 4 shares (2
    experts each) plus the shared expert counted once are the whole layer
    as the reference computes it with all 8 experts."""
    _, params, _, _ = toy
    key = jax.random.PRNGKey(3)
    whole = GPTConfig(**dict(TOY, moe_experts_held=0, moe_expert_offset=0))
    p = jax.tree.map(lambda a: a[0], gpt.init(whole, key)["layers"]["mlp"])  # 8 experts
    m = jax.random.normal(jax.random.PRNGKey(4), (96, 64), jnp.float32)
    bias = jnp.linspace(-0.05, 0.05, 8)
    want = ref.expert_layer(m, p, bias, dict(SIZES, moe_expert_offset=0))

    total = moe.swiglu(m, p["shared"])
    pairs = 0
    for share in range(4):
        cfg = GPTConfig(**dict(TOY, moe_experts_held=2, moe_expert_offset=2 * share))
        held = dict(p, experts=jax.tree.map(lambda a: a[2 * share:2 * share + 2], p["experts"]))
        part, stats = moe.routed_experts(held, m, bias, cfg)
        total = total + part
        pairs += int(stats["pairs_held"])
    assert pairs == 96 * 2  # every pair lands on exactly one share
    # sums of float32 products in another order: 1e-5 of values of order 0.1
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=1e-5, rtol=0)


# ---------------------------------------------------------------------------
# Flash attention with a window and shared KV heads (interpreted kernels)
# ---------------------------------------------------------------------------


def _qkv(s, n, n_kv, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (1, s, n, d), jnp.float32),
            jax.random.normal(ks[1], (1, s, n_kv, d), jnp.float32),
            jax.random.normal(ks[2], (1, s, n_kv, d), jnp.float32),
            jax.random.normal(ks[3], (1, s, n, d), jnp.float32))


def _plain(q, k, v, window):
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    i = jnp.arange(q.shape[1])
    seen = i[None, :] <= i[:, None]
    if window:
        seen = seen & (i[:, None] - i[None, :] < window)
    return xla_attention(q, k, v, bias=jnp.where(seen, 0.0, -1e9)[None, None])


# seq 256 in blocks of 64: windows below a block, on a block edge, off one,
# at the sequence length and above it (both: no window at all)
@pytest.mark.parametrize("window", [24, 64, 100, 192, 256, 300])
@pytest.mark.parametrize("heads", [(8, 2), (4, 4)])
def test_flash_window_and_shared_kv_heads_match_plain_attention(window, heads):
    n, n_kv = heads
    q, k, v, ct = _qkv(256, n, n_kv, 32)
    flash = lambda q, k, v: flash_attention(q, k, v, block=64, window=window)  # noqa: E731
    want_window = window if window < 256 else 0
    got = flash(q, k, v)
    want = _plain(q, k, v, want_window)
    # float32 online softmax against a plain one: 1e-5 of outputs of order 1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=0)
    gf = jax.grad(lambda *a: jnp.sum(flash(*a) * ct), argnums=(0, 1, 2))(q, k, v)
    gp = jax.grad(lambda *a: jnp.sum(_plain(*a, want_window) * ct), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gp, "qkv"):  # dq kernel; dkv kernel and the group sum
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=0,
                                   err_msg=f"d{name}")


def test_flash_without_window_is_the_program_it_was():
    """``window`` left out, 0, or at least the sequence length, with equal
    head counts: the same kernels with the same static arguments, so the
    same bits, forward and backward; and the fused backward, handed a window,
    raises by name (``_bwd_schedule`` never hands it one)."""
    q, k, v, ct = _qkv(256, 4, 4, 32, seed=1)
    runs = []
    for kw in ({}, {"window": 0}, {"window": 256}, {"window": 1000}):
        f = lambda q, k, v: flash_attention(q, k, v, block=64, **kw)  # noqa: E731
        runs.append((f(q, k, v),) + jax.grad(
            lambda *a: jnp.sum(f(*a) * ct), argnums=(0, 1, 2))(q, k, v))
        text = jax.jit(f).lower(q, k, v).as_text()
        assert text == jax.jit(lambda q, k, v: flash_attention(q, k, v, block=64)).lower(
            q, k, v).as_text()
    for other in runs[1:]:
        for a, b in zip(runs[0], other):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    from paddlefleetx_tpu.ops.flash_attention import _flash_bsnd

    with pytest.raises(NotImplementedError, match="fused"):
        jax.grad(lambda q: jnp.sum(_flash_bsnd(q, k, v, 32 ** -0.5, (64, 64), "fused", 32)))(q)


@pytest.mark.parametrize("window,heads", [(32, (4, 4)), (0, (8, 2)), (32, (8, 2))])
def test_a_windowed_or_grouped_backward_runs_the_two_split_kernels(window, heads):
    """A window or shared KV heads: the call's backward lowers to
    ``pfx_flash_bwd_dq`` + ``pfx_flash_bwd_dkv``, at the 345M cell's sequence
    and head size too, where a call with neither runs the single kernel."""
    q, k, v, _ = _qkv(1024, *heads, 64)
    text = jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, window=window)), argnums=(0, 1, 2))).lower(q, k, v).as_text(debug_info=True)
    assert "flash_bwd_dq" in text and "flash_bwd_dkv" in text and "flash_bwd_fused" not in text


# ---------------------------------------------------------------------------
# The dropless layer under imbalance, and its buffer
# ---------------------------------------------------------------------------


def _layer_params(cfg, seed=5):
    return jax.tree.map(lambda a: a[0], gpt.init(cfg, jax.random.PRNGKey(seed))["layers"]["mlp"])


def test_dropless_when_every_token_picks_one_held_expert():
    """Every token's first choice is held expert 3 (its second an expert
    held elsewhere): one group holds all the pairs, nothing is dropped."""
    cfg = GPTConfig(**TOY)
    p = _layer_params(cfg)
    m = jax.random.normal(jax.random.PRNGKey(6), (128, 64), jnp.float32)
    bias = jnp.zeros((8,)).at[3].set(10.0).at[7].set(5.0)
    got, stats = moe.routed_experts(p, m, bias, cfg)
    want = ref.routed_experts(m, p, bias, SIZES)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=0)
    assert int(stats["pairs_held"]) == 128
    assert list(np.asarray(stats["load"])) == [0, 0, 0, 128, 0, 0, 0, 128]
    assert float(stats["load_max_over_mean"]) == pytest.approx(4.0)  # 128 / (128 / 4)


def _dirty_ragged_dot():
    """``jax.lax.ragged_dot`` as the TPU runs it: the buffer rows past the
    last group come back as they were found (here: NaN, forward and
    backward); the CPU zero-fills them and would hide it."""
    real = jax.lax.ragged_dot

    @jax.custom_vjp
    def dirty(x, w, sizes):
        y = real(x, w, sizes)
        return jnp.where((jnp.arange(y.shape[0]) < jnp.sum(sizes))[:, None], y, jnp.nan)

    def fwd(x, w, sizes):
        return dirty(x, w, sizes), (x, w, sizes)

    def bwd(res, g):
        x, w, sizes = res
        _, vjp = jax.vjp(lambda x, w: real(x, w, sizes), x, w)
        dx, dw = vjp(jnp.where(jnp.isnan(g), 0.0, g))
        dead = (jnp.arange(x.shape[0]) >= jnp.sum(sizes))[:, None]
        return jnp.where(dead, jnp.nan, dx), dw, None

    dirty.defvjp(fwd, bwd)
    return dirty


def test_rows_past_the_groups_never_reach_a_token():
    """On the TPU a grouped product leaves the buffer rows past the last
    group as it found them; values and gradients must not care."""
    cfg = GPTConfig(**TOY)
    p = _layer_params(cfg)
    m = jax.random.normal(jax.random.PRNGKey(6), (128, 64), jnp.float32)
    bias = jnp.zeros((8,))

    def loss(fn, p, m):
        return jnp.sum(jnp.square(fn(p, m)))

    want = jax.value_and_grad(
        lambda p, m: loss(lambda p, m: ref.routed_experts(m, p, bias, SIZES), p, m),
        argnums=(0, 1))(p, m)
    got = jax.value_and_grad(
        lambda p, m: loss(lambda p, m: moe.routed_experts(
            p, m, bias, cfg, grouped_product=_dirty_ragged_dot())[0], p, m),
        argnums=(0, 1))(p, m)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=1e-4)


def test_dropless_at_the_worst_case_every_pair_on_a_held_expert():
    """Both choices of every token are held here: all tokens x top_k pairs
    fill the sorted-pair buffer to its last row (it has no more: no bound
    below the worst case is taken), and the answer is the reference's."""
    cfg = GPTConfig(**TOY)
    p = _layer_params(cfg)
    m = jax.random.normal(jax.random.PRNGKey(6), (128, 64), jnp.float32)
    bias = jnp.zeros((8,)).at[3].set(10.0).at[4].set(5.0)
    got, stats = moe.routed_experts(p, m, bias, cfg)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.routed_experts(m, p, bias, SIZES)), atol=1e-5, rtol=0)
    assert int(stats["pairs_held"]) == 128 * 2
    assert list(np.asarray(stats["load"])) == [0, 0, 0, 128, 128, 0, 0, 0]


# ---------------------------------------------------------------------------
# The buffer's ladder (moe.buffer_ladder, routed_experts(load_ladder=True)):
# 2 of 32 experts held, so 4,096 pairs have the rungs 512 / 4,096
# ---------------------------------------------------------------------------

LADDER_TOY = dict(TOY, num_experts=32, moe_experts_held=2, moe_expert_offset=2)
LADDER_SIZES = dict(LADDER_TOY, norm_eps=1e-5, rope_theta=10000.0)


@pytest.mark.parametrize("shape,rungs", [
    ((131072, 16, 128), (32768, 131072)),  # the benchmark's cell
    ((4096, 2, 32), (512, 4096)),  # twice the balanced share, then the worst case
    ((65536, 16, 128), (16384, 65536)),  # the cell's reference check: one sequence
    ((256, 2, 16), (128, 256)),
    ((3000, 1, 16), (384, 3000)),  # 375 rounded up to the row tile
    ((256, 4, 8), (256,)),  # twice the balanced share is the worst case: one rung
    ((256, 8, 8), (256,)),  # every expert held
    ((16, 1, 128), (16,)),  # under a tile
])
def test_the_ladder_comes_from_shapes(shape, rungs):
    assert moe.buffer_ladder(*shape) == rungs


def _ladder_layer():
    cfg = GPTConfig(**LADDER_TOY)
    m = jax.random.normal(jax.random.PRNGKey(6), (2048, 64), jnp.float32)
    return cfg, _layer_params(cfg), m


def _value_and_grads(cfg, p, m, bias, ct, **kw):
    def loss(p, m):
        out, stats = moe.routed_experts(p, m, bias, cfg, **kw)
        return jnp.sum(out * ct), (out, stats)

    (_, (out, stats)), grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(p, m)
    return out, stats, grads


def _assert_same_step(got, want):
    """Value to the bit; gradient to the last bits: a rung is the head of
    the worst case's buffer, so every pair's arithmetic and the order of
    each token's float32 sum are the same, but the CPU's transposed grouped
    product sums a group's rows in blocks that follow the buffer's LENGTH
    (w1 and w3 differ in the last bits of a few entries).  2e-6 of a leaf's
    largest entry is sixteen float32 roundings; a pair lost or counted twice
    moves a leaf by 1e-3 of it."""
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
    flat, treedef = jax.tree.flatten(got[1])
    assert treedef == jax.tree.structure(want[1])
    for g, w in zip(flat, jax.tree.leaves(want[1])):
        scale = float(jnp.max(jnp.abs(w))) + 1e-30
        assert float(jnp.max(jnp.abs(g - w))) <= 2e-6 * scale


# a bias on the two held experts moves their share of the 4,096 pairs
@pytest.mark.parametrize("held_bias,rung", [(0.0, 512), (0.015, 512), (0.03, 4096), (0.08, 4096)])
def test_every_rung_gives_the_worst_case_buffers_value_and_gradient(held_bias, rung):
    """Every leaf's gradient, the router's kernel and the tokens' among
    them, and the load statistics that the counters read."""
    cfg, p, m = _ladder_layer()
    bias = jnp.zeros((32,)).at[2:4].set(held_bias)
    ct = jax.random.normal(jax.random.PRNGKey(7), m.shape, jnp.float32)
    out, stats, grads = _value_and_grads(cfg, p, m, bias, ct, load_ladder=True)
    want_out, want_stats, want_grads = _value_and_grads(cfg, p, m, bias, ct)
    assert int(stats["buffer_rows"]) == rung and int(want_stats["buffer_rows"]) == 4096
    assert (int(stats["pairs_held"]) <= 512) == (rung == 512)  # the smallest that holds
    _assert_same_step((out, grads), (want_out, want_grads))
    assert float(jnp.max(jnp.abs(grads[0]["router_kernel"]))) > 0
    for key in ("load", "pairs_held", "load_max_over_mean"):
        assert np.array_equal(np.asarray(stats[key]), np.asarray(want_stats[key]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(
        ref.routed_experts(m, p, bias, LADDER_SIZES)), atol=1e-5, rtol=0)


@pytest.mark.parametrize("pairs,rung", [
    (0, 512), (1, 512), (511, 512), (512, 512), (513, 4096), (2048, 4096)])
def test_a_load_at_a_rungs_size_runs_it_and_one_pair_more_the_next(pairs, rung):
    """Every token's first choice is held expert 3 and its second an expert
    held elsewhere, so the held pairs are exactly the ``valid`` tokens."""
    cfg, p, m = _ladder_layer()
    bias = jnp.zeros((32,)).at[3].set(10.0).at[7].set(5.0)
    valid = jnp.arange(2048) < pairs
    ct = jnp.ones_like(m)
    out, stats, grads = _value_and_grads(cfg, p, m, bias, ct, valid=valid, load_ladder=True)
    assert int(stats["pairs_held"]) == pairs and int(stats["buffer_rows"]) == rung
    want = _value_and_grads(cfg, p, m, bias, ct, valid=valid)
    _assert_same_step((out, grads), (want[0], want[2]))


def test_the_last_rung_holds_every_pair_and_drops_none():
    """Both choices of every token are held here: all 4,096 pairs, the
    worst case, and the reference's answer."""
    cfg, p, m = _ladder_layer()
    bias = jnp.zeros((32,)).at[2].set(10.0).at[3].set(5.0)
    out, stats = moe.routed_experts(p, m, bias, cfg, load_ladder=True)
    assert int(stats["pairs_held"]) == int(stats["buffer_rows"]) == 4096
    assert list(np.asarray(stats["load"][:5])) == [0, 0, 2048, 2048, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(
        ref.routed_experts(m, p, bias, LADDER_SIZES)), atol=1e-5, rtol=0)


@pytest.mark.parametrize("held_bias,rung", [(0.0, 512), (0.05, 4096), (10.0, 4096)])
def test_invalid_tokens_and_rows_past_the_groups_reach_nothing_at_any_rung(held_bias, rung):
    """A grouped product that returns NaN past its groups (the TPU's leaves
    them as found), half of the tokens not ``valid``: at every rung the
    values and every cotangent are finite and the clean worst case's, an
    invalid token gets zeros and gives its row of ``m`` no cotangent."""
    cfg, p, m = _ladder_layer()
    bias = jnp.zeros((32,)).at[2:4].set(held_bias)
    valid = jnp.arange(2048) % 2 == 0
    ct = jax.random.normal(jax.random.PRNGKey(7), m.shape, jnp.float32)
    out, stats, grads = _value_and_grads(
        cfg, p, m, bias, ct, valid=valid, load_ladder=True, grouped_product=_dirty_ragged_dot())
    assert int(stats["buffer_rows"]) == rung
    for leaf in jax.tree.leaves((out, grads)):
        assert bool(jnp.all(jnp.isfinite(leaf)))
    want = _value_and_grads(cfg, p, m, bias, ct, valid=valid)
    _assert_same_step((out, grads), (want[0], want[2]))
    assert not np.any(np.asarray(out)[1::2]) and not np.any(np.asarray(grads[1])[1::2])
    assert np.any(np.asarray(grads[1])[0::2])


def test_the_ladder_under_jit_remat_and_the_layer_scan(monkeypatch):
    """The training step's own path (``loss_fn`` under ``jit``, full
    recompute, the period's ``lax.scan``; 512 pairs a layer: rungs 128 /
    512) with each rung taken by some layer: loss, every leaf's
    gradient and the load statistics are the worst-case buffer's (the
    ladder cut to its last rung), and the reference's."""
    cfg = GPTConfig(**dict(LADDER_TOY, use_recompute=True, recompute_granularity="full"))
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    seq = rng.integers(1, 256, size=(2, 129))
    batch = {"tokens": jnp.asarray(seq[:, :-1]), "labels": jnp.asarray(seq[:, 1:]),
             "loss_mask": jnp.ones((2, 128), jnp.float32)}
    bias = jnp.zeros((4, 32)).at[1, 2:4].set(10.0).at[2, 2:4].set(0.05)
    extra = dict(gpt.init_extra(cfg), expert_bias=bias)

    def step():
        return jax.jit(jax.value_and_grad(
            lambda p: gpt.loss_fn(p, batch, cfg, extra=extra, train=True), has_aux=True))(params)

    (loss, new), grads = step()
    stats = jax.jit(lambda p: gpt.forward_hidden(p, batch["tokens"], cfg, expert_bias=bias)[1])(params)
    assert list(np.asarray(stats["buffer_rows"])) == [128, 512, 512, 128]
    assert gpt.extra_record(gpt.extra_scalars(new))["moe_buffer_rows"] == 128 + 512 + 512 + 128
    monkeypatch.setattr(moe, "buffer_ladder", lambda rows, held, num_experts: (rows,))
    (want_loss, want_new), want_grads = step()
    assert gpt.extra_record(gpt.extra_scalars(want_new))["moe_buffer_rows"] == 4 * 512
    assert float(loss) == float(want_loss)
    _assert_same_step((loss, grads), (want_loss, want_grads))
    assert np.array_equal(np.asarray(new["expert_bias"]), np.asarray(want_new["expert_bias"]))
    ref_loss, ref_grads = jax.value_and_grad(lambda p: ref.loss(
        p, batch["tokens"], batch["labels"], batch["loss_mask"], LADDER_SIZES, bias))(params)
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        assert float(jnp.max(jnp.abs(g - w))) / (float(jnp.max(jnp.abs(w))) + 1e-12) < 1e-4


# ---------------------------------------------------------------------------
# The routing bias: no gradient, no decay, moved by the sign rule
# ---------------------------------------------------------------------------


def test_bias_rule_moves_towards_balance():
    load = jnp.asarray([[30, 10, 20, 20], [5, 5, 5, 65]])
    new = moe.next_expert_bias(jnp.zeros((2, 4)), load, 0.001)
    np.testing.assert_allclose(np.asarray(new), [[-0.001, 0.001, 0, 0],
                                                 [0.001, 0.001, 0.001, -0.001]], atol=1e-9)


def test_bias_gets_the_sign_rule_and_no_gradient(toy):
    cfg, params, batch, bias = toy
    extra = dict(gpt.init_extra(cfg), expert_bias=bias)
    _, stats = gpt.forward_hidden(params, batch["tokens"], cfg, expert_bias=bias)
    assert stats["load"].shape == (4, 8) and int(stats["load"].sum()) == 4 * 256 * 2
    _, new = gpt.loss_fn(params, batch, cfg, extra=extra, train=True)
    load = np.asarray(stats["load"], np.float32)
    want = np.asarray(bias) + 0.001 * np.sign(load.mean(-1, keepdims=True) - load)
    np.testing.assert_allclose(np.asarray(new["expert_bias"]), want, atol=1e-9)
    # evaluation leaves the buffer and the counters alone
    _, same = gpt.loss_fn(params, batch, cfg, extra=extra, train=False)
    assert same is extra
    grad = jax.grad(lambda b: gpt.loss_fn(
        params, batch, cfg, extra=dict(extra, expert_bias=b), train=True)[0])(bias)
    assert not np.any(np.asarray(grad))


def _engine(tmp_path, *overrides):
    """(engine, mesh, metrics path) of the cell's yaml at the toy sizes."""
    from paddlefleetx_tpu.core.engine import Engine
    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import get_config

    model = {k: v for k, v in TOY.items() if k != "dtype"}
    metrics = tmp_path / "metrics.jsonl"
    cfg = get_config(
        os.path.join(ROOT, "configs", "gpt", "pretrain_trinity_mini_1of8.yaml"),
        overrides=[f"Model.{k}={v}" for k, v in model.items()] + [
            "Model.use_chunked_ce=False", "Engine.mix_precision.enable=False",
            "Engine.max_steps=3", "Engine.logging_freq=1", "Engine.eval_freq=0",
            "Engine.save_load.save_steps=0", f"Engine.save_load.output_dir={tmp_path}",
            f"Engine.metrics_file={metrics}", "Data.Train.dataset.max_seq_len=128",
            "Optimizer.weight_decay=0.5", "Optimizer.lr.max_lr=1.0e-3", *overrides,
        ], num_devices=1)
    mesh = init_dist_env(cfg, devices=jax.devices()[:1])
    with mesh:
        return Engine(cfg, build_module(cfg), mesh), mesh, metrics


def _batches(n, seed=1):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        seq = rng.integers(1, 256, size=(2, 129))
        out.append({"tokens": seq[:, :-1], "labels": seq[:, 1:],
                    "loss_mask": np.ones((2, 128), np.float32),
                    "position_ids": np.tile(np.arange(128), (2, 1))})
    return out


def _records(metrics):
    import json

    return [json.loads(line) for line in open(metrics)]


def test_bias_is_engine_state_outside_the_optimizer(tmp_path):
    """Through the engine: ``expert_bias`` is no parameter (so it has no
    gradient, no Adam moment and no weight decay), it moves by exactly the
    sign rule on each step's load, and the step records carry the expert
    layer's counters."""
    engine, mesh, metrics = _engine(tmp_path, "Model.moe_bias_warm_start_steps=0")
    with mesh:
        paths = [jax.tree_util.keystr(p) for p, _ in
                 jax.tree_util.tree_leaves_with_path((engine.state.params, engine.state.opt_state))]
        assert not any("expert_bias" in p for p in paths)
        engine.fit(_batches(1) * 3, None)
        bias = np.asarray(engine.state.extra["expert_bias"])
    assert bias.shape == (4, 8) and np.all(np.abs(bias) <= 3 * 0.001 + 1e-9)
    assert np.any(bias != 0)
    recs = [r for r in _records(metrics) if "loss" in r]
    assert [r["moe_pairs_total"] for r in recs] == [4 * 256 * 2 * (i + 1) for i in range(3)]
    assert recs[-1]["moe_pairs_held"] > 0 and "moe_pairs_held_layer_max" in recs[-1]
    # 4 of 8 held: twice the balanced share is the worst case, the one rung
    assert [r["moe_buffer_rows"] for r in recs] == [r["moe_pairs_total"] for r in recs]
    assert recs[0]["moe_bias_abs_max"] == pytest.approx(0.001)
    assert recs[-1]["moe_load_max_over_mean_sum"] >= 3.0  # a max over a mean, 3 steps


def test_buffer_rows_ride_the_records_and_a_skipped_step_takes_them_back(tmp_path):
    """2 of 32 experts held (512 pairs a layer: rungs 128 / 512):
    ``moe_buffer_rows`` counts the rung each layer ran, is published beside
    the held pairs, and a step skipped for a NaN loss leaves it where it
    was, with the bias and the other counters."""
    from paddlefleetx_tpu.utils.resilience import poison_batch

    engine, mesh, metrics = _engine(
        tmp_path, "Model.moe_bias_warm_start_steps=0", "Model.num_experts=32",
        "Model.moe_experts_held=2")
    good = _batches(1)[0]
    with mesh:
        engine.fit([good, poison_batch(good), good], None)
    recs = [r for r in _records(metrics) if "loss" in r]
    assert [np.isfinite(r["loss"]) for r in recs] == [True, False, True]
    counters = ("moe_pairs_total", "moe_pairs_held", "moe_buffer_rows",
                "moe_load_max_over_mean_sum", "moe_bias_abs_max")
    assert recs[0]["moe_buffer_rows"] == 4 * 128  # every layer at the first rung
    assert recs[0]["moe_pairs_held"] <= recs[0]["moe_buffer_rows"] < recs[0]["moe_pairs_total"]
    assert [recs[1][k] for k in counters] == [recs[0][k] for k in counters]
    assert recs[2]["moe_buffer_rows"] == 2 * 4 * 128
    assert recs[2]["moe_pairs_total"] == 2 * 4 * 256 * 2
    text = engine._registry.render_prometheus()
    assert f"pfx_moe_buffer_rows_total {recs[2]['moe_buffer_rows']}" in text
    assert f"pfx_moe_pairs_held_total {recs[2]['moe_pairs_held']}" in text


def test_bias_warm_start_runs_once_before_the_first_step(tmp_path):
    """``fit`` spends the loader's first ``moe_bias_warm_start_steps``
    batches on forward-only passes of the bias rule, at rates falling
    geometrically from ``moe_bias_warm_start_rate`` to the update rate
    (0.008, 0.004, 0.002, 0.001), counts them as consumed, and then trains;
    a second call does nothing."""
    engine, mesh, metrics = _engine(
        tmp_path, "Model.moe_bias_warm_start_steps=4", "Model.moe_bias_warm_start_rate=0.008")
    batches = _batches(7)
    cfg = engine.module.config
    with mesh:
        params = jax.tree.map(np.asarray, engine.state.params)
        want = jnp.zeros((4, 8))
        for batch, rate in zip(batches, (0.008, 0.004, 0.002, 0.001)):
            load = gpt.forward_hidden(params, jnp.asarray(batch["tokens"]), cfg,
                                      expert_bias=want)[1]["load"]
            want = moe.next_expert_bias(want, load, rate)
        engine.fit(batches, None)
        # the warm start's bias (sums of four float32 rates on both sides:
        # 1e-7), then three steps of the rule at 0.001
        moved = np.abs(np.asarray(engine.state.extra["expert_bias"]) - np.asarray(want))
        assert np.all(moved <= 3 * 0.001 + 1e-7)
        assert 0.003 < float(jnp.max(jnp.abs(want))) <= 0.015 + 1e-7
        assert engine.warm_start(iter(batches)) is None
    events = [r for r in _records(metrics) if r.get("event") == "warm_start"]
    assert len(events) == 1 and events[0]["passes"] == 4
    # what each pass reported: the pairs each of the 4 expert layers held
    assert np.asarray(events[0]["reported"]).shape == (4, 4)
    recs = [r for r in _records(metrics) if "loss" in r]
    assert [r["step"] for r in recs] == [1, 2, 3]
    assert recs[-1]["consumed_samples"] == (4 + 3) * 2
    assert recs[-1]["moe_pairs_total"] == 4 * 256 * 2 * 3  # the passes are no steps


# ---------------------------------------------------------------------------
# The benchmark runner's limits (pfx_bench/runners/train_arch.py)
# ---------------------------------------------------------------------------


def test_the_runner_limits_catch_a_loss_on_another_head_and_a_wrong_small_leaf(toy):
    """What LOSS_ABS_MAX and the worst leaf's cosine are there to catch.
    The loss limit: a loss that reads the embedding where the logits read
    the untied head leaves logits and gradient of the logits' path alone.
    The leaf limit: a router whose gradient is lost moves the whole tree's
    cosine by less than its limit allows."""
    path = list(sys.path)  # the runner puts pfx_bench on it for its imports
    spec = importlib.util.spec_from_file_location(
        "train_arch_under_test", os.path.join(ROOT, "pfx_bench", "runners", "train_arch.py"))  # noqa: E10
    arch = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(arch)
    sys.path[:] = path
    cfg, params, batch, bias = toy
    extra = dict(gpt.init_extra(cfg), expert_bias=bias)
    one = {k: v[:1] for k, v in batch.items()}

    def system_loss(p):
        return gpt.loss_fn(p, one, cfg, extra=extra, train=False)[0]

    def ref_loss(p):
        lg = ref.logits(p, one["tokens"], SIZES, bias)
        return ref.loss_from_logits(lg, one["labels"], one["loss_mask"]), lg

    got = gpt.forward(params, one["tokens"], cfg, expert_bias=bias)
    (rl, want), rg = jax.value_and_grad(ref_loss, has_aux=True)(params)
    sl, sg = jax.value_and_grad(system_loss)(params)

    def verdict(sl, sg):
        return arch.verdict(jax.device_get(arch.compare(got, want, sl, rl, sg, rg)), params)

    sound = verdict(sl, sg)
    assert sound["ok"] and sound["grad_worst_leaf_cosine"] > 0.9999  # float32 on both sides
    assert len(sound["grad_leaves"]) == len(jax.tree.leaves(params))

    tied = dict(params, head={"kernel": params["embeddings"]["word"]})
    bad = verdict(system_loss(tied), sg)
    assert abs(bad["loss"] - bad["reference_loss"]) > arch.LOSS_ABS_MAX
    assert bad["logits_ok"] and not bad["grad_ok"]

    lost = jax.tree.map(lambda a: a, sg)
    lost["layers"]["mlp"]["router_kernel"] = jnp.zeros_like(sg["layers"]["mlp"]["router_kernel"])
    bad = verdict(sl, lost)
    assert bad["grad_cosine"] >= arch.GRAD_COSINE_MIN  # the whole tree does not see it
    assert bad["grad_worst_leaf"].endswith("['router_kernel']") and not bad["grad_ok"]
