"""The described block with latent attention and group-limited experts (its
DeepSeek-V3 spelling, docs/deepseek_v3.md) on the serving path, held to the
benchmark's plain reference (pfx_bench/reference/deepseek_v3.py) on the CPU at
tiny widths with seeded weights: prefill and paged decode through the latent
pool against the reference's full forward pass; the absorbed form against
the expanded one; the Pallas kernels (interpret mode) against their lax
spellings; group-limited routing; the share test that ties one chip's experts
to the whole layer; YaRN by hand; what is refused; the scheduler end to end;
the leaf-by-leaf start-up; the benchmark's new data and reader.

Everything runs in float32, where system and reference differ by
accumulation order only: the tolerances are a few float32 roundings of
values of order 1 (2e-5), and each says so where it is used."""

import dataclasses
import functools
import importlib.util
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlefleetx_tpu.models.gpt import generation as G
from paddlefleetx_tpu.models.gpt import model as gpt
from paddlefleetx_tpu.models.gpt import moe
from paddlefleetx_tpu.models.gpt.config import GPTConfig
from paddlefleetx_tpu.ops import decode_attention as DA
from paddlefleetx_tpu.ops.grouped_matmul import grouped_matmul

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "pfx_bench")  # noqa: E10 — a directory, not a metric
F32_ROUNDINGS = 2e-5  # logits of order 1, float32 both sides, another summation order


def _load(name, *parts):
    spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("dsv3_reference", "reference", "deepseek_v3.py")

# 1 dense + 2 expert layers; 16 experts in 4 groups of which 2 stay, top-4,
# ids 4..7 held; a 32-wide latent with an 8-wide rotated key; YaRN as published
TOY = dict(
    vocab_size=256, hidden_size=64, num_layers=3, num_attention_heads=4, ffn_hidden_size=96,
    moe_ffn_hidden_size=32, max_position_embeddings=128, hidden_dropout_prob=0.0,
    attention_probs_dropout_prob=0.0, norm="rmsnorm", norm_eps=1e-6, position="rope",
    use_bias=False, mlp_act="swiglu", tie_embeddings=False, num_dense_layers=1,
    num_experts=16, moe_gate="sigmoid", moe_top_k=4, moe_experts_held=4, moe_expert_offset=4,
    moe_shared_experts=1, moe_route_scale=2.5, moe_n_group=4, moe_topk_group=2,
    kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_scaling_factor=40.0, rope_original_max_position=4096, rope_beta_fast=32.0,
    rope_beta_slow=1.0, rope_mscale=1.0, rope_mscale_all_dim=1.0, dtype="float32",
    attn_impl="xla",
)
SIZES = dict(TOY, rope_theta=10000.0)  # what the reference reads
BLOCK = 16


@pytest.fixture(scope="module")
def toy():
    cfg = GPTConfig(**TOY)
    params = G.init_serving_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    # a bias that moves the choice for some tokens, also across a group
    for blk in params["blocks"][1:]:
        blk["mlp"]["e_score_correction_bias"] = jnp.asarray(
            rng.normal(size=(16,)) * 0.05, jnp.float32)
    tokens = rng.integers(1, TOY["vocab_size"], size=(2, 64))
    full = np.asarray(ref.logits(params, jnp.asarray(tokens), SIZES))
    return cfg, params, tokens, full


def test_served_tree_is_what_the_reference_reads(toy):
    cfg, params, _, _ = toy
    assert set(params) == {"embeddings", "blocks", "final_ln", "head"} and len(params["blocks"]) == 3
    dense, expert = params["blocks"][0], params["blocks"][1]
    assert set(dense["mlp"]) == {"w1", "w2", "w3"}
    assert set(expert["mlp"]) == {"router_kernel", "shared", "experts", "e_score_correction_bias"}
    assert expert["mlp"]["experts"]["w1"].shape == (4, 64, 32)  # held, not 16
    assert expert["mlp"]["router_kernel"].shape == (64, 16)  # published width
    assert set(expert["attn"]) == {"q_a_kernel", "q_a_norm", "q_b_kernel", "kv_a_kernel",
                                   "kv_a_norm", "k_b_kernel", "v_b_kernel", "out_kernel"}
    assert expert["attn"]["kv_a_kernel"].shape == (64, 40) and cfg.cached_token == ((1, 40),)
    pools = G.init_paged_pools(cfg, 5, BLOCK)
    assert pools.k.shape == (3, 5, 1, 40, BLOCK) and pools.v is None


def test_training_forward_equals_the_reference(toy):
    """``model.forward`` (the stacked tree, expanded attention) on the same
    values: the block has ONE definition."""
    cfg, params, tokens, full = toy
    stacked = gpt.init(cfg, jax.random.PRNGKey(0))
    bias = jnp.stack([b["mlp"]["e_score_correction_bias"] for b in params["blocks"][1:]])
    got = gpt.forward(stacked, jnp.asarray(tokens), cfg, expert_bias=bias)
    assert float(jnp.max(jnp.abs(got - full))) < F32_ROUNDINGS
    flash = gpt.forward(stacked, jnp.asarray(tokens), dataclasses.replace(cfg, attn_impl="flash"),
                        expert_bias=bias)  # values padded to the key width and cut again
    assert float(jnp.max(jnp.abs(flash - full))) < F32_ROUNDINGS


def _prefill_then_decode(cfg, params, tokens, lens, steps):
    """Rows of unequal length through paged_prefill then ``steps`` decode
    steps of the whole batch; -> logits at every position from each row's
    last prompt token on, [rows][steps + 1, vocab]."""
    P = 48
    pools = G.init_paged_pools(cfg, 12, BLOCK)
    tables = np.zeros((len(lens), 4), np.int32)
    out = []
    for r, n in enumerate(lens):
        tables[r] = 1 + 4 * r + np.arange(4)
        prompt = np.zeros((1, P), np.int32)
        prompt[0, :n] = tokens[r, :n]
        pools, last, counts, pairs = G.paged_prefill(
            params, jnp.asarray(prompt), jnp.int32(n), pools, jnp.asarray(tables[r, :3]), cfg,
            return_moe=True)
        assert int(pairs[0]) == n * cfg.moe_top_k * 2  # the real tokens' pairs, 2 expert layers
        assert int(counts.sum()) == n
        out.append([np.asarray(last)])
    positions = np.array(lens, np.int32)
    step = jax.jit(lambda p, nxt, pools, tables, positions: G.paged_forward_step(
        p, nxt, pools, tables, positions, jnp.ones((len(lens),), bool), cfg))
    for i in range(steps):
        nxt = jnp.asarray([tokens[r, lens[r] + i] for r in range(len(lens))])
        lg, pools = step(params, nxt, pools, jnp.asarray(tables), jnp.asarray(positions))
        for r in range(len(lens)):
            out[r].append(np.asarray(lg[r, 0]))
        positions += 1
    return [np.stack(o) for o in out]


def test_prefill_then_paged_decode_equals_the_full_forward(toy):
    """(a) rows of 23 and 40 tokens; 20 steps take the first over a block
    edge at 32 and the second over 48; every position's logits."""
    cfg, params, tokens, full = toy
    lens = [23, 40]
    got = _prefill_then_decode(cfg, params, tokens, lens, 20)
    for r, n in enumerate(lens):
        assert np.abs(got[r] - full[r, n - 1:n + 20]).max() < F32_ROUNDINGS


def test_absorbed_decode_equals_expanded_attention_on_the_same_cache(toy):
    """(b) one layer's attention both ways over the same latents."""
    cfg, params, _, _ = toy
    rng = np.random.default_rng(1)
    attn = params["blocks"][1]["attn"]
    s = 37
    x = jnp.asarray(rng.normal(size=(1, s, cfg.hidden_size)), jnp.float32)
    positions = jnp.arange(s)[None]
    q_nope, q_r, latent = gpt.latent_projections(attn, x, positions, cfg)
    expanded = gpt.latent_attention_expanded(attn, q_nope, q_r, latent, cfg)[0, -1]  # [n, v]
    pool = jnp.zeros((1, 4, 1, 40, BLOCK)).at[0, jnp.arange(1, 4)].set(
        jnp.pad(latent[0], ((0, 48 - s), (0, 0))).reshape(3, 1, BLOCK, 40).transpose(0, 1, 3, 2))
    q = jnp.concatenate([jnp.einsum("nd,cnd->nc", q_nope[0, -1], attn["k_b_kernel"]),
                         q_r[0, -1]], axis=-1)[None]
    o_lat = DA.mla_paged_decode_attention(
        q, pool, jnp.asarray([[1, 2, 3]]), jnp.asarray([s - 1]), layer=0,
        scale=gpt.latent_softmax_scale(cfg), kv_lora=cfg.kv_lora_rank)
    absorbed = jnp.einsum("nc,cnd->nd", o_lat[0], attn["v_b_kernel"])
    assert float(jnp.max(jnp.abs(absorbed - expanded))) < F32_ROUNDINGS


@pytest.mark.parametrize("block,width", [(16, 6), (8, 3), (128, 5)])
def test_the_mla_kernels_equal_their_lax_spellings(block, width):
    """(c) pfx_decode_mla_paged and pfx_mla_write in interpret mode: rows at
    a page's first and last slot, a table wider and narrower than a grid
    step, a spare page in the last group."""
    rng = np.random.default_rng(2)
    layers, w, kl, n, b = 3, 40, 32, 4, 5
    nb = b * width + 1
    pool = jnp.asarray(rng.normal(size=(layers, nb, 1, w, block)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, n, w)), jnp.float32)
    tables = jnp.asarray(1 + rng.permutation(nb - 1)[:b * width].reshape(b, width), jnp.int32)
    positions = jnp.asarray([0, block - 1, block, 2 * block + 3, width * block - 1], jnp.int32)
    for layer in (0, 2):
        kw = dict(layer=layer, scale=0.3, kv_lora=kl)
        lax = DA.mla_paged_decode_attention(q, pool, tables, positions, impl="lax", **kw)
        pallas = DA.mla_paged_decode_attention(q, pool, tables, positions, impl="pallas", **kw)
        dense = []
        for i in range(b):
            ctx = jnp.concatenate([pool[layer, t, 0].T for t in tables[i]])[:int(positions[i]) + 1]
            dense.append(jax.nn.softmax(0.3 * q[i] @ ctx.T, -1) @ ctx[:, :kl])
        assert float(jnp.max(jnp.abs(lax - jnp.stack(dense)))) < F32_ROUNDINGS
        assert float(jnp.max(jnp.abs(pallas - lax))) < F32_ROUNDINGS
    new = jnp.asarray(rng.normal(size=(b, w)), jnp.float32)
    blk, off = tables[:, 0], positions % block
    a = DA.latent_page_write(pool, new, blk, off, layer=1, impl="lax")
    p = DA.latent_page_write(pool, new, blk, off, layer=1, impl="pallas")
    assert bool((a == p).all()) and bool((a[1, blk[3], 0, :, off[3]] == new[3]).all())
    assert bool((a[0] == pool[0]).all())  # another layer's pages are untouched


_WORK_BS, _WORK_M = 16, 160  # 160 pages of 16: groups of 64 pages (1,024 tokens), the third ragged
# a context that ends in the first group, on its last slot, on the second's first slot, in the
# middle group, in the last group and on the table's last slot
_WORK_POSITIONS = [0, 17, 1023, 1024, 1500, _WORK_M * _WORK_BS - 1]
_WORK_MASKS = {
    "none_live": [0, 0, 0, 0, 0, 0], "one_live": [0, 0, 0, 0, 1, 0],
    "scattered_half": [1, 0, 0, 1, 0, 1], "all_live": [1, 1, 1, 1, 1, 1],
}


@functools.lru_cache(maxsize=None)
def _work_call(impl, masked):
    """One compiled call a (spelling, with or without a list): the mask is an
    argument, so its cases share the program."""
    def call(q, pool, tables, positions, active):
        work = DA.mla_work_list(DA.live_slots(active), positions, _WORK_BS, _WORK_M) if masked else None
        return DA.mla_paged_decode_attention(q, pool, tables, positions, layer=1, scale=0.3,
                                             kv_lora=32, impl=impl, work=work)

    return jax.jit(call)


@pytest.mark.parametrize("mask", list(_WORK_MASKS))
@pytest.mark.parametrize("impl", ["lax", "pallas"])
def test_the_mla_kernel_visits_the_work_list_only(impl, mask):
    """Both spellings: the rows the step's work list names equal the
    unmasked call's rows to the bit, whichever group their context ends in;
    a row it leaves out is exactly 0 and is NEVER READ: its table holds page
    ids past the arena and a page of NaN, its position is stale, far past the
    table."""
    rng = np.random.default_rng(48)
    b, bs, M, w = len(_WORK_POSITIONS), _WORK_BS, _WORK_M, 40
    nb = b * M + 2
    poison = nb - 1  # a page of NaN that no live row's table names
    pool = jnp.asarray(rng.normal(size=(2, nb, 1, w, bs)), jnp.float32).at[:, poison].set(jnp.nan)
    q = jnp.asarray(rng.normal(size=(b, 4, w)), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, nb - 1))[:b * M].reshape(b, M), jnp.int32)
    positions = jnp.asarray(_WORK_POSITIONS, jnp.int32)
    active = jnp.asarray(_WORK_MASKS[mask], bool)
    every = _work_call(impl, False)(q, pool, tables, positions, jnp.ones((b,), bool))
    assert bool(jnp.isfinite(every).all())
    stale = jnp.where(jnp.arange(M)[None] % 2 == 0, poison, nb + 1000)  # NaN, or past the arena
    got = _work_call(impl, True)(
        q, pool, jnp.where(active[:, None], tables, stale),
        jnp.where(active, positions, M * bs + 1000), active)
    assert got.shape == every.shape and bool(jnp.isfinite(got).all())
    np.testing.assert_array_equal(np.asarray(got[active]), np.asarray(every[active]))
    assert bool((got[~active] == 0).all())


@pytest.mark.parametrize("block,width", [(16, 160), (128, 64), (128, 8), (8, 3)])
def test_the_work_list_follows_live_slots_and_counts_what_the_counter_counts(block, width):
    """`mla_work_list` names each live row of `live_slots`, in its order, once
    a group its context reaches (groups 0 .. the last, one after the other)
    and nothing else; the tokens its steps walk are what
    `mla_tokens_computed` gives the scheduler's counter for those rows."""
    rng = np.random.default_rng(block + width)
    b = 7
    step = DA.mla_pages_per_step(block, width) * block
    positions = rng.integers(0, width * block, size=b)
    positions[:3] = [0, min(step, width * block) - 1, width * block - 1]
    for mask in ([0] * b, [1] * b, [1, 0, 1, 1, 0, 0, 1], [0, 0, 0, 0, 0, 1, 0]):
        active = np.asarray(mask, bool)
        live, n = DA.live_slots(jnp.asarray(active))
        rows, groups, count = DA.mla_work_list((live, n), jnp.asarray(positions, jnp.int32), block, width)
        assert rows.dtype == groups.dtype == count.dtype == jnp.int32 and count.shape == (1,)
        assert rows.shape == groups.shape == (b * -(-width // (step // block)),)
        want = [(r, g) for r in np.flatnonzero(active) for g in range(positions[r] // step + 1)]
        k = int(count[0])
        assert list(zip(rows[:k].tolist(), groups[:k].tolist())) == want
        assert k * step == int(DA.mla_tokens_computed(positions[active], block, width).sum())
        assert DA._live_mask((rows, count), b).tolist() == active.tolist()


def test_the_mla_kernel_s_grid_is_one_dynamic_axis():
    """The decode kernel's grid is ONE axis whose bound is the list's count
    (not slots x groups), with a list and with the identity list of a caller
    that has none."""
    pool = jnp.zeros((1, 9, 1, 40, 16))
    args = (jnp.zeros((4, 2, 40)), pool, jnp.zeros((4, 2), jnp.int32), jnp.zeros((4,), jnp.int32))
    for masked in (False, True):
        def call(q, pool, tables, positions, active):
            work = DA.mla_work_list(DA.live_slots(active), positions, 16, 2) if masked else None
            return DA.mla_paged_decode_attention(q, pool, tables, positions, layer=0, scale=1.0,
                                                 kv_lora=32, impl="pallas", work=work)

        eqns = [e for e in jax.make_jaxpr(call)(*args, jnp.ones((4,), bool)).eqns
                if e.primitive.name == "pallas_call"]
        assert len(eqns) == 1
        mapping = eqns[0].params["grid_mapping"]
        assert len(mapping.grid) == 1 and mapping.num_dynamic_grid_bounds == 1
        assert mapping.num_index_operands == 5  # layer, tables, positions, rows, groups


def test_group_limited_choice_and_weights_against_the_reference():
    """(d) the program's route against the reference's, with a bias that
    moves a token's choice into another group (weights stay unbiased)."""
    cfg = GPTConfig(**TOY)
    rng = np.random.default_rng(3)
    m = jnp.asarray(rng.normal(size=(200, 64)), jnp.float32)
    kernel = jnp.asarray(rng.normal(size=(64, 16)) * 0.3, jnp.float32)
    zero = jnp.zeros((16,), jnp.float32)
    push = zero.at[12:16].set(0.6)  # lifts group 3 over the others
    for bias in (zero, push):
        idx, w = moe.sigmoid_route(m, kernel, bias, cfg)
        ridx, rw = ref.route(m, kernel, bias, SIZES)
        assert bool((jnp.sort(idx, -1) == jnp.sort(ridx, -1)).all())
        assert float(jnp.max(jnp.abs(jnp.sort(w, -1) - jnp.sort(rw, -1)))) < 1e-6
        groups = np.asarray(idx) // 4
        assert all(len(set(row)) <= 2 for row in groups)  # topk_group = 2
        assert float(jnp.max(jnp.abs(w.sum(-1) - 2.5))) < 1e-5  # norm_topk_prob x scale
    moved = np.asarray(moe.sigmoid_route(m, kernel, push, cfg)[0]) // 4
    plain = np.asarray(moe.sigmoid_route(m, kernel, zero, cfg)[0]) // 4
    assert (np.array([3 in r for r in moved]).sum() > np.array([3 in r for r in plain]).sum())
    free, _ = ref.route(m, kernel, zero, SIZES, group_step=False)  # the control's route
    assert any(len(set(r)) > 2 for r in np.asarray(free) // 4)


@pytest.mark.parametrize("n,every,kernel", [(50, True, False), (50, False, False), (200, False, False),
                                            (50, False, True), (200, False, True)])
def test_the_shares_add_up_to_the_uncut_layer(n, every, kernel):
    """(e) the 4 shares' routed parts plus the shared expert once = the
    reference's layer with all 16 experts held, on the decode step's path
    (every held expert on every token, asked for by name) and on the sorted
    one, which a size alone never leaves: through XLA's grouped product and
    through the serving prefill's kernel, which gives what
    ``jax.lax.ragged_dot`` gives (three matrices an expert here)."""
    product = {"grouped_product": functools.partial(grouped_matmul, impl="pallas")} if kernel else {}
    whole = GPTConfig(**dict(TOY, moe_experts_held=16, moe_expert_offset=0))
    p = gpt.init(whole, jax.random.PRNGKey(4))
    mlp = jax.tree.map(lambda a: a[0], p["layers"]["mlp"])
    rng = np.random.default_rng(4)
    bias = jnp.asarray(rng.normal(size=(16,)) * 0.05, jnp.float32)
    mlp["e_score_correction_bias"] = bias
    m = jnp.asarray(rng.normal(size=(n, 64)), jnp.float32)
    want = ref.expert_layer(m, mlp, dict(SIZES, moe_expert_offset=0))
    total = moe.swiglu(m, mlp["shared"])
    for share in range(4):
        cfg = GPTConfig(**dict(TOY, moe_experts_held=4, moe_expert_offset=4 * share))
        part = dict(mlp, experts=jax.tree.map(lambda a: a[4 * share:4 * share + 4], mlp["experts"]))
        out, stats = moe.routed_experts(part, m, bias, cfg, every_held_expert=every, **product)
        assert int(stats["load"].sum()) == n * 4
        if kernel:
            ragged, _ = moe.routed_experts(part, m, bias, cfg)
            assert float(jnp.max(jnp.abs(out - ragged))) < F32_ROUNDINGS
        total = total + out
    assert float(jnp.max(jnp.abs(total - want))) < F32_ROUNDINGS


def test_yarn_frequencies_and_softmax_scale_by_hand():
    """(f) the published sizes: 64 rope dims, factor 40, 4096 original
    positions, beta 32 / 1."""
    cfg = GPTConfig(**dict(TOY, qk_nope_head_dim=128, qk_rope_head_dim=64))
    f = np.asarray(gpt.rope_frequencies(cfg), np.float64)
    base = 10000.0 ** (-2.0 * np.arange(32) / 64)
    low = 64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(10000.0))  # 10.47
    high = 64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(10000.0))  # 22.51
    assert math.floor(low) == 10 and math.ceil(high) == 23
    np.testing.assert_allclose(f[:11], base[:11], rtol=1e-6)  # fast dims keep their frequency
    np.testing.assert_allclose(f[23:], base[23:] / 40.0, rtol=1e-6)  # slow dims stretch by 40
    mid = (16 - 10) / 13.0
    np.testing.assert_allclose(f[16], base[16] * (1 - mid) + base[16] / 40 * mid, rtol=1e-6)
    np.testing.assert_allclose(f, np.asarray(ref.yarn_frequencies(dict(SIZES, qk_rope_head_dim=64))),
                               rtol=1e-6)
    m = 0.1 * math.log(40.0) + 1.0
    assert abs(m - 1.36889) < 1e-5
    assert abs(gpt.latent_softmax_scale(cfg) - 192 ** -0.5 * m * m) < 1e-9
    assert abs(gpt.latent_softmax_scale(cfg) - 0.13523) < 1e-5
    x = jnp.asarray(np.random.default_rng(5).normal(size=(1, 3, 8)), jnp.float32)
    small = GPTConfig(**TOY)
    rot = gpt.rope_pairs(x, jnp.asarray([[0, 5, 9]]), small)
    ang = 5 * np.asarray(gpt.rope_frequencies(small))[1]
    a, b = float(x[0, 1, 2]), float(x[0, 1, 3])  # ADJACENT pair (2, 3) of position 5
    np.testing.assert_allclose(
        np.asarray(rot[0, 1, 2:4]), [a * math.cos(ang) - b * math.sin(ang),
                                     a * math.sin(ang) + b * math.cos(ang)], rtol=1e-5)
    assert bool((rot[0, 0] == x[0, 0]).all())  # position 0 is not rotated


# -- through the server, the engine and the scheduler ----------------------------

SERVE = {
    "Global": {"global_batch_size": 8, "seed": 7},
    "Engine": {"mix_precision": {"enable": False}, "save_load": {"save_steps": 0}},
    "Model": dict(TOY, module="GPTModule"),
    "Distributed": {},
    "Optimizer": {"name": "FusedAdamW", "lr": {"name": "Constant", "learning_rate": 1e-3}},
    "Generation": {"max_dec_len": 10, "min_dec_len": 10, "decode_strategy": "greedy_search",
                   "pad_to_multiple": 16, "eos_token_id": 0, "pad_token_id": 0},
}


@pytest.fixture(scope="module")
def server():
    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.core.serving import GenerationServer
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import AttrDict, process_configs

    # one device: this block is served without a mesh (refused by name below)
    cfg = process_configs(AttrDict.from_nested(SERVE), num_devices=1)
    return GenerationServer(cfg, init_dist_env(cfg, devices=jax.devices()[:1]), build_module(cfg))


def _engine(server, **kw):
    from paddlefleetx_tpu.core.continuous_batching import PagedDecodeEngine

    kw.setdefault("max_batch", 4)
    kw.setdefault("block", BLOCK)
    return PagedDecodeEngine(server, **kw)


def _greedy_reference(server, prompt, tokens):
    lg = np.asarray(ref.logits(server.params, jnp.asarray([prompt + tokens]), SIZES))[0]
    rows = lg[len(prompt) - 1:len(prompt) - 1 + len(tokens)].copy()
    rows[:, 0] = -np.inf  # min_dec_len: the end token cannot be chosen
    return rows.argmax(-1).tolist()


def test_the_scheduler_serves_the_reference_s_greedy_tokens(server):
    """(h) 6 requests through GenerationServer + ContinuousScheduler with 4
    rows: every served token is the reference's greedy choice; the work
    counters and the model's bytes a token are on the scheduler's page."""
    from paddlefleetx_tpu.core.continuous_batching import ContinuousScheduler

    eng = _engine(server)
    assert eng.kv_bytes_per_token() == 3 * 40 * 4 and eng.kv_block_bytes() == 3 * 40 * 4 * BLOCK
    assert eng.pools.v is None and eng.cache.allocator.num_blocks == 4 * eng.max_row_blocks + 1
    sched = ContinuousScheduler(eng, max_depth=16, name="dsv3-test")
    sched.start()
    try:
        rng = np.random.default_rng(6)
        prompts = [rng.integers(1, 256, size=n).tolist() for n in (20, 33, 47, 17, 60, 31)]
        futures = [sched.submit([p], 10) for p in prompts]
        for p, f in zip(prompts, futures):
            tokens = f.result(timeout=300)[0]
            assert len(tokens) == 10 and tokens == _greedy_reference(server, p, tokens)
        page = dict((n, v) for n, _, v in sched.collect())
        assert page["pfx_kv_bytes_per_token"] == 480.0
        assert page["pfx_moe_serve_pairs_total"] >= sum(map(len, prompts)) * 4 * 2
        assert 0 < page["pfx_moe_serve_held_pairs_total"] < page["pfx_moe_serve_pairs_total"]
        assert page["pfx_moe_serve_held_max_pairs_total"] >= page["pfx_moe_serve_held_pairs_total"]
        # every admission went through the kernel: 2 expert layers x 3 SwiGLU matrices a prefill
        assert page["pfx_moe_serve_grouped_calls_total"] == 6 * len(prompts) == (
            eng.mcfg.sorted_pair_products * int(sched.stats["prefill_admits"]))
        assert page["pfx_sched_decode_kv_tokens_total"] > 0
    finally:
        assert sched.shutdown(timeout=30)


def test_the_latent_grid_tokens_count_the_rows_live_at_dispatch_only(server):
    """One request in an engine of 4 slots: every committed decode step adds
    the ONE live row's context in whole grid steps to `grid_tokens` (what the
    kernel's work list holds), not the three empty slots' too."""
    from paddlefleetx_tpu.core.continuous_batching import ContinuousScheduler

    eng = _engine(server)
    sched = ContinuousScheduler(eng, max_depth=16, name="dsv3-grid-test")
    sched.start()
    try:
        base = dict(eng.stats)
        prompt = np.random.default_rng(8).integers(1, 256, size=20).tolist()
        assert len(sched.submit([prompt], 10).result(timeout=300)[0]) == 10
        d = {k: eng.stats[k] - base[k] for k in ("steps", "row_steps", "slot_steps", "grid_tokens")}
        assert 0 < d["row_steps"] < d["slot_steps"] == 4 * d["steps"]
        # 30 tokens are two pages of 16: a table two pages wide is one grid step of 32 tokens
        assert int(DA.mla_tokens_computed(np.asarray(29), BLOCK, 2)) == 32
        assert d["grid_tokens"] == 32 * d["row_steps"]
    finally:
        assert sched.shutdown(timeout=30)


def test_the_model_gives_the_page_size_unless_the_caller_does(server):
    assert _engine(server, block=0).block == 128  # GPTConfig.kv_block_default
    assert _engine(server, block=32).block == 32


@pytest.mark.parametrize("named,build", [
    pytest.param("prefill-chunk", lambda s: _engine(s, prefill_chunk=16)),
    pytest.param("prefix-cache-blocks", lambda s: _engine(s, prefix_cache_blocks=4)),
    pytest.param("int8", lambda s: _engine(s, kv_dtype="int8")),
    pytest.param("draft-k", lambda s: _engine(
        s, spec=__import__("paddlefleetx_tpu.ops.speculative", fromlist=["x"]).SpecConfig(draft_k=2))),
    pytest.param("preempt-resume", lambda s: _engine(s).preempt_row(0)),
    pytest.param("KV handoff", lambda s: _engine(s).prefill_export([1, 2, 3], 4)),
    pytest.param("coalesce", lambda s: s.generate_ids([[1, 2, 3]], max_dec_len=4)),
    pytest.param("contiguous cache", lambda s: G.generate(
        s.params, jnp.ones((1, 8), jnp.int32), s.module.config, s.gen)),
    pytest.param("tensor parallelism", lambda s: G._block_paged_forward_step(
        s.params, jnp.ones((1,), jnp.int32), G.init_paged_pools(s.module.config, 3, BLOCK),
        jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32), jnp.ones((1,), bool),
        s.module.config, object())),
    pytest.param("one token a row", lambda s: G.paged_forward_step(
        s.params, jnp.ones((1, 3), jnp.int32), G.init_paged_pools(s.module.config, 3, BLOCK),
        jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32), jnp.ones((1,), bool),
        s.module.config)),
])
def test_what_this_block_cannot_take_yet_is_refused_by_name(server, named, build):
    """(g) each raises a ValueError that names the option."""
    with pytest.raises(ValueError, match=named):
        build(server)


@pytest.mark.parametrize("option", ["num_kv_heads", "qk_norm", "sliding_window"])
def test_a_described_block_without_latent_attention_is_not_servable(option):
    base = {k: v for k, v in TOY.items() if not k.startswith(("kv_lora", "q_lora", "qk_", "v_head",
                                                               "rope_"))}
    value = {"num_kv_heads": 2, "qk_norm": True, "sliding_window": 32}[option]
    with pytest.raises(ValueError, match=option if option != "num_kv_heads" else "num_kv_heads"):
        G.check_servable(GPTConfig(**dict(base, **{option: value})))
    with pytest.raises(ValueError, match="latent attention"):
        G.check_servable(GPTConfig(**base))


# -- start-up: the served tree is made leaf by leaf --------------------------------

GPT_TOY = dict(vocab_size=96, hidden_size=32, num_layers=2, num_attention_heads=4,
               max_position_embeddings=64)


@pytest.mark.parametrize("kw", [
    pytest.param(dict(GPT_TOY, dtype="bfloat16"), id="gpt-bf16"),
    pytest.param(dict(GPT_TOY, dtype="float32"), id="gpt-f32"),
    pytest.param(dict(TOY, dtype="bfloat16"), id="latent-bf16"),
])
def test_leaf_by_leaf_start_up_gives_init_then_serving_params_to_the_bit(kw):
    cfg = GPTConfig(**kw)
    made = G.init_serving_params(cfg, jax.random.PRNGKey(3))
    want = G.serving_params(gpt.init(cfg, jax.random.PRNGKey(3)), cfg)
    assert jax.tree.structure(made) == jax.tree.structure(want)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(made)[0], jax.tree.leaves(want)):
        assert a.dtype == b.dtype and bool((a == b).all()), jax.tree_util.keystr(path)
    if cfg.latent_attention:
        blk = made["blocks"][1]
        assert blk["attn"]["q_b_kernel"].dtype == jnp.bfloat16
        assert blk["mlp"]["router_kernel"].dtype == jnp.float32  # the choice is a discontinuity
        assert blk["attn"]["kv_a_norm"].dtype == jnp.float32
        assert blk["mlp"]["experts"]["w2"].dtype == jnp.bfloat16


def test_the_gpt_serving_programs_are_what_they_were():
    """The GPT-2 block's pools, step and prefill: same outputs structure (no
    expert counts ride along) and a 2-pool arena."""
    cfg = GPTConfig(**dict(GPT_TOY, dtype="float32"))
    pools = G.init_paged_pools(cfg, 5, 8)
    assert pools.k.shape == pools.v.shape == (2, 5, 4, 8, 8) and cfg.kv_block_default == 0
    assert cfg.cached_token == ((4, 8), (4, 8))
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    out = G.paged_prefill(params, jnp.ones((1, 8), jnp.int32), jnp.int32(5), pools,
                          jnp.asarray([1]), cfg, return_moe=True)
    assert out[3] is None
    rows = G.PagedRows(jnp.zeros((1, 96)), jnp.zeros((1, 96), jnp.int32), jnp.asarray([5]),
                       jnp.zeros((1,), jnp.int32), jnp.asarray([4]), jnp.ones((1,), bool),
                       jnp.asarray([3]))
    _, _, new = G.decode_step(params, out[0], jnp.asarray([[1, 0]]), rows, cfg,
                              G.GenerationConfig(decode_strategy="greedy_search"))
    assert new.moe is None


# -- the benchmark's new data, arithmetic and reader ------------------------------


def test_the_configuration_file_states_the_cut_and_the_arithmetic_counts_the_tree():
    with open(os.path.join(BENCH, "configs", "deepseek-v3.json")) as f:
        conf = json.load(f)
    model = conf["model"]
    for key, want in dict(hidden_size=7168, num_attention_heads=128, kv_lora_rank=512,
                          q_lora_rank=1536, qk_nope_head_dim=128, qk_rope_head_dim=64,
                          v_head_dim=128, ffn_hidden_size=18432, moe_ffn_hidden_size=2048,
                          num_experts=256, moe_top_k=8, moe_n_group=8, moe_topk_group=4,
                          moe_route_scale=2.5, rope_scaling_factor=40.0).items():
        assert model[key] == want, key  # every width as published
    assert (conf["hidden_size"], conf["n_routed_experts"], conf["vocab_size"]) == (7168, 256, 129280)
    assert model["num_layers"] == 7 and model["moe_experts_held"] == 8
    assert model["vocab_size"] * 8 == 129280
    assert set(conf["reduced"]) == set(conf["reduced_keys"])
    math_ = _load("dsv3_math", "math", "deepseek_v3.py")
    assert abs(math_.param_count(model) / 1e9 - 4.327) < 0.001
    toy = conf["rehearse_model"]
    cfg = GPTConfig(**{k: v for k, v in toy.items()}, dtype="float32")
    tree = G.init_serving_params(cfg, jax.random.PRNGKey(0))
    matrices = sum(a.size for a in jax.tree.leaves(tree) if a.ndim >= 2)
    assert matrices == math_.param_count(toy)  # the arithmetic counts the program's tree
    work = math_.mla_decode_work(model, 1.0, 0.0)
    assert work["flops"] == 7 * 278528 and work["bytes"] == 7 * 1152
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    assert abs(math_.roofline_seconds(work, peaks) / 7 - 278528 / 197e12) < 1e-15  # the FLOP term, just


def test_the_kernel_roofline_reader_refuses_a_reading_over_100():
    sys.path.insert(0, BENCH)
    try:
        reader = _load("kernel_roofline", "readers", "kernel_roofline.py")
        import common
    finally:
        sys.path.remove(BENCH)
    with open(os.path.join(BENCH, "configs", "deepseek-v3.json")) as f:
        conf = json.load(f)
    kernel = "pfx_" + "decode_mla_paged"  # a kernel's name, not a metric's (lint E10)
    ctx = {"math": conf["math"], "model": conf["model"],
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           "profile_counters": [{"kv_tokens": 0, "row_steps": 0},
                                {"kv_tokens": 30_000_000, "row_steps": 12_000}],
           "kernel_self_s": {kernel: 0.6}}
    args = dict(kernel=kernel, work="mla_decode_work")
    share = reader.read(ctx, **args)
    assert 40 < share < 60  # 0.30 s of roofline work in 0.6 s
    assert reader.read(dict(ctx, kernel_self_s={}), **args) is None  # the trace does not name it
    assert reader.read({k: v for k, v in ctx.items() if k != "profile_counters"}, **args) is None
    with pytest.raises(common.Fail, match="counted too high"):
        reader.read(dict(ctx, kernel_self_s={kernel: 0.2}), **args)
