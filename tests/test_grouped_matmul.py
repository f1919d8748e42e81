"""``pfx_grouped_matmul`` (ops/grouped_matmul.py), the serving prefill's
product over its sorted pairs, against a plain loop over the groups.  The
Pallas spelling runs in interpret mode here; what Mosaic makes of it at the
cells' widths is ``tests/test_chip_compile.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlefleetx_tpu.ops import grouped_matmul as gm

F32_ROUNDINGS = 2e-5


def _loop(x, w, sizes):
    """Group after group in float32; rows of no group stay 0."""
    x, w = np.asarray(x, np.float32), np.asarray(w, np.float32)
    out = np.zeros((x.shape[0], w.shape[2]), np.float32)
    start = 0
    for g, size in enumerate(sizes):
        out[start:start + size] = x[start:start + size] @ w[g]
        start += size
    return out


def _cut(y, n_held):
    """What the expert layer does around the product: rows past the held pairs are 0."""
    live = (jnp.arange(y.shape[0]) < n_held)[:, None]
    return np.asarray(jnp.where(live, y, 0), np.float32)


GROUPS = {
    "uneven-with-empty-groups": (300, [0, 5, 130, 0, 1, 40, 0, 3]),
    "a-single-row-a-group": (12, [1, 1, 1, 1]),
    "all-rows-in-one-group": (256, [0, 256, 0]),
    "all-rows-in-the-last-group": (40, [0, 0, 40]),
    "no-held-pair": (256, [0, 0, 0, 0]),
    "held-pairs-short-of-the-buffer": (640, [100, 0, 29, 128]),
    "a-group-over-three-row-tiles": (520, [60, 300, 7]),
    "rows-no-multiple-of-the-tile": (203, [3, 150, 20]),
}
# (k, n): a toy 29 x 8 has the shape of 1856, no multiple of the 128 lanes, in
# either place; 128 x 29 lies k-minor on the chip, the shape of [2688, 1856]
ORIENTATIONS = [pytest.param(29, 8, id="k29-n8"), pytest.param(8, 29, id="k8-n29"),
                pytest.param(128, 29, id="k128-n29-k-minor"), pytest.param(16, 300, id="k16-n300")]


@pytest.mark.parametrize("impl", ["pallas", "lax"])
@pytest.mark.parametrize("k,n", ORIENTATIONS)
@pytest.mark.parametrize("case", list(GROUPS))
def test_the_grouped_product_equals_a_loop_over_the_groups(case, k, n, impl):
    rows, sizes = GROUPS[case]
    rng = np.random.default_rng(len(case) + k)
    x = jnp.asarray(rng.normal(size=(rows, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(len(sizes), k, n)), jnp.float32)
    n_held = sum(sizes)
    # rows past the held pairs hold what would poison a result that read them
    x = x.at[n_held:].set(jnp.nan)
    got = gm.grouped_matmul(x, w, jnp.asarray(sizes, jnp.int32), impl=impl)
    assert got.shape == (rows, n) and got.dtype == x.dtype
    want = _loop(jnp.where(jnp.isnan(x), 0, x), w, sizes)
    scale = max(float(np.abs(want).max()), 1.0)
    assert float(np.abs(_cut(got, n_held) - want).max()) < F32_ROUNDINGS * scale


@pytest.mark.parametrize("k,n", [pytest.param(29, 8, id="k29-n8"), pytest.param(128, 29, id="k-minor")])
def test_bf16_rows_and_matrices_accumulate_in_float32(k, n):
    rows, sizes = 300, [0, 5, 130, 0, 1, 40, 0, 3]
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(rows, k)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(len(sizes), k, n)), jnp.bfloat16)
    g = jnp.asarray(sizes, jnp.int32)
    assert gm.grouped_matmul(x, w, g, impl="pallas").dtype == jnp.bfloat16
    want = _loop(x, w, sizes)  # float32 sums of the bf16 operands, rounded once below
    want = np.asarray(jnp.asarray(want).astype(jnp.bfloat16), np.float32)
    got, lax = (_cut(gm.grouped_matmul(x, w, g, impl=i), sum(sizes)) for i in ("pallas", "lax"))
    # one bf16 rounding of the float32 sum: an ulp where the sum sits on a tie
    assert float(np.abs(got - want).max()) <= 2 ** -7 * float(np.abs(want).max())
    assert float(np.abs(got - lax).max()) <= 2 ** -7 * float(np.abs(want).max())


@pytest.mark.parametrize("rows,sizes,tm,want", [
    (300, [0, 5, 130, 0, 1, 40, 0, 3], 128, [(1, 0), (2, 0), (2, 1), (4, 1), (5, 1), (7, 1)]),
    (520, [60, 300, 7], 128, [(0, 0), (1, 0), (1, 1), (1, 2), (2, 2)]),
    (256, [0, 0, 0], 128, []),
    (12, [1, 1, 1, 1], 12, [(0, 0), (1, 0), (2, 0), (3, 0)]),
])
def test_the_grid_walks_the_held_pairs_only(rows, sizes, tm, want):
    """One visit for each (group, row tile) that shares a row, in the rows'
    order; none for an empty group or a tile past the held pairs."""
    group, tile, offsets, count = gm.visits(jnp.asarray(sizes, jnp.int32), rows, tm)
    n = int(count[0])
    assert n == len(want) and group.shape == (-(-rows // tm) + len(sizes) - 1,)
    assert list(zip(np.asarray(group)[:n].tolist(), np.asarray(tile)[:n].tolist())) == want
    assert np.asarray(offsets).tolist() == [0] + np.cumsum(sizes).tolist()
    assert int(np.asarray(tile).max(initial=0)) < -(-rows // tm)  # every listed address exists


def test_tiles_come_from_the_static_shapes():
    # the cells' products: a row tile of 128 and column blocks of whole lane tiles under 4 MB
    assert gm._tiles(1536, 2688, 1856, 2) == (128, 768)
    assert gm._tiles(6144, 1856, 2688, 2) == (128, 1024)
    assert gm._tiles(24576, 7168, 2048, 2) == (128, 256)
    assert gm._tiles(24576, 2048, 7168, 2) == (128, 1024)
    assert gm._tiles(12, 29, 8, 4) == (12, 8)  # a toy: whole


@pytest.mark.parametrize("bad,named", [
    (dict(impl="mosaic"), "impl"),
    (dict(w=jnp.zeros((3, 9, 8))), "want"),
    (dict(g=jnp.zeros((4,), jnp.int32)), "want"),
    (dict(w=jnp.zeros((3, 29, 8), jnp.bfloat16)), "cast the tree"),
])
def test_what_the_product_cannot_take_is_refused_by_name(bad, named):
    x, w, g = jnp.zeros((12, 29)), jnp.zeros((3, 29, 8)), jnp.zeros((3,), jnp.int32)
    with pytest.raises(ValueError, match=named):
        gm.grouped_matmul(bad.get("x", x), bad.get("w", w), bad.get("g", g),
                          impl=bad.get("impl", "auto"))


def test_under_jit_the_group_sizes_are_data():
    """One program for every load: the sizes are traced, the grid's bound with them."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(260, 29)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 29, 8)), jnp.float32)
    fn = jax.jit(lambda x, w, g: gm.grouped_matmul(x, w, g, impl="pallas"))
    for sizes in ([10, 0, 5], [0, 0, 0], [100, 100, 60]):
        got = fn(x, w, jnp.asarray(sizes, jnp.int32))
        assert float(np.abs(_cut(got, sum(sizes)) - _loop(x, w, sizes)[:260] * (
            np.arange(260) < sum(sizes))[:, None]).max()) < 1e-4
    assert fn._cache_size() == 1


@pytest.mark.parametrize("config,products", [("nemotron-3-nano", 46), ("deepseek-v3", 18),
                                             ("trinity-mini", 12), ("gpt-1.3b", 0)])
def test_a_configuration_s_grouped_products_a_pass(config, products):
    """What ``pfx_moe_serve_grouped_calls_total`` adds at an admission: expert
    layers x matrices an expert, from the benchmark's own configuration files."""
    import json
    import os

    from paddlefleetx_tpu.models.gpt.config import GPTConfig

    bench = os.path.join(os.path.dirname(__file__), "..", "pfx_bench")  # noqa: E10 — a directory, not a metric
    with open(os.path.join(bench, "configs", f"{config}.json")) as f:
        model = json.load(f)["model"]
    assert GPTConfig(**model).sorted_pair_products == products
