"""Paged KV cache units: the pure host-side block allocator/manager
(`core/paged_cache.py` — alloc/free/fragmentation/exhaustion, loud on
every corruption-shaped misuse) and the block-table-indexed attention
kernel (`ops/decode_attention.paged_decode_attention`, lax + pallas
spellings vs a dense gather reference).  `make test-paged` runs these
plus the continuous-batching suite and drill."""

import numpy as np
import pytest

from paddlefleetx_tpu.core.paged_cache import (
    BlockAllocator,
    BlockPoolExhausted,
    NULL_BLOCK,
    PagedCacheManager,
    blocks_for,
    kv_block_size,
)

# ---------------------------------------------------------------------------
# allocator (no jax: pure host bookkeeping)
# ---------------------------------------------------------------------------


def test_alloc_free_roundtrip():
    a = BlockAllocator(9)  # blocks 1..8 usable
    got = a.alloc(3)
    assert len(got) == 3 and NULL_BLOCK not in got
    assert a.used_count() == 3 and a.free_count() == 5
    a.free(got)
    assert a.used_count() == 0 and a.free_count() == 8
    # freed blocks are reusable
    again = a.alloc(8)
    assert sorted(again) == list(range(1, 9))


def test_null_block_never_allocated():
    a = BlockAllocator(4)
    assert NULL_BLOCK not in a.alloc(3)
    with pytest.raises(ValueError, match="null block"):
        a.free([NULL_BLOCK])


def test_exhaustion_is_loud_and_names_the_shortfall():
    a = BlockAllocator(5)
    a.alloc(3)
    with pytest.raises(BlockPoolExhausted, match="need 2, have 1"):
        a.alloc(2)
    # the failed alloc took nothing: the remaining block is still usable
    assert a.free_count() == 1
    assert len(a.alloc(1)) == 1


def test_double_free_and_bad_ids_raise():
    a = BlockAllocator(6)
    got = a.alloc(2)
    a.free(got)
    with pytest.raises(ValueError, match="double free"):
        a.free([got[0]])
    with pytest.raises(ValueError, match="out of range"):
        a.free([99])
    with pytest.raises(ValueError, match="out of range"):
        a.free([-1])
    # a duplicate id WITHIN one call is the same silent-aliasing hazard:
    # accepted, it would enter the free list twice and later hand one
    # block to two sequences
    got2 = a.alloc(2)
    with pytest.raises(ValueError, match="double free"):
        a.free([got2[0], got2[0]])
    # a loud free must be ATOMIC: nothing was freed by the failing calls
    assert a.used_count() == 2
    a.free(got2)
    assert a.free_count() == 5


def test_fragmentation_metric_and_defrag():
    a = BlockAllocator(9)
    rows = [a.alloc(2) for _ in range(4)]  # all 8 blocks out
    assert a.fragmentation() == 0.0  # empty free list counts as unfragmented
    a.free(rows[0])  # blocks 1,2
    a.free(rows[2])  # blocks 5,6 — two separate runs of 2
    assert a.fragmentation() == pytest.approx(0.5)
    a.free(rows[1])  # 3,4: free space becomes one run 1..6
    a.defrag()
    assert a.fragmentation() == 0.0
    # lowest-first handout keeps live allocations packed
    assert a.alloc(3) == [1, 2, 3]


def test_blocks_for_and_block_size():
    assert blocks_for(0, 16) == 0
    assert blocks_for(1, 16) == 1
    assert blocks_for(16, 16) == 1
    assert blocks_for(17, 16) == 2
    # the caller's, else the model's own, else the library's 16
    assert kv_block_size(32, default=128) == 32
    assert kv_block_size(default=128) == 128
    assert kv_block_size() == 16
    for bad in (12, 4, -16):
        with pytest.raises(ValueError, match="multiple of 8"):
            kv_block_size(bad)


def test_manager_admit_release_tables():
    m = PagedCacheManager(10, block=16)
    t1 = m.admit(1, 40)  # 3 blocks
    assert len(t1) == 3
    assert m.table(1, width=5) == t1 + [NULL_BLOCK, NULL_BLOCK]
    with pytest.raises(ValueError, match="already admitted"):
        m.admit(1, 16)
    with pytest.raises(ValueError, match="width"):
        m.table(1, width=2)
    assert m.stats()["kv_blocks_used"] == 3
    assert m.can_admit(16 * 6) and not m.can_admit(16 * 7)
    m.release(1)
    with pytest.raises(ValueError, match="no allocation"):
        m.release(1)
    assert m.stats()["kv_blocks_used"] == 0 and m.live_sequences() == 0


def test_manager_exhaustion_keeps_bookkeeping_consistent():
    m = PagedCacheManager(4, block=16)
    m.admit(1, 32)  # 2 of 3 usable
    with pytest.raises(BlockPoolExhausted):
        m.admit(2, 32)
    # the failed admission left no phantom sequence behind
    assert m.live_sequences() == 1
    m.release(1)
    assert len(m.admit(2, 48)) == 3


# ---------------------------------------------------------------------------
# paged attention kernel (lax CPU-mandatory; pallas interpret-mode, slow
# per the repo's interpret-compile convention)
# ---------------------------------------------------------------------------

LAX = pytest.param("lax", id="lax")
PALLAS = pytest.param("pallas", id="pallas", marks=pytest.mark.slow)

# (impl, shape) cases: the first two are the kernel's original cases
# (pallas interpreted: slow); the others are shapes the grid of PR 25
# has to get right — every head of a row in one grid step, a group of
# pages (128 tokens) a step, nothing run past a row's context — small
# enough to interpret inside the default run.  ``pos`` is each row's
# query slot (context pos + 1); a table row of None is an empty slot
# (all null blocks, position 0).
_BASE = dict(n=2, d=8, bs=8, M=4, pos=[17, 9, 30])
_SHAPES = {
    # 345M / 1.3B head shapes: 16 heads of 64 / 128, pages of 16
    "n16_d64": dict(n=16, d=64, bs=16, M=8, pos=[17, 100, 127]),
    "n16_d128": dict(n=16, d=128, bs=16, M=8, pos=[64, 3, 126]),
    # a context that ends exactly on a page edge (32, 16 tokens), on a
    # page-group edge (128 = 8 pages), and one token past each
    "page_edge": dict(n=2, d=8, bs=16, M=16, pos=[31, 15, 32, 16]),
    "group_edge": dict(n=2, d=8, bs=16, M=16, pos=[127, 128, 255, 129]),
    "length_1": dict(n=2, d=8, bs=16, M=8, pos=[0, 0, 5]),
    # an empty slot (null table, position 0) beside a full-width row
    "null_slot": dict(n=2, d=8, bs=16, M=16, pos=[0, 255, 40], null=[0]),
    # table width not a multiple of the page group (12 pages, groups of 8)
    "ragged_width": dict(n=2, d=8, bs=16, M=12, pos=[191, 130, 127, 7]),
    # one page a grid step (a page as wide as the group), two pages
    "page_128": dict(n=2, d=8, bs=128, M=2, pos=[200, 127, 128]),
    "page_64": dict(n=2, d=8, bs=64, M=4, pos=[255, 63, 64, 129]),
    "int8": dict(n=4, d=16, bs=16, M=12, pos=[100, 191, 15, 128], int8=True),
    "int8_n16_d128": dict(n=16, d=128, bs=16, M=8, pos=[127, 40], int8=True),
}
CASES = (
    [pytest.param("lax", _BASE, id="lax"),
     pytest.param("pallas", _BASE, id="pallas", marks=pytest.mark.slow)]
    + [pytest.param(impl, shape, id=f"{impl}-{name}")
       for name, shape in _SHAPES.items() for impl in ("lax", "pallas")]
)


def _paged_case(rng, b, n, d, bs, M, nb, t=1, null=(), int8=False):
    """-> q [b, t, n, d], pools, tables, scale kwargs ({} unless int8)."""
    import jax.numpy as jnp

    from paddlefleetx_tpu.ops.decode_attention import quantize_kv

    k_pool = jnp.asarray(rng.normal(size=(nb, n, bs, d)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(nb, n, bs, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, t, n, d)), jnp.float32)
    # disjoint per-row tables, shuffled so pool order != logical order
    ids = rng.permutation(np.arange(1, nb))[: b * M].reshape(b, M)
    for r in null:
        ids[r] = NULL_BLOCK
    tables = jnp.asarray(ids, jnp.int32)
    scales = {}
    if int8:
        k_pool, ks = quantize_kv(k_pool)
        v_pool, vs = quantize_kv(v_pool)
        scales = {"k_scale": ks, "v_scale": vs}
    return q, k_pool, v_pool, tables, scales


def _shape_case(rng, shape, t=1):
    import jax.numpy as jnp

    shape = dict(shape)
    pos = shape.pop("pos")
    b, M = len(pos), shape["M"]
    q, k_pool, v_pool, tables, scales = _paged_case(
        rng, b=b, nb=b * M + 1, t=t, **shape
    )
    return q, k_pool, v_pool, tables, jnp.asarray(pos, jnp.int32), scales


def _dense_ref(q, k_pool, v_pool, tables, positions, k_scale=None,
               v_scale=None):
    import jax
    import jax.numpy as jnp

    if k_scale is not None:
        k_pool = k_pool.astype(jnp.float32) * k_scale[..., None]
        v_pool = v_pool.astype(jnp.float32) * v_scale[..., None]
    d = q.shape[-1]
    outs = []
    for r in range(q.shape[0]):
        ks = jnp.concatenate([k_pool[t] for t in np.asarray(tables[r])], axis=1)
        vs = jnp.concatenate([v_pool[t] for t in np.asarray(tables[r])], axis=1)
        lim = int(positions[r]) + 1
        s = jnp.einsum("nd,nkd->nk", q[r, 0], ks[:, :lim]) / np.sqrt(d)
        outs.append(jnp.einsum(
            "nk,nkd->nd", jax.nn.softmax(s, axis=-1), vs[:, :lim]
        ))
    return jnp.stack(outs)[:, None]


@pytest.mark.parametrize("impl,shape", CASES)
def test_paged_attention_matches_dense_gather(impl, shape):
    from paddlefleetx_tpu.ops.decode_attention import paged_decode_attention

    rng = np.random.default_rng(0)
    q, k_pool, v_pool, tables, positions, scales = _shape_case(rng, shape)
    got = paged_decode_attention(
        q, k_pool, v_pool, tables, positions, impl=impl, **scales
    )
    want = _dense_ref(q, k_pool, v_pool, tables, positions, **scales)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("impl,shape", CASES)
def test_paged_attention_never_reads_past_a_rows_limit(impl, shape):
    """NaN-poison proof (the PR 1 convention): every pool block WHOLLY
    beyond a row's visit bound ``ceil((pos+1)/bs)`` is poisoned with NaN
    — table padding a fori bound or a DMA clamp must never gather, and
    a grid step past a row's last page must load, multiply and store
    nothing.  The result must EQUAL the unpoisoned one to the bit, or
    the kernel touched blocks it has no business touching.  (int8 pools
    cannot hold a NaN: their scale planes carry the poison.  Within a
    visited block, masked tail slots follow the stale-tail contract:
    they hold stale-but-finite values in real traffic, same as the
    contiguous kernel.)"""
    import jax.numpy as jnp

    from paddlefleetx_tpu.ops.decode_attention import paged_decode_attention

    rng = np.random.default_rng(1)
    q, k_pool, v_pool, tables, positions, scales = _shape_case(rng, shape)
    bs, M = k_pool.shape[2], tables.shape[1]
    clean = paged_decode_attention(
        q, k_pool, v_pool, tables, positions, impl=impl, **scales
    )

    past = []
    for r, pos in enumerate(np.asarray(positions)):
        first_unvisited = -(-(int(pos) + 1) // bs)
        # (an empty slot's null block is shared padding: never poisoned)
        past += [int(tables[r, j]) for j in range(first_unvisited, M)
                 if int(tables[r, j]) != NULL_BLOCK]
    assert past
    if scales:
        poisoned = {k: v.at[np.array(past)].set(np.nan)
                    for k, v in scales.items()}
        args = (k_pool, v_pool)
    else:
        kp, vp = np.array(k_pool), np.array(v_pool)
        kp[past] = np.nan
        vp[past] = np.nan
        poisoned, args = {}, (jnp.asarray(kp), jnp.asarray(vp))
    got = paged_decode_attention(
        q, *args, tables, positions, impl=impl, **poisoned
    )
    assert np.all(np.isfinite(np.asarray(got)))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))


@pytest.mark.parametrize("impl,shape", CASES)
def test_paged_attention_multi_token_chunk_is_causal(impl, shape):
    """t > 1 (the speculative verify chunk): query qi of row r attends
    its logical slots [0, positions[r] + qi + 1) — each chunk query must
    equal a t=1 call at its own position (same cache, shifted limit).
    The chunk starts 2 slots before each case's position, so where that
    sits on a page or group edge the chunk straddles it."""
    import jax.numpy as jnp

    from paddlefleetx_tpu.ops.decode_attention import paged_decode_attention

    rng = np.random.default_rng(2)
    t = 3
    qt, k_pool, v_pool, tables, positions, scales = _shape_case(rng, shape, t=t)
    positions = jnp.maximum(positions - (t - 1), 0)
    got = paged_decode_attention(
        qt, k_pool, v_pool, tables, positions, impl=impl, **scales
    )
    for qi in range(t):
        one = paged_decode_attention(
            qt[:, qi : qi + 1], k_pool, v_pool, tables, positions + qi,
            impl=impl, **scales
        )
        np.testing.assert_allclose(
            np.asarray(got[:, qi : qi + 1]), np.asarray(one), atol=2e-5
        )


@pytest.mark.parametrize("impl", ["lax", "pallas"])
def test_paged_attention_prefill_chunk_wider_than_a_query_tile(impl):
    """A prefill chunk through the paged path (prefix reuse, chunked
    prefill) is wider than the kernel's query tile: 80 queries are two
    tiles, the second ragged, each with its own last needed page.  Both
    spellings must agree with the dense reference query by query."""
    import jax.numpy as jnp

    from paddlefleetx_tpu.ops.decode_attention import paged_decode_attention

    rng = np.random.default_rng(3)
    t = 80
    q, k_pool, v_pool, tables, _ = _paged_case(
        rng, b=2, n=2, d=8, bs=16, M=16, nb=33, t=t
    )
    positions = jnp.asarray([100, 0], jnp.int32)
    got = paged_decode_attention(q, k_pool, v_pool, tables, positions, impl=impl)
    for qi in (0, 1, 27, 63, 64, 79):
        want = _dense_ref(q[:, qi: qi + 1], k_pool, v_pool, tables,
                          positions + qi)
        np.testing.assert_allclose(
            np.asarray(got[:, qi: qi + 1]), np.asarray(want), atol=2e-5
        )


@pytest.mark.parametrize("t", [1, 3])
@pytest.mark.parametrize("int8", [False, True], ids=["native", "int8"])
@pytest.mark.parametrize("impl", ["lax", "pallas"])
def test_paged_attention_reads_one_layer_of_a_stacked_arena(impl, int8, t):
    """With ``layer`` the pools are the whole arena [layers, nb, ...] and
    the kernel reads that layer's pages where they lie (the serving step
    carries the arena and never slices a layer out of it).  The result is
    the one-layer call's on ``pools[layer]``, to the bit, with every OTHER
    layer poisoned: a page address that dropped the layer would read NaN.
    (int8 pools cannot hold a NaN: their scale planes carry it.)"""
    import jax
    import jax.numpy as jnp

    from paddlefleetx_tpu.ops.decode_attention import paged_decode_attention

    rng = np.random.default_rng(4)
    layers, b, M = 3, 3, 12
    cases = [
        _paged_case(rng, b=b, n=4, d=16, bs=16, M=M, nb=b * M + 1, t=t, int8=int8)
        for _ in range(layers)
    ]
    q, _, _, tables, _ = cases[1]
    positions = jnp.asarray([100, 15, 128], jnp.int32)
    stacked = jax.jit(
        lambda layer, k, v, scales: paged_decode_attention(
            q, k, v, tables, positions, layer=layer, impl=impl, **scales))
    k_all = jnp.stack([c[1] for c in cases])
    v_all = jnp.stack([c[2] for c in cases])
    scales_all = {
        name: jnp.stack([c[4][name] for c in cases]) for name in cases[0][4]
    }

    def poison(x, layer):  # every layer but this one
        keep = (jnp.arange(layers) == layer).reshape((layers,) + (1,) * (x.ndim - 1))
        return jnp.where(keep, x, jnp.nan)

    for layer in range(layers):
        k, v, scales = k_all, v_all, scales_all
        if int8:
            scales = {name: poison(x, layer) for name, x in scales.items()}
        else:
            k, v = poison(k, layer), poison(v, layer)
        got = stacked(jnp.int32(layer), k, v, scales)
        _, k1, v1, _, scales1 = cases[layer]
        want = paged_decode_attention(
            q, k1, v1, tables, positions, impl=impl, **scales1)
        assert np.isfinite(np.asarray(want)).all()
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("bs,M,pages", [(16, 64, 8), (16, 4, 4), (32, 32, 4),
                                        (128, 8, 1), (256, 4, 1), (8, 1, 1)])
def test_paged_pages_per_step_follows_the_shapes(bs, M, pages):
    """A grid step walks 128 tokens of a row: fewer pages as the page
    grows, never more than the table holds, at least one."""
    from paddlefleetx_tpu.ops.decode_attention import paged_pages_per_step

    assert paged_pages_per_step(bs, M) == pages


@pytest.mark.parametrize("pos,t,bs,M,want", [
    # 16-slot pages, groups of 8 (128 tokens): context 1 -> one step;
    # 128 -> one; 129 -> two; an empty slot (position 0) -> one
    ([0, 127, 128, 0], 1, 16, 64, [128, 128, 256, 128]),
    # a verify chunk of 5 reaches 4 slots further
    ([123, 124], 5, 16, 64, [128, 256]),
    # never past the table: 12 pages are two steps of 8 pages
    ([191, 500], 1, 16, 12, [256, 256]),
    # a table narrower than a group: the step is the table
    ([3, 40], 1, 16, 4, [64, 64]),
    ([100, 300], 1, 128, 8, [128, 384]),
])
def test_paged_tokens_computed_is_the_context_in_whole_grid_steps(
        pos, t, bs, M, want):
    """What `pfx_sched_decode_grid_tokens_total` sums: each slot's
    context rounded up to the kernel's grid step."""
    from paddlefleetx_tpu.ops.decode_attention import paged_tokens_computed

    got = paged_tokens_computed(np.asarray(pos), t, bs, M)
    assert got.tolist() == want


def test_paged_attention_arg_validation():
    import jax.numpy as jnp

    from paddlefleetx_tpu.ops.decode_attention import paged_decode_attention

    rng = np.random.default_rng(2)
    q, k_pool, v_pool, tables, _ = _paged_case(rng, b=1, n=1, d=8, bs=8, M=2, nb=4)
    with pytest.raises(ValueError, match="valid: auto"):
        paged_decode_attention(
            q, k_pool, v_pool, tables, jnp.asarray([3], jnp.int32), impl="cuda"
        )
