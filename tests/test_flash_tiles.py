"""The flash kernels' tile is chosen in one place, ``_block_sizes``, from the
sequence length: the tiles of the lengths the benchmark's cells trace are
pinned here (``tools/program_text.py`` masks a kernel's body, the grid in it,
so its hashes do not see a tile move), and ``flash_supported`` says what
``_block_sizes`` means.  The backward's schedule is chosen in one place too,
``_bwd_schedule``, from the call's shapes, and so is the layout the kernels are
handed, ``_operand_layout``: the cells' are pinned here, and a lowered
``flash_attention`` shows that the choice is the kernel that runs.  No kernel
runs."""

import re

import jax
import jax.numpy as jnp
import pytest

from paddlefleetx_tpu.ops.flash_attention import (
    _block_sizes, _bwd_schedule, _operand_layout, flash_attention, flash_supported,
)

# sequence length -> tile (0: no rung divides it, attention() takes the XLA path)
CELLS = {
    # train-345m-1chip, train-trinity-mini-1of8
    1024: 512, 8192: 512,
    # serve-1.3b-docs / -chat: prompts of 512-960 padded to 64
    512: 512, 576: 0, 640: 128, 704: 0, 768: 256, 832: 0, 896: 128, 960: 0,
    # serve-dsv3-1of32-think: 1,024-3,072 padded to 512
    1536: 512, 2048: 512, 2560: 512, 3072: 512,
    # serve-nemotron3-nano-1of8-chat, serve-falcon-h1-34b-6of72-chat: padded to 256
    # (768 and 1,024 above); serve-mellum2-12b-1of4-code: the one bucket, 2,048 (above)
    256: 256,
    # a short sequence is one block; a length no rung divides
    64: 64, 1000: 0,
}


@pytest.mark.parametrize("seq", sorted(CELLS))
def test_block_sizes_of_the_cells(seq):
    tile = CELLS[seq]
    assert flash_supported(seq) == bool(tile)
    bq, bk = _block_sizes(seq)
    if tile:
        assert (bq, bk) == (tile, tile)
    else:
        assert seq % bq or seq % bk


def test_a_caller_s_tile_is_taken_as_given():
    assert _block_sizes(256, 64) == (64, 64)
    assert _block_sizes(1000, 8) == (8, 8)  # off the ladder, the caller's own


@pytest.mark.parametrize("block,match", [(96, "divisor"), (4, "multiple of 8"), (-64, "divisor")])
def test_an_invalid_tile_raises(block, match):
    with pytest.raises(ValueError, match=match):
        _block_sizes(256, block)


# (seq, head_dim, window, group) -> the backward schedule
SCHEDULES = {
    "345m": ((1024, 64, 0, 1), "fused"),
    # train-trinity-mini-1of8: 32 query heads over 4 KV heads, window 2,048 in 3 layers of 4
    "trinity-window": ((8192, 128, 2048, 8), "split"),
    "trinity-full": ((8192, 128, 0, 8), "split"),
    "window-alone": ((1024, 64, 256, 1), "split"),
    "group-alone": ((1024, 64, 0, 2), "split"),
    # the GPT 1.3B / 6.7B / 175B recipes: head 128 at seq 1,024-2,048
    "head-128-seq-2048": ((2048, 128, 0, 1), "fused"),
    "the-longest-measured": ((4096, 64, 0, 1), "fused"),
    "above-the-measured-bound": ((8192, 64, 0, 1), "split"),
    "a-head-size-not-measured": ((1024, 80, 0, 1), "split"),
    "a-tile-not-measured": ((768, 64, 0, 1), "split"),  # the ladder gives 768 a tile of 256
    "one-short-block": ((256, 64, 0, 1), "split"),
}


@pytest.mark.parametrize("case", sorted(SCHEDULES))
def test_bwd_schedule_of_the_cells(case):
    shapes, want = SCHEDULES[case]
    assert _bwd_schedule(*shapes) == want


BF16 = jnp.bfloat16
# (seq, one shard's heads, head_dim, window, group, dtype) -> what the kernels are handed
LAYOUTS = {
    "345m": ((1024, 16, 64, 0, 1, BF16), "bsh"),
    "345m-over-two-model-shards": ((1024, 8, 64, 0, 1, BF16), "bsh"),
    "entered-512": ((512, 16, 64, 0, 1, BF16), "bsh"),
    "entered-2048": ((2048, 16, 64, 0, 1, BF16), "bsh"),
    "entered-4096": ((4096, 16, 64, 0, 1, BF16), "bsh"),
    # every reason to stay on [batch*heads, seq, head_dim]
    "window": ((1024, 16, 64, 256, 1, BF16), "bh"),
    "shared-kv-heads": ((1024, 16, 64, 0, 2, BF16), "bh"),
    "the-split-schedule": ((8192, 16, 64, 0, 1, BF16), "bh"),
    "a-tile-not-measured": ((768, 16, 64, 0, 1, BF16), "bh"),
    "a-head-that-does-not-divide-128-lanes": ((1024, 16, 80, 0, 1, BF16), "bh"),
    "an-odd-number-of-local-heads": ((1024, 3, 64, 0, 1, BF16), "bh"),
    "head-128-not-entered": ((1024, 16, 128, 0, 1, BF16), "bh"),
    "head-32-not-entered": ((1024, 16, 32, 0, 1, BF16), "bh"),
    "float32-not-measured": ((1024, 16, 64, 0, 1, jnp.float32), "bh"),
    "the-ladder-s-tile-named": ((1024, 16, 64, 0, 1, BF16, 512), "bsh"),
    "a-caller-s-tile-not-measured": ((1024, 16, 64, 0, 1, BF16, 256), "bh"),
    "a-caller-s-tile-no-multiple-of-128-lanes": ((1024, 16, 64, 0, 1, BF16, 64), "bh"),
    # the other cells' flash calls: trinity, the docs prefill, dsv3's padded latent heads
    "trinity-window": ((8192, 32, 128, 2048, 8, BF16), "bh"),
    "trinity-full": ((8192, 32, 128, 0, 8, BF16), "bh"),
    "docs-prefill": ((512, 16, 128, 0, 1, BF16), "bh"),
    "dsv3-prefill": ((2048, 16, 192, 0, 1, BF16), "bh"),
}


@pytest.mark.parametrize("case", sorted(LAYOUTS))
def test_operand_layout_of_the_cells(case):
    shapes, want = LAYOUTS[case]
    assert _operand_layout(*shapes) == want


@pytest.mark.parametrize("seq,heads,window,want", [
    (1024, (2, 2), 0, "fused"), (1024, (2, 2), 256, "split"), (1024, (4, 2), 0, "split")])
def test_flash_attention_lowers_the_schedule_the_rule_names(seq, heads, window, want):
    """The public call, as ``ops/attention.py`` makes it (no tile, no
    schedule): its backward holds the rule's kernel and not the other's."""
    n, n_kv = heads
    q = jnp.zeros((1, seq, n, 64), jnp.bfloat16)
    kv = jnp.zeros((1, seq, n_kv, 64), jnp.bfloat16)
    grad = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, window=window).astype(jnp.float32)), (0, 1, 2))
    text = jax.jit(grad).lower(q, kv, kv).as_text(debug_info=True)
    kernels = {name for name in ("dq", "dkv", "fused") if f"flash_bwd_{name}" in text}
    assert kernels == ({"fused"} if want == "fused" else {"dq", "dkv"})
    # ... in the layout the rule names: the cell's sequence and head size in the model's own
    assert _operand_layout(seq, n, 64, window, n // n_kv, jnp.bfloat16) == ("bsh" if want == "fused" else "bh")
    calls = set(re.findall(r"name=pfx_flash_(\w+)", str(jax.make_jaxpr(grad)(q, kv, kv))))
    assert calls == ({"fwd_bsh", "bwd_fused_bsh"} if want == "fused" else {"fwd", "bwd_dq", "bwd_dkv"})


@pytest.mark.parametrize("block,want", [(0, "bsh"), (512, "bsh"), (256, "bh"), (64, "bh")])
def test_a_caller_s_tile_reaches_the_layout_rule(block, want):
    """``flash_attention(block=)`` at an entered shape: only the ladder's tile
    runs in the model's layout (the statistics' block has the tile in the
    lanes: 64 there is what Mosaic refuses on the chip, and no other tile
    was measured)."""
    q = jnp.zeros((1, 1024, 2, 64), jnp.bfloat16)
    grad = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, block=block).astype(jnp.float32)), (0, 1, 2))
    calls = set(re.findall(r"name=pfx_flash_(\w+)", str(jax.make_jaxpr(grad)(q, q, q))))
    assert calls == ({"fwd_bsh", "bwd_fused_bsh"} if want == "bsh" else {"fwd", "bwd_fused"})
