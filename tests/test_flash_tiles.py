"""The flash kernels' tile is chosen in one place, ``_block_sizes``, from the
sequence length: the tiles of the lengths the benchmark's cells trace are
pinned here (``tools/program_text.py`` masks a kernel's body, the grid in it,
so its hashes do not see a tile move), and ``flash_supported`` says what
``_block_sizes`` means.  No kernel runs."""

import pytest

from paddlefleetx_tpu.ops.flash_attention import _block_sizes, flash_supported

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
