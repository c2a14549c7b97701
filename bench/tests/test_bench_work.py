"""Needed work, against hand counts; padding, tiles and splits do not
enter it, where the program's own cost model (what the kernel moves)
changes with them."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import work  # noqa: E402

S = {"layers": 36, "d": 2560, "heads": 32, "kv_heads": 8, "head_dim": 128,
     "ff": 9728, "vocab": 151936}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_decode_row_by_hand():
    # a row holding 99 tokens attends 100: K and V of 100 tokens of 8
    # heads of 128 in bf16, plus q and out of 32 heads of 128 in bf16
    f, b = work.attention(work.decode_items([99]), S)
    assert f == 4 * 32 * 128 * 100
    assert b == 2 * 100 * 8 * 128 * 2 + 2 * 32 * 128 * 2
    t, bound = work.roofline_s(f, b, PEAKS)
    assert bound == "memory" and t == pytest.approx(b / 819e9)


def test_chunk_by_hand():
    # 3 queries at positions 5, 6, 7 attend 6 + 7 + 8 keys; K/V of 8
    f, b = work.attention(work.chunk_items([(5, 3)]), S)
    assert f == 4 * 32 * 128 * (6 + 7 + 8)
    assert b == 2 * 8 * 8 * 128 * 2 + 2 * 3 * 32 * 128 * 2


def test_long_chunk_is_compute_bound():
    f, b = work.attention(work.chunk_items([(2000, 256)]), S)
    assert work.roofline_s(f, b, PEAKS)[1] == "compute"


def test_model_flops_by_hand():
    dense = 36 * (2 * 2560 * 32 * 128 + 2 * 2560 * 8 * 128 + 3 * 2560 * 9728)
    assert work.dense_params(S) == dense
    got = work.model_flops(2, 2, 101 + 51, S)
    assert got == 2 * dense * 2 + 4 * 32 * 128 * 152 * 36 \
        + 2 * 2560 * 151936 * 2


@pytest.mark.parametrize("tile,split", [(1, 1), (4, 1), (8, 2), (2, 4)])
def test_padding_tile_and_split_leave_needed_work(tile, split):
    """The program's cost model counts what the kernel moves, which grows
    with the table's padding; the needed work has no argument for padding,
    tile or split, so a change to them moves only the measured time."""
    from repro.serve.kernel_costs import decode_launch_cost
    lengths = [37, 400, 1000, 3]
    need = work.attention(work.decode_items(lengths), S)
    kv_need = sum(2 * (n + 1) * 8 * 128 * 2 for n in lengths)
    moved = set()
    for width in (64, 128):
        c = decode_launch_cost(
            [n + 1 for n in lengths], width, n_q_heads=32, n_kv_heads=8,
            head_dim=128, block_size=16, kv_tile_blocks=tile,
            split_k=split, kv_dtype="bfloat16")
        assert c.gather_bytes >= kv_need
        moved.add(c.gather_bytes)
        assert work.attention(work.decode_items(lengths), S) == need
    assert len(moved) == 2
