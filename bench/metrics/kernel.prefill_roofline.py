"""Paged prefill kernel's share of its roofline: the least time of the
needed work of every chunk (the larger of its FLOPs and K/V bytes at
peak, ``bench/work.py``) over the summed device time of
``flash_prefill_paged`` (the Pallas call's HLO name)."""
from bench.shares import prefill_roofline

LAYER, UNIT, SOURCE, MOVES = ("kernels", "%", "device_trace", "output_tok_s")


def read(ctx):
    return prefill_roofline(ctx)
