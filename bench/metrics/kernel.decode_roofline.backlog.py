"""``kernel.decode_roofline`` in the backlog cell, where it moves
throughput."""
from bench.shares import decode_roofline

LAYER, UNIT, SOURCE, MOVES = ("kernels", "%", "device_trace",
                              "output_tok_s")


def read(ctx):
    return decode_roofline(ctx)
