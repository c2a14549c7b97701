"""``device.idle_share`` in the backlog cell, where it moves throughput."""
from bench.shares import idle_share

LAYER, UNIT, SOURCE, MOVES = ("device", "%", "device_trace", "output_tok_s")


def read(ctx):
    return idle_share(ctx)
