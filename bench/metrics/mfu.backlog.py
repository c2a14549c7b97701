"""Model FLOPs of every token computed in the traced window (decoded and
prefilled) over its length at the chip's bf16 peak."""
from bench.shares import chunk_flops, decode_flops, mfu

LAYER, UNIT, SOURCE, MOVES = ("whole step", "%", "device_trace",
                              "output_tok_s")


def read(ctx):
    if ctx.trace is None:
        return None
    return mfu(decode_flops(ctx) + chunk_flops(ctx), ctx.trace.window_s, ctx)
