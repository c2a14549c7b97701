"""Mean device time of one run of the decode program (``jit__decode_fn``)
in the traced window."""
from bench.shares import DECODE_PROGRAM

LAYER, UNIT, SOURCE, MOVES = ("engine step", "ms", "device_trace",
                              "output_tok_s")


def read(ctx):
    if ctx.trace is None:
        return None
    s, n = ctx.trace.modules_matching(DECODE_PROGRAM)
    return 1000.0 * s / n if n else None
