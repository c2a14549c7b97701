"""Mean device time of one run of the chunked-prefill program
(``jit__prefill_chunk_fn``) in the traced window."""
from bench.shares import CHUNK_PROGRAM

LAYER, UNIT, SOURCE, MOVES = ("engine step", "ms", "device_trace",
                              "output_tok_s")


def read(ctx):
    if ctx.trace is None:
        return None
    s, n = ctx.trace.modules_matching(CHUNK_PROGRAM)
    return 1000.0 * s / n if n else None
