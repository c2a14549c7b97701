"""Output tokens of every step dispatched in the window, over its seconds:
from the clock read after the wait that opens it to the clock read after
the wait for all its steps that closes it."""
LAYER, UNIT, SOURCE, MOVES = "end to end", "tokens/s", "host_clock", None


def read(ctx):
    return ctx.tokens_window / ctx.window_s
