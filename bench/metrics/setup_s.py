"""Process start to the start of the lead-in: imports, weights, engine,
every program compiled or loaded from the persistent cache."""
LAYER, UNIT, SOURCE, MOVES = "end to end", "s", "host_clock", None


def read(ctx):
    return ctx.setup_s
