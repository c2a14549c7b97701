"""Requests preempted when the KV pool ran dry, over the window
(``EngineMetrics.preemptions``)."""
LAYER, UNIT, SOURCE, MOVES = ("kv pool", "count", "program_counter",
                              "output_tok_s")


def read(ctx):
    return ctx.counters["preemptions"]
