"""Scheduler (serving/engine.py): the traced span over the engine ticks
that ran work in it (ms per tick)."""


def read(ctx):
    return ctx.trace_s() * 1e3 / len(ctx.ticks) if ctx.ticks else None
