"""Scheduler: mean number of decoding slots per engine tick in the span."""


def read(ctx):
    if not ctx.ticks:
        return None
    return sum(len(t["dec"]) for t in ctx.ticks) / len(ctx.ticks)
