"""Model step: device-busy time (union of operation intervals) per engine
tick in the traced span (ms). Beside tick_ms it gives the host's part."""


def read(ctx):
    return ctx.busy_s() * 1e3 / len(ctx.ticks) if ctx.ticks else None
