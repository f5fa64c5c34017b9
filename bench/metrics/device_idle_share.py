"""Device: share of the traced span with no operation running (%)."""


def read(ctx):
    return 100.0 * (1.0 - ctx.busy_s() / ctx.trace_s())
