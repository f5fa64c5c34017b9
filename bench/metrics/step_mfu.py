"""Model step: the least time the traced span's useful work needs at peak
(int8 ops at the int8 peak, bf16 flops at the bf16 peak: ternary matmuls
for every processed token, attention at each token's context, the LM head
for each emitted token; bench/work.py), as a share of the span (%)."""

import work


def read(ctx):
    if not ctx.ticks:
        return None
    c = ctx.cell.config
    need = sum(work.tick_least_s(c, t, ctx.peaks) for t in ctx.ticks)
    return 100.0 * need / ctx.trace_s()
