"""Kernels (kernels/prefill_append): the least time the span's prefill_append
calls need at the roofline (bench/work.py) over the device time of the
prefill_append kernels in the trace (%)."""

import work


def read(ctx):
    dev = ctx.kernel_s("prefill_append")
    if not ctx.ticks or dev <= 0:
        return None
    c = ctx.cell.config
    need = sum(work.tick_kernel_least_s(c, t, ctx.peaks)["prefill_append"]
               for t in ctx.ticks)
    return 100.0 * need / dev if need > 0 else None
