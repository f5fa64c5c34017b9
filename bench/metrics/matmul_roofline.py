"""Kernels (kernels/ternary_matmul): the least time the span's ternary
matmuls need at the roofline (bench/work.py) over the device time of the
matmul kernels in the trace (%)."""

import work


def read(ctx):
    dev = ctx.kernel_s("ternary_matmul")
    if not ctx.ticks or dev <= 0:
        return None
    c = ctx.cell.config
    need = sum(work.tick_kernel_least_s(c, t, ctx.peaks)["ternary_matmul"]
               for t in ctx.ticks)
    return 100.0 * need / dev if need > 0 else None
