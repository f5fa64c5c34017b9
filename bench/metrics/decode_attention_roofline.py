"""Kernels (kernels/decode_attention): the least time the span's decode_attention
calls need at the roofline (bench/work.py) over the device time of the
decode_attention kernels in the trace (%)."""

import work


def read(ctx):
    dev = ctx.kernel_s("decode_attention")
    if not ctx.ticks or dev <= 0:
        return None
    c = ctx.cell.config
    need = sum(work.tick_kernel_least_s(c, t, ctx.peaks)["decode_attention"]
               for t in ctx.ticks)
    return 100.0 * need / dev if need > 0 else None
