"""95th percentile gap between consecutive tokens of a stream (ms), pooled
over every stream, for gaps that end in the window."""

import numpy as np


def read(ctx):
    v = [b - a for s in ctx.streams for a, b in zip(s.times, s.times[1:])
         if ctx.in_window(b)]
    return float(np.percentile(v, 95) * 1e3) if v else None
