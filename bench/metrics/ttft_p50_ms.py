"""Median time to first token (ms) over every request whose first token
falls in the window, timed from the request's scheduled send time."""

import numpy as np


def read(ctx):
    v = [s.times[0] - s.t_sched for s in ctx.streams
         if s.times and ctx.in_window(s.times[0])]
    return float(np.median(v) * 1e3) if v else None
