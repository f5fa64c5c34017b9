"""Scheduler (serving/engine.py): median time from a request's admission
into a slot to its first token read back from the device (ms): the
``serve.prefill`` spans that end in the window."""

import numpy as np

import spans


def read(ctx):
    recs = spans.window_records(ctx)
    if recs is None:
        return None
    v = [r.end_ns - r.start_ns for r in spans.named(recs, "serve.prefill")]
    return float(np.median(v)) * 1e-6 if v else None
