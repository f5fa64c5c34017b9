"""Front door (serving/server.py): median time from a request's arrival at
the front door to its admission into an engine slot (ms), the command
queue and the engine queue together: each request's first ``serve.queue``
span, over the requests admitted in the window."""

import numpy as np

import spans


def read(ctx):
    recs = spans.window_records(ctx)
    if recs is None:
        return None
    v = [r.end_ns - r.start_ns for r in spans.named(recs, "serve.queue")
         if not r.attrs.get("requeued")]
    return float(np.median(v)) * 1e-6 if v else None
