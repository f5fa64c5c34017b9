"""Front door (serving/server.py): median time from a request's first token
read back from the device to its SSE event written to the socket (ms): the
``serve.deliver`` spans that end in the window. With ``queue_wait_p50_ms``
and ``admit_to_first_p50_ms`` it splits the time to first token."""

import numpy as np

import spans


def read(ctx):
    recs = spans.window_records(ctx)
    if recs is None:
        return None
    v = [r.end_ns - r.start_ns for r in spans.named(recs, "serve.deliver")]
    return float(np.median(v)) * 1e-6 if v else None
