"""Scheduler (serving/engine.py): the host's time between two engine ticks
in which the device has nothing queued (ms), from the program's spans:
over consecutive ``serve.tick``s of one replica inside the traced span, the
mean of tick N+1's ``serve.tick.launch`` start minus tick N's
``serve.tick.wait`` end. The traced span is the one ``tick_ms`` and
``step_device_ms`` read; after it the profiler's stop holds the
interpreter for seconds, which would stretch these gaps."""

import spans


def read(ctx):
    recs = spans.window_records(ctx)
    if recs is None:
        return None
    a, b = ctx.window.trace_host
    recs = [r for r in recs
            if a <= r.start_ns * 1e-9 and r.end_ns * 1e-9 <= b]
    launch, wait = {}, {}  # a retried tick launches twice: take its first
    for r in recs:  # launch and its last wait
        if r.name == "serve.tick.launch":
            launch[r.parent_id] = min(launch.get(r.parent_id, r.start_ns),
                                      r.start_ns)
        elif r.name == "serve.tick.wait":
            wait[r.parent_id] = max(wait.get(r.parent_id, r.end_ns), r.end_ns)
    ticks = {(r.attrs.get("replica"), r.attrs["tick"]): r.span_id
             for r in spans.named(recs, "serve.tick")}
    gaps = [launch[ticks[(rep, n + 1)]] - wait[sid]
            for (rep, n), sid in ticks.items()
            if (rep, n + 1) in ticks and sid in wait
            and ticks[(rep, n + 1)] in launch]
    return sum(gaps) / len(gaps) * 1e-6 if gaps else None
