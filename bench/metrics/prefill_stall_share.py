"""Scheduler (serving/engine.py): share of the slot-ticks spent mid-prefill
in which the slot's prompt chunk did not run (%), over the ``serve.tick``s
in the window: 100 x (1 - sum of ``selected`` / sum of ``prefilling``)."""

import spans


def read(ctx):
    recs = spans.window_records(ctx)
    if recs is None:
        return None
    ticks = spans.named(recs, "serve.tick")
    pre = sum(r.attrs.get("prefilling", 0) for r in ticks)
    sel = sum(r.attrs.get("selected", 0) for r in ticks)
    return 100.0 * (1.0 - sel / pre) if pre else None
