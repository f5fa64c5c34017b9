"""The program's own spans inside the window, for the metric readers.

The serving path records its spans into one process-wide ring
(``repro.serving.tracing``), timed with ``time.perf_counter_ns()``: the
clock of the window's ends. ``window_records`` gives the records that
ended inside the window, or ``None`` where they cannot be read in full:
the program has no tracer (a tree from before it), or the ring dropped a
record that ended after the window opened.
"""

from __future__ import annotations


def window_records(ctx) -> list | None:
    try:
        from repro.serving import tracing
    except ImportError:
        return None
    rec = tracing.recorder()
    if rec.lost_since(int(ctx.window.t_open * 1e9)):
        return None
    return [r for r in rec.records() if ctx.in_window(r.end_ns * 1e-9)]


def named(records: list, name: str) -> list:
    return [r for r in records if r.name == name]
