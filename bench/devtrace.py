"""From a profiler trace to device busy time, kernel time and idle gaps.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. Device planes (``/device:TPU:<n>``) hold one line of operations
(``XLA Ops``); the host plane holds one line per thread, where the
harness's ``TraceAnnotation`` spans (names starting ``bench.``) appear. All
times here are nanoseconds on the trace's own clock.

Run ``python bench/devtrace.py <file.xplane.pb>`` to print the planes,
lines and busiest operation names of one trace.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
# ops that contain other ops (a scanned layer loop): busy, not a kernel
CONTAINERS = ("while", "conditional", "call")


def op_name(text: str) -> str:
    """The instruction's name from an op event's HLO text, without its
    numeric suffix: ``%ternary_matmul_kernel.44 = bf16[...] ...`` ->
    ``ternary_matmul_kernel``."""
    name = text.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", name)


@dataclasses.dataclass
class Trace:
    ops: list  # per device plane: [(name, start_ns, end_ns)]
    spans: list  # host spans: [(name, start_ns, end_ns)]


def find_xplane(logdir: str) -> str:
    files = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, spans = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev += [(op_name(e.name), e.start_ns, e.end_ns)
                            for e in line.events]
            ops.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.end_ns)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return Trace(ops, spans)


def merge(intervals) -> list:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, t0, t1) -> list:
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def busy_ns(ops, t0, t1) -> float:
    """Length of the union of operation intervals inside ``[t0, t1]``."""
    return sum(e - s for s, e in merge(clip([(s, e) for _, s, e in ops],
                                            t0, t1)))


def idle_gaps(ops, t0, t1) -> list:
    """``[(start, end)]`` of the stretches in ``[t0, t1]`` with no operation
    running, longest first."""
    gaps, cur = [], t0
    for s, e in merge(clip([(s, e) for _, s, e in ops], t0, t1)):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        gaps.append((cur, t1))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def op_totals(ops, t0, t1) -> dict:
    """Seconds of device time per operation name inside ``[t0, t1]``,
    leaving out the ops that contain others."""
    tot = {}
    for name, s, e in ops:
        s, e = max(s, t0), min(e, t1)
        if e > s and name not in CONTAINERS:
            tot[name] = tot.get(name, 0.0) + (e - s) * 1e-9
    return tot


def kernel_seconds(ops, t0, t1, patterns: list) -> float:
    """Device seconds of the operations whose name matches any of
    ``patterns`` (regular expressions, matched in full)."""
    rx = [re.compile(p) for p in patterns]
    return sum((min(e, t1) - max(s, t0)) * 1e-9 for name, s, e in ops
               if e > t0 and s < t1 and any(r.fullmatch(name) for r in rx))


def label_gap(spans, start, end) -> str:
    """What the host was doing in a gap: the shortest ``bench.`` span that
    covers the gap's midpoint, else ``host_other``."""
    mid = (start + end) / 2
    cover = [(e - s, n) for n, s, e in spans if s <= mid <= e
             and n != "bench.window"]
    return min(cover)[1] if cover else "host_other"


def main(argv) -> int:
    from jax.profiler import ProfileData

    path = argv[0] if argv[0].endswith(".pb") else find_xplane(argv[0])
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  line", repr(line.name), len(evs))
            if DEVICE_PLANE.match(plane.name) and evs:
                tot = {}
                for e in evs:
                    k = op_name(e.name)
                    tot[k] = tot.get(k, 0.0) + e.duration_ns
                top = sorted(tot.items(), key=lambda kv: -kv[1])[:25]
                print(json.dumps(top, indent=1))
                print("  span ns", evs[0].start_ns, evs[-1].end_ns)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
