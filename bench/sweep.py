#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains: several rates after one
set-up, in one process.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 2,4,8

At each rate the cell's mix runs open-loop for its ramp plus ``--seconds``.
One JSON line per rate: offered and completed requests per second, TTFT
percentiles over the window and over each half of it, and the requests
still unfinished when the window closed. A growing backlog shows as a
second half slower than the first and as requests left over. Run once, by
hand, to fix a cell's rate; the benchmark's runs never search for one.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]


async def sweep(eng, cell, seed, seconds, rates):
    import client as C
    import traffic as T
    from repro.serving.server import ServingServer

    server = ServingServer(eng, host="127.0.0.1", port=0)
    await server.start()
    while not server.ready:
        await asyncio.sleep(0.02)
    ramp = float(cell.mix.get("ramp_s", 0.0))
    for i, rate in enumerate(rates):
        reqs = T.open_plan(cell.mix, rate=rate, seconds=seconds,
                           seed=seed + i, vocab=cell.config["vocab_size"],
                           max_len=cell.spec["max_len"])
        load = C.Load(server.host, server.port)
        t0 = time.perf_counter()
        load.open(reqs, t0)
        a, b = t0 + ramp, t0 + ramp + seconds
        await asyncio.sleep(b - time.perf_counter())
        left = sum(1 for s in load.started if s.status is None)
        await load.close()
        first = [(s.times[0], s.times[0] - s.t_sched) for s in load.streams
                 if s.times and a <= s.times[0] < b]
        ttft = np.array([t for _, t in first]) * 1e3
        half = [np.array([t for x, t in first if (x < (a + b) / 2) == h])
                * 1e3 for h in (True, False)]
        done = sum(1 for s in load.streams if s.ok and a <= s.times[-1] < b)
        pct = lambda v, q: float(np.percentile(v, q)) if len(v) else None
        print(json.dumps({
            "rate": rate, "offered_per_s": len(reqs) / (ramp + seconds),
            "completed_per_s": done / seconds, "ttft_p50_ms": pct(ttft, 50),
            "ttft_p95_ms": pct(ttft, 95),
            "ttft_p50_ms_halves": [pct(h, 50) for h in half],
            "unfinished_at_close": left,
            "lateness_ms_max": 1e3 * max(load.lateness, default=0.0)}),
            flush=True)
        await asyncio.sleep(2.0)
    server.begin_drain()
    await server.serve_until_drained()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import jax

    import harness

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    cell = harness.find_cell(args.workload)
    eng, _, _ = harness.prepare(cell, args.seed)
    asyncio.run(sweep(eng, cell, args.seed, args.seconds,
                      [float(r) for r in args.rates.split(",")]))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
