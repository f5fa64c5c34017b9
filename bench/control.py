#!/usr/bin/env python3
"""Readings for the limits of ``correct``: many seeds in one process.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3 \
        [--kv int8] [--variants ref,act_int4]

Each seed is a whole run of the cell (weights, server, load, window) at
its own size and load, judged by the harness's own predicate
(``harness.make_checks`` and ``harness.judge``), as the benchmark's runs
are. ``program`` is the run as served: with the configuration's KV cache
it is the sound reading; with ``--kv int8`` the program's own int8-KV path
is switched on, the control one step below the configuration's bfloat16
cache. Each reference variant other than ``ref`` (``act_int4``, see
``reference.py``) is the reference put in the program's place in a lower
precision: the gap of the token it puts first stands where the served
token's would. One JSON line per seed on standard output. The benchmark's
own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]


def verdicts(cell, res: dict) -> dict:
    """The harness's verdict on the served run and on each reference
    variant put in its place."""
    import harness

    ref = res["info"]["reference"]
    want = harness.KV_BITS[cell.config["kv_cache_dtype"]]
    out = {"program": {"correct": res["correct"], "checks": res["checks"]}}
    for name, reading in ref.items():
        if name == "ref":
            continue
        checks = harness.make_checks(cell.spec["check"], reading,
                                     reading["kv_bits"], want)
        out[name] = {"correct": harness.judge(checks), "checks": checks}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--kv", default=None, help="serve this KV-cache dtype")
    ap.add_argument("--variants", default="ref,act_int4")
    args = ap.parse_args(argv)
    import jax

    import harness

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    cell = harness.find_cell(args.workload)
    variants = tuple(args.variants.split(","))
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        res = harness.run_cell(cell, seed, args.seconds, False, t_start=t0,
                               variants=variants, kv=args.kv)
        info = res["info"]
        print(json.dumps({"seed": seed, "kv": args.kv,
                          "verdicts": verdicts(cell, res),
                          "reference": info["reference"],
                          "reference_s": info["reference_s"],
                          "setup_parts": info["setup_parts"],
                          "metrics": {k: v["value"] for k, v in
                                      res["metrics"].items()},
                          "failed": res["failed"],
                          "attempted": res["attempted"],
                          "memory_peak_bytes":
                              res["device"]["memory_peak_bytes"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
