#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; see ``bench/harness.py`` for
how its files are found. The last line of standard output is one JSON
object with ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``:
each number the correctness check compared, beside its limit. The checks
are also the last lines of standard error. A run that finds no TPU, or
fewer chips than the cell asks for, exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    import harness

    try:
        cell = harness.find_cell(args.workload)
    except (harness.SetupError, OSError, ValueError) as exc:
        log(f"cannot find the cell: {exc}")
        return 2
    import jax

    try:
        devs = jax.devices()
        t_devices = time.perf_counter()
    except RuntimeError as exc:
        log(f"no accelerator: {exc}")
        return 2
    if devs[0].platform != "tpu" or len(devs) < cell.entry["chips"]:
        log(f"needs {cell.entry['chips']} TPU chip(s); JAX has "
            f"{len(devs)} {devs[0].platform} device(s)")
        return 2
    try:
        res = harness.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START, log=log,
                               t_devices=t_devices)
    except harness.SetupError as exc:
        log(f"FAILED: {exc}")
        return 1
    info = res.pop("info")
    print(json.dumps({"info": info}), flush=True)
    for name, c in res["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
