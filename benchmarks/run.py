"""Benchmark harness — one section per paper table/figure.

Prints ``name,value,notes`` CSV rows. Roofline tables (from the dry-run JSON)
are rendered by ``python -m benchmarks.roofline``. After all sections the
harness consolidates every ``BENCH_*.json`` in the repo root into
``BENCH_trajectory.json`` — one index row per bench (name, device, headline
metric, acceptance bars) so CI uploads a single artifact that tracks the
whole trajectory instead of a loose pile of files.
"""

from __future__ import annotations

import glob
import json
import os
import sys
import traceback


def _headline(data: dict) -> tuple[str | None, object]:
    """Best-effort single number for the index row: an explicit
    ``headline`` key wins; else the first scalar leaf one level deep."""
    if "headline" in data:
        return "headline", data["headline"]
    for key, val in data.items():
        if key in ("bench", "smoke", "device", "bars", "bars_passed"):
            continue
        if isinstance(val, (int, float)) and not isinstance(val, bool):
            return key, val
        if isinstance(val, dict):
            for k2, v2 in val.items():
                if isinstance(v2, (int, float)) and not isinstance(v2, bool):
                    return f"{key}.{k2}", v2
    return None, None


def write_trajectory(root: str = ".") -> dict:
    """Index every BENCH_*.json under ``root`` into BENCH_trajectory.json."""
    out = os.path.join(root, "BENCH_trajectory.json")
    benches = []
    for path in sorted(glob.glob(os.path.join(root, "BENCH_*.json"))):
        if os.path.abspath(path) == os.path.abspath(out):
            continue
        entry: dict = {"file": os.path.basename(path)}
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            entry["error"] = str(e)
            benches.append(entry)
            continue
        if not isinstance(data, dict):
            data = {}
        entry["bench"] = data.get(
            "bench", os.path.basename(path)[len("BENCH_"):-len(".json")])
        entry["device"] = data.get("device")
        entry["smoke"] = data.get("smoke")
        key, val = _headline(data)
        entry["headline_metric"] = key
        entry["headline_value"] = val
        if "bars" in data:
            entry["bars"] = data["bars"]
            entry["bars_passed"] = data.get(
                "bars_passed", all(data["bars"].values()))
        benches.append(entry)
    payload = {
        "trajectory": benches,
        "total": len(benches),
        "bars_all_passed": all(b.get("bars_passed", True) for b in benches),
    }
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
    return payload


def main() -> int:
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    sections = [
        ("tableI_ternary_matmul", "benchmarks.bench_ternary_matmul"),
        ("tableII_attention_schedule", "benchmarks.bench_attention_schedule"),
        ("fig9_inference", "benchmarks.bench_inference"),
        ("decode_fast_path", "benchmarks.bench_decode"),
        ("prefill_fast_path", "benchmarks.bench_prefill"),
        ("layer_fusion", "benchmarks.bench_layer_fusion"),
        ("kv_cache", "benchmarks.bench_kv_cache"),
        ("paged_kv", "benchmarks.bench_paged_kv"),
        ("speculative_decode", "benchmarks.bench_speculative"),
        ("tableV_compression", "benchmarks.bench_compression"),
        ("tl_engine", "benchmarks.bench_tl_engine"),
        ("serving_resilience", "benchmarks.bench_resilience"),
        ("serving_front_door", "benchmarks.bench_serving"),
        ("replica_pool", "benchmarks.bench_pool"),
    ]
    failures = 0
    print("name,value,notes")
    for title, mod_name in sections:
        print(f"# --- {title} ---")
        try:
            mod = __import__(mod_name, fromlist=["run"])
            for row in mod.run():
                print(row)
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
    traj = write_trajectory()
    print(f"# --- trajectory ---")
    print(f"trajectory_benches,{traj['total']},BENCH_trajectory.json")
    print(f"trajectory_bars_all_passed,{traj['bars_all_passed']},"
          f"every bench with explicit bars passed them")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
