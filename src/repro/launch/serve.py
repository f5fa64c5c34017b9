"""Canonical batch-serving CLI: packed-ternary continuous batching under the
full resilience envelope.

This is the single home of the one-shot serving launcher (the repo-root
``launch/serve.py`` is a thin wrapper). It converts trained (or randomly
initialized) float params into the 2-bit packed serving form, then serves a
batch of prompts through the continuous-batching engine — chunked prefill in
bucketed fixed-size chunks, decode slots advancing every tick — under the
PR-7 resilience envelope: bounded admission queue, per-request deadlines and
priorities with preemption, numerics quarantine, sticky kernel→XLA fallback.
``step()`` never raises (DESIGN.md §resilience), so the drive loop is the
whole production driver. Reports time-to-first-token and decode throughput
(the paper's Fig. 9 metrics, on CPU at smoke scale) plus every request's
structured terminal status.

For the *streaming* front door (HTTP/SSE, open-loop traffic), see
``repro.launch.server`` (DESIGN.md §serving-frontdoor).

Requests come from ``--requests FILE`` (one JSON object per line:
``{"rid": 0, "prompt": [1, 2, 3], "max_new": 16, "priority": 0}``) or, with
no file, a synthetic batch shaped by --prompt-len/--ragged/--gen/--batch.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch tellme-0.7b --smoke \
      --prompt-len 64 --gen 32 --batch 4 [--speculative] [--queue-cap N] \
      [--deadline-s S] [--json]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import jax
import numpy as np

from ..configs import get_config
from ..core import params as P
from ..models import transformer as Tr
from ..serving import engine as E
from .compile_cache import use_compile_cache


def _load_requests(path, cfg, args):
    if path is None:
        lens = [args.prompt_len] * args.batch
        if args.ragged:
            lens = [max(8, args.prompt_len // (1 << (i % 3)))
                    for i in range(args.batch)]
        return [
            E.Request(rid=i,
                      prompt=jax.random.randint(jax.random.PRNGKey(i + 1),
                                                (l,), 0, cfg.vocab_size),
                      max_new=args.gen,
                      deadline_s=args.deadline_s or None)
            for i, l in enumerate(lens)
        ]
    reqs = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            d = json.loads(line)
            reqs.append(E.Request(
                rid=int(d["rid"]), prompt=np.asarray(d["prompt"], np.int64),
                max_new=int(d.get("max_new", 16)),
                priority=int(d.get("priority", 0)),
                deadline_s=d.get("deadline_s", args.deadline_s or None)))
    return reqs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="tellme-0.7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--ragged", action="store_true",
                    help="vary prompt lengths across the batch")
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--slots", type=int, default=0,
                    help="decode slots (default: batch)")
    ap.add_argument("--max-len", type=int, default=0,
                    help="cache length (default: prompt+gen rounded up)")
    ap.add_argument("--mode", default="packed", choices=["packed", "eval", "wq"])
    ap.add_argument("--kv-cache-dtype", default="bf16", choices=["bf16", "int8"],
                    help="int8 = absmax-quantized KV cache with per-row f32 "
                         "scales, dequantized inside the attention kernels "
                         "(DESIGN.md §kv-cache); halves cache HBM bytes")
    ap.add_argument("--prefill", default="auto",
                    choices=["auto", "chunked", "legacy"],
                    help="chunked = fused cache-resident prefill; legacy = "
                         "per-request bucketed prefill + scatter")
    ap.add_argument("--speculative", action="store_true",
                    help="speculative decoding (DESIGN.md §speculative): "
                         "prompt-lookup drafting + chunk-verify; greedy "
                         "output bit-identical to plain decode")
    ap.add_argument("--spec-gamma", type=int, default=0,
                    help="draft tokens verified per tick (default: "
                         "cfg.spec_gamma)")
    ap.add_argument("--queue-cap", type=int, default=0,
                    help="bound the admission queue (0 = unbounded); full "
                         "queue rejects the submit with FAILED/queue_full")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="default per-request TTL (0 = none); expired "
                         "requests retire as DEADLINE_EXCEEDED")
    ap.add_argument("--requests", default=None, metavar="FILE",
                    help="JSONL request file (default: synthetic batch)")
    ap.add_argument("--json", action="store_true",
                    help="emit a machine-readable result object instead of "
                         "the human summary")
    ap.add_argument("--ckpt")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = dataclasses.replace(get_config(args.arch, smoke=args.smoke),
                              kv_cache_dtype=args.kv_cache_dtype)
    specs = Tr.param_specs(cfg)
    params = P.init_params(specs, jax.random.PRNGKey(0))
    if args.ckpt:
        from ..checkpoint import CheckpointManager

        ckpt = CheckpointManager(args.ckpt)
        trees, _ = ckpt.restore(ckpt.latest_step())
        params = trees["params"]
    serve_params = (Tr.pack_tree(params, specs)
                    if args.mode == "packed" else params)
    if args.mode == "packed" and not args.json:
        fb = P.param_bytes(specs)
        pb = sum(x.size * x.dtype.itemsize
                 for x in jax.tree.leaves(serve_params))
        print(f"[serve] packed weights: {pb/2**20:.1f} MiB "
              f"(float master {fb/2**20:.1f} MiB, {fb/pb:.1f}x compression)")

    reqs = _load_requests(args.requests, cfg, args)
    lens = [len(r.prompt) for r in reqs]
    max_len = args.max_len or max(lens) + max(r.max_new for r in reqs) + 1
    eng = E.ServingEngine(
        serve_params, cfg, slots=args.slots or len(reqs), max_len=max_len,
        mode=args.mode, prefill=args.prefill, speculative=args.speculative,
        spec_gamma=args.spec_gamma or None,
        queue_cap=args.queue_cap or None,
    )
    admitted = [eng.submit(r) for r in reqs]

    if not args.json:
        got, ref16 = E.cache_savings(eng)
        print(f"[serve] kv_cache_dtype={cfg.kv_cache_dtype}: cache resident "
              f"{got/2**20:.2f} MiB (bf16 layout {ref16/2**20:.2f} MiB, "
              f"{ref16/got:.2f}x)")
        if args.speculative and not eng.speculative:
            print(f"[serve] speculative requested but family={cfg.family!r} "
                  f"prefill={eng.prefill!r} stays on plain decode "
                  f"(DESIGN.md §speculative)")

    t0 = time.time()
    first_tok_at = {}
    ticks = 0
    while eng.queue or any(s is not None for s in eng.live):
        eng.step()
        ticks += 1
        for r in reqs:
            if r.generated and r.rid not in first_tok_at:
                first_tok_at[r.rid] = time.time() - t0
    dt = time.time() - t0
    stats = eng.stats()
    total = sum(len(r.generated) for r in reqs)
    ttft = sorted(first_tok_at.values())

    if args.json:
        json.dump({
            "requests": [{
                "rid": r.rid, "status": r.status.name,
                "detail": r.status_detail, "tokens": list(r.generated),
                "preemptions": r.preemptions,
            } for r in reqs],
            "admitted": sum(admitted), "rejected": len(reqs) - sum(admitted),
            "tokens": total, "ticks": stats["ticks"], "seconds": round(dt, 3),
            "ttft_ms": [round(t * 1e3, 2) for t in ttft],
            "statuses": stats["statuses"], "events": stats["events"],
            "attn_impl": stats["attn_impl"],
            "xla_fallback": stats["xla_fallback"],
        }, sys.stdout, indent=2)
        print()
    else:
        print(f"[serve] prefill={eng.prefill} lens={lens}: served "
              f"{sum(admitted)}/{len(reqs)} admitted, {total} tokens in "
              f"{ticks} ticks / {dt*1e3:.1f} ms (incl. compile)")
        if ttft:
            print(f"[serve] time-to-first-token ms: "
                  f"min={ttft[0]*1e3:.1f} max={ttft[-1]*1e3:.1f}")
        print(f"[serve] decode throughput: {total/max(dt, 1e-9):.1f} tok/s "
              f"({eng.compiled_prefill_shapes} compiled tick shapes)")
        if eng.speculative:
            rates = " ".join(f"r{r.rid}={r.spec_acceptance:.2f}" for r in reqs)
            print(f"[serve] speculative γ={eng.spec_gamma}: acceptance "
                  f"{eng.spec_acceptance_rate:.2f} overall ({rates})")
        for r in reqs:
            note = f" ({r.status_detail})" if r.status_detail else ""
            pre = f" preempted×{r.preemptions}" if r.preemptions else ""
            print(f"  req {r.rid}: prompt={len(r.prompt)} "
                  f"[{r.status.name}{note}]{pre} -> {len(r.generated)} tokens")
        print(f"[serve] statuses: {stats['statuses']} | "
              f"preemptions={stats['preemptions']} "
              f"quarantined={stats['quarantined']} "
              f"stragglers={stats['straggler']['straggler_events']} "
              f"attn_impl={stats['attn_impl']}"
              f"{' (xla fallback)' if stats['xla_fallback'] else ''}")
    # operator exit code: 0 only if every admitted request ended OK
    bad = [r for r, a in zip(reqs, admitted)
           if a and r.status.name not in ("OK",)]
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
