"""HTTP/SSE server launcher: the streaming front door as a process.

Builds the packed-ternary engine, wraps it in ``serving.server.ServingServer``
(DESIGN.md §serving-frontdoor), installs SIGTERM/SIGINT → graceful drain, and
serves until drained. Exit code 0 after a clean drain — in-flight streams
finish or deadline-out, ``/readyz`` flips to 503 the instant the signal
lands, lingering sockets are aborted at the hard-kill timeout.

Endpoints: POST /v1/generate (SSE token stream), GET /healthz, GET /readyz,
GET /v1/stats.

Run:  PYTHONPATH=src python -m repro.launch.server --smoke --port 8080
Try:  curl -N localhost:8080/v1/generate -d '{"prompt": [1,2,3], "max_new": 8}'
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import signal

import jax

from ..configs import get_config
from ..core import params as P
from ..models import transformer as Tr
from ..serving import engine as E
from ..serving.pool import ReplicaPool
from ..serving.server import ServingServer
from .compile_cache import use_compile_cache


def build_config(args):
    return dataclasses.replace(get_config(args.arch, smoke=args.smoke),
                               kv_cache_dtype=args.kv_cache_dtype)


def build_backend(args, params=None):
    """One bare engine, or — with ``--replicas N > 1`` — a ReplicaPool of N
    engines built from one packed params pytree (byte-identical migration
    needs identical weights). Replica ``i`` lives on ``jax.devices()[i]``
    (round-robin past the device count): on a four-chip host, four replicas
    hold one chip each. ``params`` (packed) defaults to a random init from
    ``--seed``."""
    cfg = build_config(args)
    if params is None:
        specs = Tr.param_specs(cfg)
        params = Tr.pack_tree(
            P.init_params(specs, jax.random.PRNGKey(args.seed)), specs)
    if args.replicas <= 1:
        return E.ServingEngine(params, cfg, slots=args.slots,
                               max_len=args.max_len, mode="packed",
                               speculative=args.speculative,
                               queue_cap=args.queue_cap or None)
    devices = jax.devices()

    def factory(idx):
        return E.ServingEngine(params, cfg, slots=args.slots,
                               max_len=args.max_len, mode="packed",
                               speculative=args.speculative,
                               replica_id=idx,
                               device=devices[idx % len(devices)])

    return ReplicaPool(factory, cfg, replicas=args.replicas,
                       queue_cap=args.queue_cap)  # 0 = unbounded, as engine


async def amain(args) -> int:
    server = ServingServer(build_backend(args), host=args.host,
                           port=args.port,
                           drain_timeout_s=args.drain_timeout_s or None)
    await server.start()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        loop.add_signal_handler(sig, server.begin_drain)
    print(f"[server] listening on http://{server.host}:{server.port} "
          f"(replicas={args.replicas} slots={args.slots} "
          f"queue_cap={args.queue_cap or 'unbounded'}); "
          f"SIGTERM drains", flush=True)
    await server.serve_until_drained()
    print("[server] drained, exiting 0", flush=True)
    return 0


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="tellme-0.7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--seed", type=int, default=0,
                    help="random weight init seed")
    ap.add_argument("--host", default=None,
                    help="bind host (default: cfg.server_host)")
    ap.add_argument("--port", type=int, default=None,
                    help="bind port, 0 = ephemeral (default: cfg.server_port)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--kv-cache-dtype", default="bf16",
                    choices=["bf16", "int8"])
    ap.add_argument("--speculative", action="store_true")
    ap.add_argument("--queue-cap", type=int, default=32,
                    help="bounded admission queue; full → HTTP 429 "
                         "(0 = unbounded)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="N > 1 serves a ReplicaPool: SLO-class admission, "
                         "health-gated routing, crash failover "
                         "(DESIGN.md §replica-pool)")
    ap.add_argument("--drain-timeout-s", type=float, default=0.0,
                    help="graceful-drain hard-kill timeout "
                         "(default: cfg.server_drain_timeout_s)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    use_compile_cache()
    return asyncio.run(amain(args))


if __name__ == "__main__":
    raise SystemExit(main())
