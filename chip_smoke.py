#!/usr/bin/env python3
"""Chip smoke: the packed-ternary serving path, end to end, on a TPU.

Default phase (one chip):

* builds ``tellme-0.7b`` at its published widths from ``--seed`` (random
  init, then ``pack_tree``) and serves it through ``ServingServer`` exactly
  as ``repro.launch.server`` builds it, on ``127.0.0.1``;
* streams four concurrent ``POST /v1/generate`` requests with ragged
  prompts (64, 128, 300 and 512 tokens), ``max_new`` 32, and drains;
* fails unless every stream ends ``done``/``OK``, no ``xla_fallback`` or
  ``tick_failure`` event fired, attention ran on the Pallas kernels with the
  KV cache written in place (``kv_write``), and the compiled decode and
  prefill ticks hold a ``tpu_custom_call``;
* checks the served path against an independent one on the same chip:
  teacher-forced logits of the same token streams through the packed
  serving steps (``prefill_chunk_step`` / ``decode_step`` on the Pallas
  kernels) against ``Tr.forward(mode="eval")``, which calls no kernel.

``--chips 4`` runs only the replica-pool phase: the same requests through
a one-replica server and then through a four-replica ``ReplicaPool`` with
replica ``i`` on ``jax.devices()[i]``; the streams must be equal and each
device must hold exactly its replica's params and caches.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``;
anything else goes on earlier lines. Without a TPU the script exits 2 and
prints no result. Byte counts and times are printed for information only:
this is a smoke run, not a benchmark.

Run:  python chip_smoke.py [--seed S] [--chips 4]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

PROMPT_LENS = (64, 128, 300, 512)  # 300 is off the 64/128/256 chunk grid
MAX_NEW = 32
SLOTS = 4
MAX_LEN = 1024
# Served (packed, Pallas) logits against the eval path's, over every
# teacher-forced position: rms(diff) / rms(ref) and max|diff| / max|ref|.
# Both paths compute the same int8 x ternary arithmetic; they differ in
# attention blocking and bf16 rounding, which can flip single int8 codes.
TOL_REL_RMS = 0.05
TOL_REL_MAX = 0.2
PHASE_TIMEOUT_S = 900.0


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def server_args(seed: int, *, replicas: int = 1, smoke: bool = False):
    from repro.launch import server as launcher

    argv = ["--arch", "tellme-0.7b", "--seed", str(seed),
            "--slots", str(SLOTS), "--max-len", str(MAX_LEN),
            "--replicas", str(replicas)]
    return launcher.parse_args(argv + (["--smoke"] if smoke else []))


def make_prompts(seed: int, vocab: int) -> list[list[int]]:
    import numpy as np

    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, vocab, size=n)]
            for n in PROMPT_LENS]


def tree_bytes(tree) -> int:
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


async def _get_status(host: str, port: int, path: str) -> int:
    reader, writer = await asyncio.open_connection(host, port)
    writer.write(f"GET {path} HTTP/1.1\r\nhost: {host}\r\n\r\n".encode())
    await writer.drain()
    line = await reader.readline()
    writer.close()
    return int(line.split()[1])


async def serve_once(backend, prompts, errors: list) -> tuple[list, float, float]:
    """Boot a ServingServer over ``backend``, wait for /readyz, stream every
    prompt concurrently, drain. Returns (results, warmup_s, streams_s)."""
    from examples.stream_client import sse_generate
    from repro.serving.server import ServingServer

    server = ServingServer(backend, host="127.0.0.1", port=0)
    t0 = time.perf_counter()
    await server.start()
    while await _get_status(server.host, server.port, "/readyz") != 200:
        await asyncio.sleep(0.1)
    # a pool turns ready with its first replica; send once all have warmed
    while any(r.state != "ready" for r in getattr(backend, "replicas", ())):
        await asyncio.sleep(0.1)
    warmup_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    results = await asyncio.gather(*[
        sse_generate(server.host, server.port,
                     {"prompt": p, "max_new": MAX_NEW}, quiet=True)
        for p in prompts])
    streams_s = time.perf_counter() - t1
    server.begin_drain()
    await server.serve_until_drained()
    for i, r in enumerate(results):
        term = r["terminal"] or {}
        log(f"request {i}: prompt={len(prompts[i])} http={r['status']} "
            f"status={term.get('status')} tokens={len(r['tokens'])}")
        if r["status"] != 200 or term.get("status") != "OK" \
                or len(r["tokens"]) != MAX_NEW:
            errors.append(f"request {i} did not end OK with {MAX_NEW} "
                          f"tokens: http={r['status']} terminal={term}")
    return results, warmup_s, streams_s


def check_engine(eng, errors: list, name: str) -> None:
    """No hidden fallback: the kernels ran, nothing failed over to XLA."""
    st = eng.stats()
    for e in st["events"]:
        if e["kind"] in ("xla_fallback", "tick_failure"):
            log(f"{name}: event {e}")
            errors.append(f"{name}: {e['kind']} event: "
                          f"{e.get('error') or e.get('detail')}")
    if st["attn_impl"] != "kernel":
        errors.append(f"{name}: attn_impl={st['attn_impl']!r}, not 'kernel'")
    if st["kv_write"] != "in_place":
        errors.append(f"{name}: kv_write={st['kv_write']!r}, not 'in_place'")
    bad = {k: v for k, v in st["statuses"].items()
           if k in ("FAILED", "QUARANTINED") and v}
    if bad:
        errors.append(f"{name}: statuses {bad}")
    log(f"{name}: statuses={st['statuses']} attn_impl={st['attn_impl']} "
        f"kv_write={st['kv_write']} xla_fallback={st['xla_fallback']} "
        f"ticks={st['ticks']}")


def count_custom_calls(eng) -> tuple[int, int]:
    """``tpu_custom_call`` count in the compiled decode tick and in the
    compiled 64-row prefill tick, lowered with the engine's own arguments."""
    import jax.numpy as jnp

    decode = eng._serve.lower(eng.params, {"tokens": eng.cur_tok[:, None]},
                              eng.caches, eng.pos, None).compile().as_text()
    s, c = eng.slots, eng.chunk_sizes[0]
    fused = eng._get_fused(c).lower(
        eng.params, eng.caches, eng.cur_tok, eng.pos, eng.done,
        eng.gen_count, eng.max_new_arr, jnp.zeros((s,), bool),
        jnp.zeros((s, c), jnp.int32), jnp.zeros((s,), jnp.int32),
        jnp.zeros((s,), bool), jnp.zeros((s,), jnp.int32),
        jnp.zeros((s,), jnp.int32), None).compile().as_text()
    return decode.count("tpu_custom_call"), fused.count("tpu_custom_call")


def reference_check(params, packed, cfg, prompts, streams, errors) -> None:
    """Teacher-forced logits of each prompt + its streamed tokens: the packed
    serving steps on the Pallas kernels against the eval path."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer as Tr
    from repro.serving import engine as E

    chunk = 64
    total = max(PROMPT_LENS) + MAX_NEW  # teacher-forced length per row
    prefix = (max(PROMPT_LENS) // chunk) * chunk  # chunked part, rest decoded
    rng = np.random.default_rng(1)
    toks = rng.integers(1, cfg.vocab_size, size=(len(prompts), total))
    for i, (p, g) in enumerate(zip(prompts, streams)):
        seq = p + g[:-1]
        toks[i, :len(seq)] = seq
    toks = jnp.asarray(toks, jnp.int32)

    t0 = time.perf_counter()
    ref = jax.jit(lambda p, t: Tr.forward(p, {"tokens": t}, cfg,
                                          mode="eval")[0])(params, toks)
    ref = np.asarray(jax.device_get(ref), np.float32)
    t_ref = time.perf_counter() - t0

    chunk_step = jax.jit(lambda p, t, c, o: Tr.prefill_chunk_step(
        p, {"tokens": t}, c, o, cfg, mode="packed", attn_impl="kernel"),
        donate_argnums=2)
    decode_step = jax.jit(lambda p, t, c, pos: Tr.decode_step(
        p, {"tokens": t}, c, pos, cfg, mode="packed", attn_impl="kernel"),
        donate_argnums=2)
    t0 = time.perf_counter()
    b = len(prompts)
    caches = E.init_caches(cfg, b, -(-total // chunk) * chunk, dtype=cfg.dtype)
    got = []
    for off in range(0, prefix, chunk):
        lg, caches = chunk_step(packed, toks[:, off:off + chunk], caches,
                                jnp.full((b,), off, jnp.int32))
        got.append(lg)
    for pos in range(prefix, total):
        lg, caches = decode_step(packed, toks[:, pos:pos + 1], caches,
                                 jnp.full((b,), pos, jnp.int32))
        got.append(lg[:, None])
    got = np.asarray(jax.device_get(jnp.concatenate(got, axis=1)), np.float32)
    t_got = time.perf_counter() - t0

    diff = got - ref
    rel_rms = float(np.sqrt(np.mean(diff ** 2)) / np.sqrt(np.mean(ref ** 2)))
    rel_max = float(np.abs(diff).max() / np.abs(ref).max())
    finite = bool(np.isfinite(got).all())
    agree_eval, agree_kern, n = 0, 0, 0
    for i, g in enumerate(streams):
        lo = len(prompts[i]) - 1
        agree_eval += int((ref[i, lo:lo + MAX_NEW].argmax(-1) == g).sum())
        agree_kern += int((got[i, lo:lo + MAX_NEW].argmax(-1) == g).sum())
        n += MAX_NEW
    log(f"reference: eval forward {t_ref:.1f}s, packed teacher-forced "
        f"{t_got:.1f}s (compile included), logits {got.shape}")
    log(f"reference: max|diff|={np.abs(diff).max():.6g} "
        f"max|ref|={np.abs(ref).max():.6g} rel_max={rel_max:.6g} "
        f"(tol {TOL_REL_MAX}) rel_rms={rel_rms:.6g} (tol {TOL_REL_RMS})")
    log(f"reference: greedy agreement of streamed tokens with eval path "
        f"{agree_eval}/{n}, with packed teacher-forced path {agree_kern}/{n}")
    if not finite:
        errors.append("packed teacher-forced logits are not finite")
    if not (rel_rms <= TOL_REL_RMS and rel_max <= TOL_REL_MAX):
        errors.append(f"logits off the eval path: rel_rms={rel_rms:.4g} "
                      f"rel_max={rel_max:.4g}")


def build_params(cfg, seed: int):
    import jax

    from repro.core import params as P
    from repro.models import transformer as Tr

    specs = Tr.param_specs(cfg)
    params = P.init_params(specs, jax.random.PRNGKey(seed))
    packed = Tr.pack_tree(params, specs)
    jax.block_until_ready(packed)
    return params, packed


def phase_one_chip(seed: int, errors: list, *, smoke: bool = False) -> None:
    import jax

    from repro.launch import server as launcher

    args = server_args(seed, smoke=smoke)
    cfg = launcher.build_config(args)
    t0 = time.perf_counter()
    params, packed = build_params(cfg, seed)
    log(f"{cfg.name}: d={cfg.d_model} layers={cfg.n_layers} "
        f"heads={cfg.n_heads}x{cfg.head_dim} ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size}; init+pack {time.perf_counter() - t0:.1f}s")
    eng = launcher.build_backend(args, params=packed)
    log(f"resident on device: packed weights {tree_bytes(eng.params)} B, "
        f"KV cache {tree_bytes(eng.caches)} B ({eng.slots} slots x "
        f"{eng.cache_len} rows, {cfg.kv_cache_dtype})")
    prompts = make_prompts(seed, cfg.vocab_size)
    results, warmup_s, streams_s = asyncio.run(asyncio.wait_for(
        serve_once(eng, prompts, errors), PHASE_TIMEOUT_S))
    log(f"warmup (compile) {warmup_s:.2f}s; {len(prompts)} streams "
        f"{streams_s:.2f}s wall (smoke run, not a benchmark)")
    check_engine(eng, errors, "engine")
    n_dec, n_pre = count_custom_calls(eng)
    log(f"tpu_custom_call: decode tick {n_dec}, prefill tick {n_pre}")
    if n_dec < 1 or n_pre < 1:
        errors.append(f"compiled ticks hold no Pallas kernel "
                      f"(decode {n_dec}, prefill {n_pre})")
    streams = [r["tokens"] for r in results]
    if not errors:
        reference_check(params, packed, cfg, prompts, streams, errors)
    mem = jax.devices()[0].memory_stats() or {}
    log(f"device memory: in use {mem.get('bytes_in_use')} B, "
        f"peak {mem.get('peak_bytes_in_use')} B")


def phase_four_chips(seed: int, errors: list, *, smoke: bool = False) -> None:
    import jax

    from repro.launch import server as launcher

    devices = jax.devices()
    if len(devices) < 4:
        errors.append(f"--chips 4 needs four devices, found {len(devices)}")
        return
    args = server_args(seed, smoke=smoke)
    cfg = launcher.build_config(args)
    params, packed = build_params(cfg, seed)
    del params
    prompts = make_prompts(seed, cfg.vocab_size)

    one = launcher.build_backend(args, params=packed)
    ref, _, ref_s = asyncio.run(asyncio.wait_for(
        serve_once(one, prompts, errors), PHASE_TIMEOUT_S))
    check_engine(one, errors, "one replica")
    log(f"one replica: {len(prompts)} streams {ref_s:.2f}s wall")
    one_bytes = tree_bytes(one.params) + tree_bytes(one.caches)
    del one

    pool = launcher.build_backend(server_args(seed, replicas=4, smoke=smoke),
                                  params=packed)
    got, _, pool_s = asyncio.run(asyncio.wait_for(
        serve_once(pool, prompts, errors), PHASE_TIMEOUT_S))
    log(f"four replicas: {len(prompts)} streams {pool_s:.2f}s wall; "
        f"migrated={pool.migrated_total}")
    if pool.migrated_total:
        errors.append(f"the pool migrated {pool.migrated_total} requests: a "
                      f"replica stalled past pool_hang_timeout_s")
    for i, (a, b) in enumerate(zip(ref, got)):
        if a["tokens"] != b["tokens"]:
            errors.append(f"request {i}: pool stream differs from the "
                          f"one-replica stream")
    log(f"streams equal to the one-replica run: "
        f"{sum(a['tokens'] == b['tokens'] for a, b in zip(ref, got))}"
        f"/{len(prompts)}")
    for rep in pool.replicas:
        eng, dev = rep.engine, devices[rep.idx]
        check_engine(eng, errors, f"replica {rep.idx}")
        leaves = jax.tree.leaves((eng.params, eng.caches))
        placed = {d for x in leaves for d in x.devices()}
        nbytes = tree_bytes(eng.params) + tree_bytes(eng.caches)
        mem = dev.memory_stats() or {}
        log(f"replica {rep.idx}: devices {sorted(d.id for d in placed)} "
            f"params+KV {nbytes} B; {dev} in use "
            f"{mem.get('bytes_in_use')} B")
        if placed != {dev}:
            errors.append(f"replica {rep.idx} is not on {dev} alone: "
                          f"{placed}")
        if nbytes != one_bytes:
            errors.append(f"replica {rep.idx} holds {nbytes} B, one replica "
                          f"holds {one_bytes} B")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the four-replica pool phase")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax

    if jax.default_backend() != "tpu":
        log(f"no TPU: JAX backend is {jax.default_backend()!r}")
        return 2
    from repro.kernels import autotune
    from repro.launch.compile_cache import use_compile_cache

    log(f"compile cache: {use_compile_cache()}")
    # Block sizes only from tables git commits, never from ~/.cache.
    autotune.set_cache_path(os.path.join(
        ROOT, "autotune", f"{autotune.device_key()}.json"))
    dev = jax.devices()[0]
    log(f"device: {dev.device_kind} x{len(jax.devices())}, jax {jax.__version__}")

    errors: list[str] = []
    phase = phase_four_chips if args.chips == 4 else phase_one_chip
    try:
        phase(args.seed, errors)
    except Exception as exc:  # noqa: BLE001 — reported, then exit nonzero
        import traceback

        traceback.print_exc()
        errors.append(f"{type(exc).__name__}: {exc}")
    log(f"wall {time.perf_counter() - t_start:.1f}s")
    for e in errors:
        log(f"FAIL: {e}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(json.dumps({"ok": not errors, "device": device}), flush=True)
    return 1 if errors else 0


if __name__ == "__main__":
    raise SystemExit(main())
