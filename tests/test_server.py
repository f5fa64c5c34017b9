"""Socket-level tests for the HTTP/SSE front door (DESIGN.md §serving-frontdoor).

Everything here talks to a real ``ServingServer`` over real loopback sockets
(the SSE client is the bench's): token streams terminate with the mapped
terminal event, bounded admission answers 429 + Retry-After, `/readyz`
tracks warmup and drain, graceful drain finishes in-flight streams with no
stuck connections, and a client disconnect cancels its request while
co-batched streams stay bit-identical.
"""

import asyncio
import dataclasses
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.bench_serving import _sse_request
from repro.configs import get_config
from repro.core import params as P
from repro.models import transformer as T
from repro.serving import engine as E
from repro.serving.server import SSE_EVENT_FOR_STATUS, ServingServer


def _cfg(**kw):
    cfg = get_config("tellme-0.7b", smoke=True)
    return dataclasses.replace(cfg, dtype=jnp.float32, **kw)


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    params = P.init_params(T.param_specs(cfg), jax.random.PRNGKey(0))
    return cfg, params


def _engine(params, cfg, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 192)
    return E.ServingEngine(params, cfg, mode="eval", eos_id=-2, **kw)


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(1, 200, size=n)]


async def _boot(params, cfg, **kw):
    warmup = kw.pop("warmup", True)
    server = ServingServer(_engine(params, cfg, **kw), host="127.0.0.1",
                           port=0, warmup=warmup)
    await server.start()
    while warmup is True and not server.ready:
        await asyncio.sleep(0.02)
    return server


async def _get(host, port, path):
    reader, writer = await asyncio.open_connection(host, port)
    writer.write(f"GET {path} HTTP/1.1\r\nhost: {host}\r\n\r\n".encode())
    await writer.drain()
    raw = await reader.read()
    writer.close()
    head, _, body = raw.partition(b"\r\n\r\n")
    return int(head.split()[1]), body


def test_stream_completes_with_mapped_terminal(setup):
    cfg, params = setup

    async def body():
        server = await _boot(params, cfg)
        try:
            rec = await _sse_request(server.host, server.port,
                                     {"prompt": _prompt(24), "max_new": 6})
            assert rec["http"] == 200
            assert rec["events"][0] == "start"
            assert rec["status"] == "OK"
            assert rec["events"][-1] == SSE_EVENT_FOR_STATUS["OK"] == "done"
            assert len(rec["tokens"]) == 6
            return rec["tokens"]
        finally:
            await server.drain_and_stop(5.0)

    streamed = asyncio.run(body())
    # bit-identity through the pipe: SSE tokens == a direct engine run
    eng = _engine(params, cfg)
    req = E.Request(rid=0, prompt=np.asarray(_prompt(24), np.int64), max_new=6)
    eng.submit(req)
    eng.run()
    assert streamed == [int(t) for t in req.generated]


def test_deadline_and_priority_map_to_lifecycle(setup):
    cfg, params = setup

    async def body():
        server = await _boot(params, cfg, slots=1)
        try:
            # the only slot is busy, so the tiny deadline expires in the
            # admission queue: DEADLINE_EXCEEDED with zero tokens and zero
            # prefill burned, stream closes right after the terminal event
            blocker = asyncio.ensure_future(_sse_request(
                server.host, server.port,
                {"prompt": _prompt(40), "max_new": 48}))
            while server.driver.tracked == 0:
                await asyncio.sleep(0.01)
            rec = await _sse_request(
                server.host, server.port,
                {"prompt": _prompt(16), "max_new": 8, "deadline_s": 0.001})
            assert rec["status"] == "DEADLINE_EXCEEDED"
            assert rec["tokens"] == []
            assert rec["events"][-1] == "done"
            assert (await blocker)["status"] == "OK"
        finally:
            await server.drain_and_stop(5.0)

    asyncio.run(body())


def test_backpressure_429_with_retry_after(setup):
    cfg, params = setup

    async def body():
        server = await _boot(params, cfg, slots=1, queue_cap=1)
        try:
            recs = await asyncio.gather(*(
                _sse_request(server.host, server.port,
                             {"prompt": _prompt(32, seed=i), "max_new": 8})
                for i in range(8)))
            rejected = [r for r in recs if r["http"] == 429]
            served = [r for r in recs if r["http"] == 200]
            assert rejected, "burst against queue_cap=1 must yield 429s"
            assert all(r["retry_after"] for r in rejected)
            assert served and all(r["status"] == "OK" for r in served)
        finally:
            await server.drain_and_stop(5.0)

    asyncio.run(body())


def test_readyz_false_during_warmup_then_true(setup):
    cfg, params = setup
    gate = threading.Event()

    async def body():
        server = await _boot(params, cfg, warmup=gate.wait)
        try:
            code, text = await _get(server.host, server.port, "/readyz")
            assert (code, text) == (503, b"warming up")
            code, _ = await _get(server.host, server.port, "/healthz")
            assert code == 200  # alive even while not ready
            gate.set()
            while not server.ready:
                await asyncio.sleep(0.02)
            code, text = await _get(server.host, server.port, "/readyz")
            assert (code, text) == (200, b"ready")
        finally:
            gate.set()
            await server.drain_and_stop(5.0)

    asyncio.run(body())


def test_default_warmup_compiles_every_usable_chunk_size(setup):
    """/readyz turns true only once each prefill chunk size a prompt below
    max_len can issue has its tick compiled — live traffic never stalls on
    a first compile (on a chip, longer than the pool's hang timeout)."""
    cfg, params = setup

    async def body():
        server = await _boot(params, cfg)  # max_len 192: chunks 64 and 128
        try:
            eng = server.driver.engine
            assert sorted(eng._fused) == [64, 128]
            assert eng.stats()["statuses"] == {"OK": 2}
        finally:
            await server.drain_and_stop(5.0)

    asyncio.run(body())


def test_graceful_drain_finishes_inflight_streams(setup):
    cfg, params = setup

    async def body():
        server = await _boot(params, cfg)
        try:
            inflight = asyncio.ensure_future(_sse_request(
                server.host, server.port,
                {"prompt": _prompt(40), "max_new": 16}))
            # wait until the stream has started, then pull the trigger
            while server.driver.tracked == 0:
                await asyncio.sleep(0.01)
            server.begin_drain()
            code, text = await _get(server.host, server.port, "/readyz")
            assert (code, text) == (503, b"draining")  # flips immediately
            rec_new = await _sse_request(server.host, server.port,
                                         {"prompt": _prompt(8), "max_new": 4})
            assert rec_new["http"] == 503  # no new admissions while draining
            rec = await inflight  # in-flight stream runs to completion
            assert rec["status"] == "OK"
            assert len(rec["tokens"]) == 16
            await asyncio.wait_for(server.serve_until_drained(), timeout=30)
            assert server.driver.stopped
            assert server.driver.tracked == 0  # no stuck connections
        finally:
            if not server.driver.stopped:
                await server.drain_and_stop(5.0)

    asyncio.run(body())


def test_drain_hard_kill_timeout_cancels_leftovers(setup):
    cfg, params = setup

    async def body():
        server = await _boot(params, cfg)
        try:
            inflight = asyncio.ensure_future(_sse_request(
                server.host, server.port,
                {"prompt": _prompt(40), "max_new": 4000}))  # can't finish fast
            while server.driver.tracked == 0:
                await asyncio.sleep(0.01)
            await server.drain_and_stop(0.2)  # hard-kill path
            rec = await inflight
            # the leftover stream was cancelled, not left hanging
            assert rec["status"] in ("CANCELLED", "FAILED", "CACHE_EXHAUSTED")
            assert server.driver.tracked == 0
        finally:
            if not server.driver.stopped:
                await server.drain_and_stop(5.0)

    asyncio.run(body())


def test_client_disconnect_cancels_and_keeps_cobatch_bit_identical(setup):
    cfg, params = setup
    keep_prompt = _prompt(24, seed=7)

    async def body():
        server = await _boot(params, cfg)
        try:
            # two co-batched streams; one client hangs up after its first token
            gone, kept = await asyncio.gather(
                _sse_request(server.host, server.port,
                             {"prompt": _prompt(40, seed=3), "max_new": 64},
                             disconnect_after=1),
                _sse_request(server.host, server.port,
                             {"prompt": keep_prompt, "max_new": 8}))
            assert gone["disconnected"]
            assert kept["status"] == "OK" and len(kept["tokens"]) == 8
            # server side observed the cancellation and freed the slot: the
            # engine went fully idle (cancel retires within one tick; a hung
            # slot would keep `live` non-zero and the engine never idle)
            for _ in range(200):
                stats = json.loads((await _get(server.host, server.port,
                                               "/v1/stats"))[1])
                if stats["statuses"].get("CANCELLED"):
                    break
                await asyncio.sleep(0.02)
            assert stats["statuses"].get("CANCELLED") == 1
            assert stats["live"] == 0 and stats["queued"] == 0
            return kept["tokens"]
        finally:
            await server.drain_and_stop(5.0)

    kept_tokens = asyncio.run(body())
    # bit-identity: the surviving stream matches a run where the
    # disconnected request was never admitted at all
    eng = _engine(params, cfg)
    ref = E.Request(rid=0, prompt=np.asarray(keep_prompt, np.int64), max_new=8)
    eng.submit(ref)
    eng.run()
    assert kept_tokens == [int(t) for t in ref.generated]


@pytest.mark.slow
def test_sigterm_process_exits_zero():
    """Full-process acceptance: boot the launcher, stream against it, send
    SIGTERM mid-serve, require exit code 0 (graceful drain)."""
    import os
    import pathlib
    import signal
    import subprocess
    import sys

    env = dict(os.environ)
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.server", "--smoke", "--port", "0",
         "--slots", "2", "--max-len", "192"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env)
    try:
        line = proc.stdout.readline()
        assert "listening on http://" in line, line
        port = int(line.split("http://")[1].split()[0].rsplit(":", 1)[1])

        async def probe():
            while True:
                code, _ = await _get("127.0.0.1", port, "/readyz")
                if code == 200:
                    break
                await asyncio.sleep(0.1)
            return await _sse_request("127.0.0.1", port,
                                      {"prompt": list(range(1, 17)),
                                       "max_new": 4})

        rec = asyncio.run(probe())
        assert rec["status"] == "OK"
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=60) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


def test_unknown_route_and_bad_request(setup):
    cfg, params = setup

    async def body():
        server = await _boot(params, cfg)
        try:
            code, _ = await _get(server.host, server.port, "/nope")
            assert code == 404
            reader, writer = await asyncio.open_connection(server.host,
                                                           server.port)
            body_b = b'{"max_new": 4}'  # missing prompt
            writer.write(b"POST /v1/generate HTTP/1.1\r\n"
                         b"content-length: %d\r\n\r\n%s" %
                         (len(body_b), body_b))
            await writer.drain()
            status = await reader.readline()
            assert b"400" in status
            writer.close()
        finally:
            await server.drain_and_stop(5.0)

    asyncio.run(body())
