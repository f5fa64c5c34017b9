"""Async streaming front door: HTTP/SSE over the ``ServingEngine`` tick loop.

TeLLMe's headline numbers are *serving-latency* numbers (0.55–1.15 s prefill,
~9 tok/s decode under 7 W); this module is what makes them observable as a
service: an asyncio HTTP/1.1 + SSE server (stdlib only — no new deps) that
streams tokens per scheduler tick and maps every PR-7 lifecycle outcome onto
a transport-visible termination (DESIGN.md §serving-frontdoor).

Threading model
---------------
The engine is single-threaded by construction (jitted tick functions, donated
buffers, host-side bookkeeping), so ALL engine access happens on one
dedicated **driver thread** (:class:`EngineDriver`): it drains a thread-safe
command queue (submits, cancels, stats snapshots posted by the asyncio side),
then runs ``engine.step()`` whenever work exists. Results flow the other way
through the engine's ``on_emit``/``on_finish`` hooks — fired by ``step()``
after its single per-tick device transfer — which the driver bridges onto
per-request :class:`asyncio.Queue`\\ s via ``loop.call_soon_threadsafe``. The
asyncio side never touches the engine; the driver never touches a socket.

Transport contract
------------------
``POST /v1/generate`` (JSON body ``{"prompt": [ids], "max_new": N,
"priority": P, "deadline_s": S}``; ``x-priority`` / ``x-deadline-s`` headers
override) answers:

* ``429`` + ``Retry-After`` when the bounded admission queue is full —
  backpressure is an admission-time rejection, never unbounded buffering in
  the server (per-stream buffers are bounded by the request's own
  ``max_new``);
* ``503`` during warmup jit and drain (``/readyz`` mirrors this);
* otherwise ``200 text/event-stream``:
  ``event: start``  ``{"rid": r}``, then per emitted token
  ``event: token``  ``{"index": i, "token": t}``, then exactly one terminal
  event and EOF — ``event: done`` ``{"status": "OK" | "CACHE_EXHAUSTED" |
  "DEADLINE_EXCEEDED" | "CANCELLED", ...}`` or ``event: error``
  ``{"status": "QUARANTINED" | "FAILED", ...}``. An engine re-init (PR-7
  last-resort containment) therefore surfaces as ``error`` events on the
  affected streams, never a hung connection.

Client disconnect mid-stream posts ``engine.cancel(rid)``; the next tick
retires the request ``CANCELLED`` and frees its slot (co-batched requests
bit-identical — the PR-7 isolation contract, re-tested for the disconnect
path in tests/test_resilience.py).

Drain state machine (SIGTERM)
-----------------------------
``serving → draining → stopped``. ``begin_drain()`` (the SIGTERM handler)
immediately flips ``/readyz`` to 503 and rejects new ``/v1/generate``; in-
flight requests finish or deadline-out on the still-running engine; past
``drain_timeout_s`` every remaining request is cancelled (hard kill). Then
the listener closes, the driver thread stops — failing any still-tracked
stream so no connection is ever left hanging — lingering sockets are
aborted, and the launcher exits 0.
"""

from __future__ import annotations

import asyncio
import itertools
import json
import queue as thread_queue
import threading
import time

import numpy as np

from ..configs.base import resolve_slo
from . import engine as E
from . import resilience as R
from . import tracing

# Terminal-status → SSE event name. QUARANTINED/FAILED are server-side
# faults (event: error); everything else is a normal stream end (event:
# done) — DEADLINE_EXCEEDED/CANCELLED close the stream right after it.
SSE_EVENT_FOR_STATUS = {
    "OK": "done",
    "CACHE_EXHAUSTED": "done",
    "DEADLINE_EXCEEDED": "done",
    "CANCELLED": "done",
    "QUARANTINED": "error",
    "FAILED": "error",
}

_MAX_BODY_BYTES = 8 << 20
_HEADER_TIMEOUT_S = 30.0


def sse_event(event: str, data: dict) -> bytes:
    return f"event: {event}\ndata: {json.dumps(data)}\n\n".encode()


class _StreamSink:
    """Driver-thread → asyncio bridge for one request's event stream."""

    def __init__(self, loop: asyncio.AbstractEventLoop):
        self.loop = loop
        self.queue: asyncio.Queue = asyncio.Queue()

    def push(self, item) -> None:  # driver thread
        try:
            self.loop.call_soon_threadsafe(self.queue.put_nowait, item)
        except RuntimeError:
            pass  # loop already closed: the stream's connection is gone too


class EngineDriver:
    """Owns the engine thread; the only code that ever touches the engine.

    Commands (submit/cancel/stats) arrive on a thread-safe queue and run
    between ticks; token/terminal delivery rides the engine's
    ``on_emit``/``on_finish`` hooks. ``engine.step()`` never raises (PR-7),
    but the loop still wraps it: an unexpected escape fails the tracked
    streams and re-initializes device state instead of killing the thread —
    the server process survives anything the engine does.
    """

    def __init__(self, engine: E.ServingEngine, *, poll_s: float | None = None,
                 warmup=True, name: str = "engine-driver"):
        self.engine = engine
        self.poll_s = (float(getattr(engine.cfg, "server_poll_s", 0.001))
                       if poll_s is None else float(poll_s))
        self._warmup = warmup  # True = default tiny request; callable = custom
        self._cmds: thread_queue.SimpleQueue = thread_queue.SimpleQueue()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self.ready = threading.Event()  # set once warmup jit completes
        self._rids = itertools.count(1)
        self._sinks: dict[int, _StreamSink] = {}  # driver thread only
        self._reqs: dict[int, E.Request] = {}
        # Pool taps (DESIGN.md §replica-pool): emit/finish listeners fire on
        # the driver thread for EVERY request (pool-submitted requests never
        # appear in _sinks); fault_hook runs at the top of each loop
        # iteration (the pool's replica_crash/replica_hang injection point —
        # a SystemExit raised there kills the thread with no cleanup, the
        # same observable as a real crash); beat is the loop heartbeat the
        # pool's hang detector watches.
        self.emit_listener = None  # callable(req, list[int]) | None
        self.finish_listener = None  # callable(req) | None
        self.fault_hook = None  # callable(driver) | None
        self.beat = time.monotonic()
        engine.on_emit = self._on_emit
        engine.on_finish = self._on_finish
        self._thread = threading.Thread(target=self._run, name=name,
                                        daemon=True)

    # -- asyncio-side API ----------------------------------------------------

    def start(self) -> None:
        self._thread.start()

    @property
    def stopped(self) -> bool:
        return self._stop.is_set() or not self._thread.is_alive()

    @property
    def crashed(self) -> bool:
        """The thread died without anyone asking it to stop — a crash, not
        a shutdown (never true before ``start()``)."""
        return (self._thread.ident is not None
                and not self._thread.is_alive()
                and not self._stop.is_set())

    @property
    def tracked(self) -> int:
        """Streams with no terminal event delivered yet."""
        return len(self._sinks)

    def tracked_rids(self) -> list[int]:
        return list(self._sinks)

    async def submit(self, prompt, *, max_new: int, priority: int = 0,
                     deadline_s: float | None = None, slo: str | None = None,
                     budget_weight: float = 1.0,
                     arrival_ns: int | None = None):
        """Submit on the driver thread; returns ``(rid, sink)`` or ``None``
        when the bounded admission queue rejected it (the HTTP 429 path).
        ``arrival_ns`` (perf_counter ns) is when the front door took the
        request; its ``serve.queue`` span starts there."""
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        rid = next(self._rids)
        sink = _StreamSink(loop)
        prompt = np.asarray(prompt, np.int64)

        def cmd():
            req = E.Request(rid=rid, prompt=prompt, max_new=int(max_new),
                            priority=int(priority),
                            deadline_s=(None if deadline_s is None
                                        else float(deadline_s)),
                            slo=slo, budget_weight=float(budget_weight),
                            t_arrival_ns=arrival_ns)
            if self.engine.submit(req):
                self._sinks[rid] = sink
                self._reqs[rid] = req
                ok = True
            else:
                ok = False  # queue_full: terminal already stamped on req
            loop.call_soon_threadsafe(_resolve, fut, ok)

        self._post(cmd)
        return (rid, sink) if await fut else None

    def cancel(self, rid: int) -> None:
        self._post(lambda: self.engine.cancel(rid))

    # -- pool-side API (thread-safe, no asyncio loop required) ---------------

    def submit_request(self, req: E.Request, cb=None) -> None:
        """Post a fully-built :class:`Request` for engine admission on the
        driver thread. ``cb(ok)`` (if given) runs on the driver thread right
        after ``engine.submit`` — the pool's dispatch bookkeeping hook.
        Raises :class:`ConnectionError` when the driver is stopped/dead."""
        def cmd():
            ok = self.engine.submit(req)
            if cb is not None:
                cb(ok)

        self._post(cmd)

    def stats_blocking(self, timeout_s: float = 1.0) -> dict | None:
        """Engine stats taken on the driver thread, awaited with a plain
        threading.Event — usable off-asyncio (the pool's aggregation path).
        Returns ``None`` when the driver is stopped, crashed, or wedged past
        ``timeout_s`` (a hung replica must not hang ``/v1/stats``)."""
        box: dict = {}
        done = threading.Event()

        def cmd():
            box["s"] = self.engine.stats()
            done.set()

        try:
            self._post(cmd)
        except ConnectionError:
            return None
        if not done.wait(timeout_s):
            return None
        return box.get("s")

    async def stats(self) -> dict:
        """Engine stats snapshot taken on the driver thread (no torn reads)."""
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()

        def cmd():
            s = self.engine.stats()
            s["tracked_streams"] = self.tracked
            loop.call_soon_threadsafe(_resolve, fut, s)

        self._post(cmd)
        return await fut

    def stop(self) -> None:
        """Stop the driver (blocking; call via ``asyncio.to_thread``). Any
        stream still tracked afterwards is failed so it cannot hang."""
        self._stop.set()
        self._wake.set()
        self._thread.join(timeout=10.0)

    # -- driver thread -------------------------------------------------------

    def _post(self, cmd) -> None:
        if self.stopped:
            # resolve-by-failure instead of queueing into a dead thread: the
            # command's future would otherwise never complete
            raise ConnectionError("engine driver is stopped")
        self._cmds.put(cmd)
        self._wake.set()

    def _run(self) -> None:
        try:
            if callable(self._warmup):
                self._warmup()
            elif self._warmup:
                self._default_warmup()
        except Exception:  # noqa: BLE001 — warmup is best-effort compile
            pass
        self.ready.set()
        eng = self.engine
        while not self._stop.is_set():
            self.beat = time.monotonic()
            if self.fault_hook is not None:
                # May raise SystemExit (replica_crash: the thread dies here,
                # mid-loop, with no cleanup — exactly like a real crash) or
                # sleep (replica_hang: beat goes stale for the duration).
                self.fault_hook(self)
            self._drain_cmds()
            if eng.queue or any(r is not None for r in eng.live):
                try:
                    eng.step()
                except Exception as exc:  # noqa: BLE001 — survive anything
                    self._contain(f"driver_escape: {type(exc).__name__}")
            else:
                self._wake.wait(self.poll_s)
                self._wake.clear()
        self._drain_cmds()
        self._fail_tracked("server_shutdown")

    def _drain_cmds(self) -> None:
        with tracing.span("serve.drive.cmds",
                          replica=self.engine.replica_id) as span:
            n = 0
            while True:
                try:
                    cmd = self._cmds.get_nowait()
                except thread_queue.Empty:
                    break
                try:
                    cmd()
                except Exception:  # noqa: BLE001
                    pass  # a bad command must not kill the driver
                n += 1
            if n:
                span.set(commands=n)
            else:
                span.discard()

    def _default_warmup(self) -> None:
        """Compile the tick jits before /readyz goes true: one request per
        prefill chunk size the engine can issue, each prompt a single chunk,
        through prefill + decode (rid 0 is never handed out, so the hooks
        ignore it). A tick shape left to compile under live traffic stalls
        its tick for the whole compile — on a TPU long enough for the
        replica pool to declare the replica hung."""
        eng = self.engine
        vocab = int(getattr(eng.cfg, "vocab_size", 2))
        for n in [c for c in eng.chunk_sizes if c < eng.max_len - 2] or [8]:
            req = E.Request(rid=0, max_new=2,
                            prompt=np.arange(1, n + 1, dtype=np.int64) % vocab)
            if eng.submit(req):
                while not req.done and (
                        eng.queue or any(r is not None for r in eng.live)):
                    eng.step()

    def _contain(self, detail: str) -> None:
        """An exception escaped ``step()`` (should be impossible post-PR-7):
        terminate every queued + live request FAILED, re-init device state,
        deliver terminal events — the streams end, the process survives."""
        eng = self.engine
        queued, eng.queue = list(eng.queue), []
        for req in queued:
            eng._finish(None, req, R.Status.FAILED, detail=detail)
        eng._fail_all_live(detail)
        for req in queued:
            self._on_finish(req)

    def _fail_tracked(self, detail: str) -> None:
        """Deliver a terminal event to every stream still tracked (shutdown
        path): no connection is left waiting on a queue nobody will fill."""
        for rid in list(self._sinks):
            req = self._reqs.get(rid)
            if req is not None and not req.done:
                req.done = True
                req.status = R.Status.FAILED
                req.status_detail = detail
            self._on_finish(req if req is not None
                            else E.Request(rid=rid, prompt=[], max_new=0,
                                           done=True, status=R.Status.FAILED,
                                           status_detail=detail))

    # -- engine hooks (driver thread, fired by step()) -----------------------

    def _on_emit(self, req: E.Request, toks: list) -> None:
        if self.emit_listener is not None:
            self.emit_listener(req, toks)
        sink = self._sinks.get(req.rid)
        if sink is not None:
            sink.push(("tokens", [int(t) for t in toks], req.t_first_ns,
                       self.engine.replica_id))

    def _on_finish(self, req: E.Request) -> None:
        if self.finish_listener is not None:
            self.finish_listener(req)
        sink = self._sinks.pop(req.rid, None)
        self._reqs.pop(req.rid, None)
        if sink is not None:
            sink.push(("final", req.status.name, req.status_detail,
                       len(req.generated)))


def _resolve(fut: asyncio.Future, value) -> None:
    if not fut.done():
        fut.set_result(value)


class ServingServer:
    """The HTTP/SSE front door. One instance, one backend.

    The backend is either a bare ``ServingEngine`` (wrapped in a single
    :class:`EngineDriver`) or a ``serving.pool.ReplicaPool`` (detected via
    its ``IS_POOL`` marker — the pool owns its own drivers, SLO-class
    admission, health-gated routing and crash failover; the server just
    routes submits/cancels/stats at it and aggregates ``/v1/stats``).

    Lifecycle: ``await start()`` (binds the socket, starts the driver),
    ``begin_drain()`` (SIGTERM handler; idempotent), ``await
    serve_until_drained()`` (the launcher's main await). Tests drive
    ``drain_and_stop`` directly with a short timeout.
    """

    def __init__(self, engine, *, host: str | None = None,
                 port: int | None = None, drain_timeout_s: float | None = None,
                 warmup=True, poll_s: float | None = None):
        cfg = engine.cfg
        self.cfg = cfg
        tracing.configure(int(getattr(cfg, "trace_ring_spans", 0)))
        if getattr(engine, "IS_POOL", False):  # serving.pool.ReplicaPool
            self.pool = engine
            self.driver = None
        else:
            self.pool = None
            self.driver = EngineDriver(engine, warmup=warmup, poll_s=poll_s)
        self.host = (getattr(cfg, "server_host", "127.0.0.1")
                     if host is None else host)
        self.port = (int(getattr(cfg, "server_port", 8080))
                     if port is None else int(port))
        self.drain_timeout_s = (
            float(getattr(cfg, "server_drain_timeout_s", 30.0))
            if drain_timeout_s is None else float(drain_timeout_s))
        self.draining = False
        self._drained = None  # asyncio.Event, created on start()
        self._server = None
        self._loop = None
        self._writers: set[asyncio.StreamWriter] = set()

    # -- lifecycle -----------------------------------------------------------

    async def start(self) -> "ServingServer":
        self._loop = asyncio.get_running_loop()
        self._drained = asyncio.Event()
        if self.pool is not None:
            self.pool.start()
        else:
            self.driver.start()
        self._server = await asyncio.start_server(self._handle, self.host,
                                                  self.port)
        self.port = self._server.sockets[0].getsockname()[1]
        return self

    @property
    def ready(self) -> bool:
        if self.pool is not None:
            return (self.pool.ready and not self.draining
                    and not self.pool.stopped)
        return (self.driver.ready.is_set() and not self.draining
                and not self.driver.stopped)

    def begin_drain(self) -> None:
        """SIGTERM entry: flip to draining *now* (readyz 503, new generates
        rejected) and finish the rest asynchronously."""
        if not self.draining:
            self.draining = True
            self._loop.create_task(self.drain_and_stop())

    async def drain_and_stop(self, timeout_s: float | None = None) -> None:
        """stop admitting → let in-flight finish or deadline-out → hard-kill
        leftovers at the timeout → stop driver, abort lingering sockets."""
        self.draining = True
        timeout = self.drain_timeout_s if timeout_s is None else timeout_s
        deadline = self._loop.time() + timeout
        while self._loop.time() < deadline and not await self._idle():
            await asyncio.sleep(0.02)
        if not await self._idle():  # hard kill: cancel whatever is left
            for rid in self._tracked_rids():
                self._cancel(rid)
            grace = self._loop.time() + 2.0
            while self._loop.time() < grace and not await self._idle():
                await asyncio.sleep(0.02)
        self._server.close()
        await self._server.wait_closed()
        stop = self.pool.stop if self.pool is not None else self.driver.stop
        await asyncio.to_thread(stop)  # fails any leftover stream
        await asyncio.sleep(0.05)  # let final events flush through handlers
        for w in list(self._writers):  # no stuck connections, ever
            w.close()
        self._drained.set()

    async def serve_until_drained(self) -> None:
        await self._drained.wait()

    def _tracked_rids(self) -> list[int]:
        return (self.pool.tracked_rids() if self.pool is not None
                else self.driver.tracked_rids())

    def _cancel(self, rid: int) -> None:
        if self.pool is not None:
            self.pool.cancel(rid)
        else:
            self.driver.cancel(rid)

    async def _idle(self) -> bool:
        if self.pool is not None:
            return self.pool.stopped or self.pool.idle()
        if self.driver.stopped:
            return True
        s = await self.driver.stats()
        return (s["queued"] == 0 and s["live"] == 0
                and s["tracked_streams"] == 0)

    # -- HTTP ----------------------------------------------------------------

    async def _handle(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter) -> None:
        self._writers.add(writer)
        try:
            try:
                method, path, headers, body = await asyncio.wait_for(
                    _read_request(reader), _HEADER_TIMEOUT_S)
            except (asyncio.TimeoutError, asyncio.IncompleteReadError,
                    ValueError, ConnectionError):
                return
            if method == "GET" and path == "/healthz":
                await _plain(writer, 200, "ok")
            elif method == "GET" and path == "/readyz":
                if self.ready:
                    await _plain(writer, 200, "ready")
                else:
                    await _plain(writer, 503,
                                 "draining" if self.draining else "warming up")
            elif method == "GET" and path == "/v1/stats":
                await self._handle_stats(writer)
            elif method == "POST" and path == "/v1/generate":
                await self._handle_generate(reader, writer, headers, body)
            else:
                await _plain(writer, 404, f"no route {method} {path}")
        except (ConnectionError, asyncio.CancelledError):
            pass
        finally:
            self._writers.discard(writer)
            writer.close()
            try:
                await writer.wait_closed()
            except Exception:  # noqa: BLE001
                pass

    async def _handle_stats(self, writer: asyncio.StreamWriter) -> None:
        if self.pool is not None:
            if self.pool.stopped:
                return await _plain(writer, 503, "stopped")
            # pool.stats() blocks up to its per-replica stats timeout when a
            # replica is wedged — keep the event loop out of that wait
            s = await asyncio.to_thread(self.pool.stats)
        else:
            if self.driver.stopped:
                return await _plain(writer, 503, "stopped")
            s = await self.driver.stats()
        s["draining"] = self.draining
        s["ready"] = self.ready
        s["trace"] = tracing.recorder().stats()  # process-wide
        await _plain(writer, 200, json.dumps(s), ctype="application/json")

    async def _handle_generate(self, reader, writer, headers: dict,
                               body: bytes) -> None:
        if not self.ready:
            return await _plain(writer, 503,
                                "draining" if self.draining else "warming up",
                                extra={"retry-after": "1"})
        try:
            payload = json.loads(body or b"{}")
            prompt = [int(t) for t in payload["prompt"]]
            max_new = int(payload.get("max_new", 16))
            # SLO class seeds priority/deadline/chunk-budget weight
            # (DESIGN.md §replica-pool); explicit priority/deadline_s still
            # override the class defaults. Unknown class → KeyError → 400.
            slo = payload.get("slo", headers.get("x-slo"))
            slo = None if slo is None else str(slo)
            priority, deadline_s, weight = 0, None, 1.0
            if slo is not None:
                priority, deadline_s, weight = resolve_slo(self.cfg, slo)
            raw_prio = payload.get("priority", headers.get("x-priority"))
            if raw_prio is not None:
                priority = int(raw_prio)
            raw_deadline = payload.get("deadline_s",
                                       headers.get("x-deadline-s"))
            if raw_deadline is not None:
                deadline_s = float(raw_deadline)
            if max_new < 1:
                raise ValueError("max_new must be >= 1")
        except (KeyError, TypeError, ValueError) as exc:
            return await _plain(writer, 400, f"bad request: {exc}")

        arrival = time.perf_counter_ns()  # the request's serve.queue starts
        if self.pool is not None:
            sink = _StreamSink(asyncio.get_running_loop())
            rid = self.pool.submit(prompt, max_new=max_new, slo=slo,
                                   priority=priority, deadline_s=deadline_s,
                                   budget_weight=weight, sink=sink)
            sub = None if rid is None else (rid, sink)
        else:
            sub = await self.driver.submit(prompt, max_new=max_new,
                                           priority=priority,
                                           deadline_s=deadline_s, slo=slo,
                                           budget_weight=weight,
                                           arrival_ns=arrival)
        if sub is None:  # bounded admission queue: backpressure, not buffering
            return await _plain(writer, 429, "admission queue full",
                                extra={"retry-after": "1"})
        rid, sink = sub
        writer.write(b"HTTP/1.1 200 OK\r\n"
                     b"content-type: text/event-stream\r\n"
                     b"cache-control: no-cache\r\n"
                     b"connection: close\r\n\r\n")
        writer.write(sse_event("start", {"rid": rid}))
        try:
            await writer.drain()
        except ConnectionError:
            self._cancel(rid)

        # reader EOF = client went away: cancel within one tick, then keep
        # draining the sink until the engine's terminal event tears it down
        eof_task = asyncio.ensure_future(reader.read())
        get_task = asyncio.ensure_future(sink.queue.get())
        disconnected = False
        idx = 0
        try:
            while True:
                pending = {get_task} | ({eof_task} if not disconnected
                                        else set())
                done, _ = await asyncio.wait(
                    pending, return_when=asyncio.FIRST_COMPLETED)
                if eof_task in done and not disconnected:
                    disconnected = True
                    self._cancel(rid)
                if get_task not in done:
                    continue
                item = get_task.result()
                if item[0] == "tokens":
                    _, toks, t_first, replica = item
                    if not disconnected:
                        first = idx == 0
                        for t in toks:
                            writer.write(sse_event(
                                "token", {"index": idx, "token": t}))
                            idx += 1
                        try:
                            await writer.drain()
                        except ConnectionError:
                            disconnected = True
                            self._cancel(rid)
                        if first and not disconnected and t_first is not None:
                            tracing.interval(
                                "serve.deliver", t_first,
                                time.perf_counter_ns(), rid=rid,
                                replica=replica)
                    else:
                        idx += len(toks)
                    get_task = asyncio.ensure_future(sink.queue.get())
                    continue
                _, status, detail, n_tokens = item
                if not disconnected:
                    writer.write(sse_event(
                        SSE_EVENT_FOR_STATUS.get(status, "error"),
                        {"status": status, "detail": detail,
                         "tokens": n_tokens}))
                    try:
                        await writer.drain()
                    except ConnectionError:
                        pass
                return
        finally:
            for t in (eof_task, get_task):
                if not t.done():
                    t.cancel()


async def _read_request(reader: asyncio.StreamReader):
    """Minimal HTTP/1.1 request parse: request line, headers, body by
    Content-Length. One request per connection (`Connection: close`)."""
    line = await reader.readline()
    if not line:
        raise ConnectionError("empty request")
    method, path, _ = line.decode("latin-1").split()
    headers: dict[str, str] = {}
    while True:
        raw = await reader.readline()
        if raw in (b"\r\n", b"\n", b""):
            break
        key, _, value = raw.decode("latin-1").partition(":")
        headers[key.strip().lower()] = value.strip()
    length = int(headers.get("content-length", 0))
    if length > _MAX_BODY_BYTES:
        raise ValueError(f"body too large: {length}")
    body = await reader.readexactly(length) if length else b""
    return method, path.split("?", 1)[0], headers, body


async def _plain(writer: asyncio.StreamWriter, status: int, text: str, *,
                 ctype: str = "text/plain", extra: dict | None = None) -> None:
    reason = {200: "OK", 400: "Bad Request", 404: "Not Found",
              413: "Payload Too Large", 429: "Too Many Requests",
              503: "Service Unavailable"}.get(status, "")
    body = text.encode()
    head = [f"HTTP/1.1 {status} {reason}", f"content-type: {ctype}",
            f"content-length: {len(body)}", "connection: close"]
    for k, v in (extra or {}).items():
        head.append(f"{k}: {v}")
    writer.write(("\r\n".join(head) + "\r\n\r\n").encode() + body)
    try:
        await writer.drain()
    except ConnectionError:
        pass
