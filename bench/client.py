"""Asyncio SSE client for ``POST /v1/generate``, and the two load loops.

Each stream is timed from its scheduled send time: in the open loop that
is the time the schedule gave it, however late the generator got round to
sending it; in the closed loop it is the moment the client's previous
request ended. Tokens are timed when the bytes that carry them arrive.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import time

from traffic import Req


@dataclasses.dataclass
class Stream:
    """What the client saw of one request."""
    req: Req
    t_sched: float
    t_sent: float = 0.0
    http: int = 0
    tokens: list = dataclasses.field(default_factory=list)
    times: list = dataclasses.field(default_factory=list)
    status: str | None = None  # the terminal event's status
    cut: bool = False  # still running when the window closed
    error: str | None = None  # the connection failed

    @property
    def ok(self) -> bool:
        return (self.http == 200 and self.status == "OK"
                and len(self.tokens) == self.req.max_new)


def _parse_events(buf: bytes, st: Stream, now: float) -> bytes:
    """Consume every complete SSE event in ``buf``; return the rest."""
    while True:
        end = buf.find(b"\n\n")
        if end < 0:
            return buf
        block, buf = buf[:end], buf[end + 2:]
        event, data = None, None
        for line in block.split(b"\n"):
            if line.startswith(b"event:"):
                event = line[6:].strip()
            elif line.startswith(b"data:"):
                data = line[5:].strip()
        if event == b"token":
            st.tokens.append(int(json.loads(data)["token"]))
            st.times.append(now)
        elif event in (b"done", b"error"):
            st.status = json.loads(data).get("status")


async def stream(host: str, port: int, st: Stream) -> None:
    """Send ``st.req`` and fill ``st`` in place as the stream arrives, so a
    stream cut short keeps what it had received."""
    st.t_sent = time.perf_counter()
    reader, writer = await asyncio.open_connection(host, port)
    try:
        body = json.dumps({"prompt": st.req.prompt,
                           "max_new": st.req.max_new}).encode()
        writer.write(b"POST /v1/generate HTTP/1.1\r\nhost: bench\r\n"
                     b"content-length: %d\r\n\r\n" % len(body) + body)
        await writer.drain()
        head = await reader.readuntil(b"\r\n\r\n")
        st.http = int(head.split(b" ", 2)[1])
        if st.http != 200:
            return
        buf = b""
        while st.status is None:
            chunk = await reader.read(65536)
            if not chunk:
                return
            buf = _parse_events(buf + chunk, st, time.perf_counter())
    finally:
        writer.close()


class Load:
    """Drives one mix against a server and keeps every stream it started."""

    def __init__(self, host: str, port: int):
        self.host, self.port = host, port
        self.streams: list[Stream] = []  # ended, failed or cut
        self.started: list[Stream] = []
        self.dry = 0  # closed-loop clients that ran out of requests
        self.lateness: list[float] = []  # open loop: send time - due time
        self.stop = asyncio.Event()
        self._tasks: list[asyncio.Task] = []

    async def _one(self, req: Req, t_sched: float) -> Stream:
        st = Stream(req, t_sched)
        self.started.append(st)
        try:
            await stream(self.host, self.port, st)
        except asyncio.CancelledError:
            st.cut = True
            raise
        except (OSError, asyncio.IncompleteReadError,
                asyncio.LimitOverrunError, ValueError) as exc:
            st.error = f"{type(exc).__name__}: {exc}"
        finally:
            self.streams.append(st)
        return st

    def closed(self, plans: list[list[Req]]) -> None:
        """One client per plan, each sending its next request as soon as
        the previous one has ended, until ``stop`` is set."""
        async def client(plan):
            for req in plan:
                if self.stop.is_set():
                    return
                await self._one(req, time.perf_counter())
            self.dry += 1

        self._tasks += [asyncio.ensure_future(client(p)) for p in plans]

    def open(self, reqs: list[Req], t0: float) -> None:
        """Send each request at ``t0 + req.at`` until ``stop`` is set."""
        async def schedule():
            for req in reqs:
                due = t0 + req.at
                delay = due - time.perf_counter()
                if delay > 0:
                    await asyncio.sleep(delay)
                if self.stop.is_set():
                    return
                self.lateness.append(time.perf_counter() - due)
                self._tasks.append(asyncio.ensure_future(self._one(req, due)))

        self._tasks.append(asyncio.ensure_future(schedule()))

    async def close(self) -> None:
        """Stop sending, cut every stream still running, and wait for all."""
        self.stop.set()
        for t in self._tasks:
            t.cancel()
        await asyncio.gather(*self._tasks, return_exceptions=True)
