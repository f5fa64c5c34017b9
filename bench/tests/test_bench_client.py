"""The open-loop client times each request from its scheduled send time, so
a stalled generator or server shows up in the latency."""

import asyncio
import time

import client as C
import traffic as T


async def _server(delay_first_s):
    async def handle(reader, writer):
        await reader.readuntil(b"\r\n\r\n")
        writer.write(b"HTTP/1.1 200 OK\r\ncontent-type: text/event-stream"
                     b"\r\n\r\nevent: start\ndata: {\"rid\": 1}\n\n")
        await asyncio.sleep(delay_first_s)
        for i in range(3):
            writer.write(b'event: token\ndata: {"index": %d, "token": %d}\n\n'
                         % (i, 7 + i))
            await writer.drain()
            await asyncio.sleep(0.01)
        writer.write(b'event: done\ndata: {"status": "OK", "tokens": 3}\n\n')
        await writer.drain()
        writer.close()

    return await asyncio.start_server(handle, "127.0.0.1", 0)


def test_open_loop_times_from_schedule():
    async def main():
        srv = await _server(0.05)
        port = srv.sockets[0].getsockname()[1]
        load = C.Load("127.0.0.1", port)
        t0 = time.perf_counter()
        reqs = [T.Req([1, 2], 3, at=0.0), T.Req([3], 3, at=0.1)]
        load.open(reqs, t0)
        # the loop is blocked past the second request's due time
        await asyncio.sleep(0.01)
        time.sleep(0.3)
        await asyncio.sleep(0.5)
        await load.close()
        srv.close()
        return load, t0

    load, t0 = asyncio.run(main())
    st = sorted(load.streams, key=lambda s: s.t_sched)
    assert [s.ok for s in st] == [True, True]
    assert [s.tokens for s in st] == [[7, 8, 9], [7, 8, 9]]
    assert st[1].t_sched == t0 + 0.1
    # sent late, and the lateness counts in its time to first token
    assert st[1].t_sent - st[1].t_sched > 0.15
    assert st[1].times[0] - st[1].t_sched >= 0.2 + 0.05
    assert len(load.lateness) == 2 and max(load.lateness) > 0.15


def test_close_cuts_running_streams():
    async def main():
        srv = await _server(5.0)
        port = srv.sockets[0].getsockname()[1]
        load = C.Load("127.0.0.1", port)
        load.closed([[T.Req([1], 3)], [T.Req([2], 3)]])
        await asyncio.sleep(0.2)
        await load.close()
        srv.close()
        return load

    load = asyncio.run(main())
    assert len(load.streams) == 2
    assert all(s.cut and not s.ok and s.http == 200 for s in load.streams)
