"""The serving path's tracer (DESIGN.md §tracing): the bounded ring and its
drop count, nested span ids, compiles counted by replica, recording
switched off, the ``serve.tick`` attributes of a smoke engine (a failed
tick's too), the request spans through the HTTP/SSE front door against the
client's own TTFT, the spans on the profiler's host plane, and the tick
programs' ``named_scope``s in their lowered HLO."""

import asyncio
import dataclasses
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.bench_serving import _sse_request
from repro.configs import get_config
from repro.core import params as P
from repro.models import transformer as T
from repro.serving import engine as E
from repro.serving import tracing
from repro.serving.server import ServingServer

# prompt lengths whose chunk schedules differ (256 | 128 | 128+64 | 64) on
# the smoke chunk sizes (64, 128, 256), with a 256-row budget: the first
# tick runs the 200-token prompt's 256-chunk alone, so the others stall
MIXED = (200, 70, 130, 20)


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_config("tellme-0.7b", smoke=True),
                              dtype=jnp.float32)
    params = P.init_params(T.param_specs(cfg), jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture
def ring(setup):
    rec = tracing.configure(setup[0].trace_ring_spans)
    rec.clear()
    yield rec
    rec.clear()


def _engine(params, cfg, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("max_len", 256)
    return E.ServingEngine(params, cfg, mode="eval", eos_id=-2, **kw)


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 200, size=n) for n in lens]


def _serve(eng, lens, max_new=4):
    for i, p in enumerate(_prompts(lens)):
        eng.submit(E.Request(rid=i, prompt=p, max_new=max_new))
    eng.run()


async def _get(host, port, path):
    reader, writer = await asyncio.open_connection(host, port)
    writer.write(f"GET {path} HTTP/1.1\r\nhost: {host}\r\n\r\n".encode())
    await writer.drain()
    raw = await reader.read()
    writer.close()
    head, _, body = raw.partition(b"\r\n\r\n")
    return int(head.split()[1]), body


def _named(rec, name):
    return [r for r in rec.records() if r.name == name]


def test_ring_is_bounded_and_counts_drops():
    rec = tracing.Recorder(3)
    for i in range(5):
        rec.interval("x", i, 10 + i, i=i)
    assert [r.attrs["i"] for r in rec.records()] == [2, 3, 4]
    assert rec.stats() == {"kept": 3, "dropped": 2, "compiles": 0}
    assert rec.lost_end_ns == 11  # the later of the two dropped records
    assert rec.lost_since(11) and not rec.lost_since(12)
    rec.resize(1)  # shrinking keeps the newest and counts the rest
    assert [r.attrs["i"] for r in rec.records()] == [4]
    assert rec.dropped == 4


def test_nested_spans_carry_parent_ids():
    rec = tracing.Recorder(16)
    with rec.span("a", replica=1) as a:
        with rec.span("b") as b:
            with rec.span("c") as c:
                c.set(n=3)
        with rec.span("d") as d:
            d.discard()
    got = {r.name: r for r in rec.records()}
    assert set(got) == {"a", "b", "c"}  # d was discarded
    assert got["a"].parent_id is None
    assert got["b"].parent_id == a.span_id == got["a"].span_id
    assert got["c"].parent_id == b.span_id
    assert got["c"].attrs == {"n": 3} and got["a"].attrs == {"replica": 1}
    assert got["a"].start_ns <= got["b"].start_ns <= got["c"].start_ns
    assert got["c"].end_ns <= got["b"].end_ns <= got["a"].end_ns


def test_compile_is_recorded_inside_its_tick():
    # counted under the replica whose tick ran it; no record in the ring
    rec = tracing.Recorder(16)
    with rec.span("serve.tick", tick=7, replica=2):
        with rec.span("serve.tick.launch"):
            rec.on_event(tracing.COMPILE_EVENT, 0.002)
    rec.on_event(tracing.COMPILE_EVENT, 0.001)
    rec.on_event("/jax/some/other_event", 0.5)
    assert [r.name for r in rec.records()] == ["serve.tick.launch",
                                               "serve.tick"]
    assert rec.compiles_in(2) == rec.compiles_in(None) == 1
    assert rec.compiles_in(0) == 0
    assert rec.stats()["compiles"] == 2
    # a real backend compile reaches the process-wide recorder's listener
    glob = tracing.configure(tracing.recorder().capacity)
    with tracing.span("serve.tick", tick=0, replica=-7):
        jax.jit(lambda x: x * 3.25 + 0.5)(jnp.ones(7)).block_until_ready()
    assert glob.compiles_in(-7) >= 1


def test_ring_off_records_nothing(setup, ring):
    cfg, params = setup
    off = dataclasses.replace(cfg, trace_ring_spans=0)
    eng = _engine(params, off)
    ServingServer(eng, port=0)  # the process's owner sizes the ring
    assert ring.capacity == 0
    _serve(eng, (20, 30))
    assert ring.records() == []
    assert ring.stats()["kept"] == 0
    assert eng.straggler.count == eng.tick_count > 0  # timed all the same


def test_tick_attributes_on_mixed_chunks(setup, ring):
    cfg, params = setup
    eng = _engine(params, cfg)
    _serve(eng, MIXED)
    ticks = _named(ring, "serve.tick")
    assert [t.attrs["tick"] for t in ticks] == list(range(eng.tick_count))
    fused = [t.attrs for t in ticks if t.attrs["kind"] == "fused"]
    assert {a["chunk"] for a in fused} >= {256, 128, 64}  # mixed chunk sizes
    assert fused[0]["chunk"] == 256 and fused[0]["selected"] == 1
    assert fused[0]["prefilling"] == 3  # two prompts stalled behind it
    assert fused[0]["prefill_rows"] == 200
    assert all(a["selected"] <= a["prefilling"] for a in fused)
    decode = [t.attrs for t in ticks if t.attrs["kind"] == "decode"]
    assert decode and all(a["prefilling"] == a["chunk"] == 0 for a in decode)
    # every token the engine emitted is counted by exactly one tick
    assert sum(t.attrs["emitted"] for t in ticks) == 4 * len(MIXED)
    # the tick's phases are its children, in order
    for t in ticks:
        kids = [r for r in ring.records() if r.parent_id == t.span_id]
        names = [k.name for k in kids]
        assert names == ["serve.tick.admit", "serve.tick.plan",
                         "serve.tick.launch", "serve.tick.wait",
                         "serve.tick.retire"]
        assert all(t.start_ns <= k.start_ns <= k.end_ns <= t.end_ns
                   for k in kids)
    # each request: one queue wait from submit to admission, one prefill
    # from admission to the first token's read-back
    pre = {r.attrs["rid"]: r for r in _named(ring, "serve.prefill")}
    queue = {r.attrs["rid"]: r for r in _named(ring, "serve.queue")}
    assert set(pre) == set(queue) == set(range(len(MIXED)))
    for rid in pre:
        assert queue[rid].end_ns <= pre[rid].start_ns + 1
    # the 70-token prompt sat out the first tick: its prefill is longer
    # than the one tick its single chunk ran
    assert pre[1].end_ns - pre[1].start_ns > ticks[0].end_ns - \
        ticks[0].start_ns
    st = ring.stats()
    assert st["kept"] == len(ring.records()) and st["dropped"] == 0
    assert eng.stats()["compiles"] == ring.compiles_in(eng.replica_id)


def test_failed_tick_carries_every_attribute(setup, ring, monkeypatch):
    cfg, params = setup
    eng = _engine(params, cfg)
    for i, p in enumerate(_prompts((20, 30))):
        eng.submit(E.Request(rid=i, prompt=p, max_new=4))

    def boom():
        raise RuntimeError("tick lost")

    monkeypatch.setattr(eng, "_dispatch", boom)
    assert eng.step()  # the tick fails before it plans: FAILED retirements
    (t,) = _named(ring, "serve.tick")
    assert t.attrs == {"tick": 0, "replica": eng.replica_id, "kind": "failed",
                       "chunk": 0, "decoding": 0, "prefilling": 0,
                       "selected": 0, "prefill_rows": 0, "emitted": 0}
    assert eng.stats()["statuses"]["FAILED"] == 2


def test_request_spans_account_for_ttft(setup, ring):
    cfg, params = setup
    lens = (40, 150, 90)

    async def body():
        server = ServingServer(_engine(params, cfg, slots=2, max_len=192),
                               host="127.0.0.1", port=0)
        await server.start()
        try:
            while not server.ready:
                await asyncio.sleep(0.02)
            out = []
            for p in _prompts(lens):  # one at a time: rids 1, 2, 3
                out.append(await _sse_request(
                    server.host, server.port,
                    {"prompt": [int(t) for t in p], "max_new": 3}))
            code, raw = await _get(server.host, server.port, "/v1/stats")
            assert code == 200
            stats = json.loads(raw)
            # the ring's counters once, process-wide; compiles per engine
            assert set(stats["trace"]) == {"kept", "dropped", "compiles"}
            assert stats["trace"]["kept"] > 0
            assert stats["trace"]["dropped"] == 0
            assert stats["compiles"] == tracing.recorder().compiles_in(
                stats["replica_id"])
            return out
        finally:
            await server.drain_and_stop(5.0)

    recs = asyncio.run(body())
    assert [r["status"] for r in recs] == ["OK"] * 3
    by = {}
    for r in ring.records():
        if r.attrs.get("rid", 0) > 0:  # rid 0 is the server's warmup
            by.setdefault(r.attrs["rid"], {}).setdefault(r.name, []).append(r)
    assert sorted(by) == [1, 2, 3]
    for rid, client in zip((1, 2, 3), recs):
        spans = by[rid]
        assert len(spans["serve.prefill"]) == 1
        assert len(spans["serve.deliver"]) == 1
        assert len(spans["serve.queue"]) >= 1
        q, p, d = (spans[n][0] for n in ("serve.queue", "serve.prefill",
                                         "serve.deliver"))
        assert q.end_ns <= p.start_ns + 1 and p.end_ns == d.start_ns
        parts = sum(s.end_ns - s.start_ns for s in (q, p, d)) * 1e-9
        ttft = client["t_first"] - client["t_sent"]
        assert 0 < parts <= ttft
        assert set(spans) == {"serve.queue", "serve.prefill",
                              "serve.deliver"}
    assert _named(ring, "serve.drive.cmds")


def test_tick_spans_on_the_profiler_host_plane(setup, ring, tmp_path):
    from jax.profiler import ProfileData

    cfg, params = setup
    eng = _engine(params, cfg)
    _serve(eng, (30,), max_new=2)  # compile outside the trace
    ring.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(eng, MIXED, max_new=3)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    host = [e for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name == "serve.tick"]
    ticks = _named(ring, "serve.tick")
    assert len(host) == len(ticks) > 0
    host.sort(key=lambda e: e.start_ns)
    # anchor on the first event: the ring's clock and the trace's differ
    # by one offset
    off = ticks[0].start_ns - host[0].start_ns
    for t, e in zip(ticks, host):
        assert abs(t.start_ns - off - e.start_ns) < 0.5e6
        stats = dict(e.stats)
        for k in ("tick", "kind", "chunk", "decoding", "prefilling",
                  "selected", "prefill_rows", "emitted"):
            assert stats[k] == t.attrs[k], k


@pytest.mark.parametrize("kind", ["decode", "fused"])
def test_tick_programs_carry_named_scopes(setup, kind):
    cfg, params = setup
    eng = _engine(params, cfg, slots=2, max_len=128)
    args = (eng.params, eng.caches, eng.cur_tok, eng.pos, eng.done,
            eng.gen_count, eng.max_new_arr, jnp.zeros((2,), bool))
    if kind == "fused":
        c = eng.chunk_sizes[0]
        text = eng._get_fused(c).lower(
            *args, jnp.zeros((2, c), jnp.int32),
            jnp.full((2,), eng.trash_base, jnp.int32), jnp.zeros((2,), bool),
            jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
            None).as_text(debug_info=True)
    else:
        text = eng._serve.lower(
            eng.params, {"tokens": eng.cur_tok[:, None]}, eng.caches,
            eng.pos, None).as_text(debug_info=True)
    for scope in (f"tick/{kind}", "attn/qkv", "attn/kv_write", "attn/core",
                  "mlp", "head"):
        assert f"{scope}/" in text, scope
