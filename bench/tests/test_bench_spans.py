"""The program's spans as the benchmark reads them: ``serve.tick``'s
attributes agree tick for tick with the work ``harness.Ticks`` rebuilds
from outside, and each span reader gives its number on a hand-built ring,
or nothing where the ring dropped records in the window or the program
has no tracer."""

import dataclasses
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness

from repro.serving import tracing
from repro.serving.tracing import Span

WINDOW = (10.0, 20.0)  # perf_counter seconds
MS = 1_000_000  # ns


@pytest.fixture
def ring(monkeypatch):
    rec = tracing.Recorder(64)
    monkeypatch.setattr(tracing, "recorder", lambda: rec)
    return rec


def ctx(trace=WINDOW):
    win = harness.Window(t_open=WINDOW[0], t_close=WINDOW[1],
                         trace_host=trace)
    return harness.Ctx(cell=None, seconds=WINDOW[1] - WINDOW[0], setup_s=0.0,
                       streams=[], window=win, lateness=[])


def at(s: float) -> int:
    return int(s * 1e9)


def tick(rec, n, t0, launch, wait, t1, replica=None, **attrs):
    """One ``serve.tick`` with its launch and wait children (times in s)."""
    sid = 1000 * (n + 1) + (replica or 0)
    rec.add(Span("serve.tick.launch", at(launch[0]), at(launch[1]), sid + 1,
                  sid, {}))
    rec.add(Span("serve.tick.wait", at(wait[0]), at(wait[1]), sid + 2, sid,
                 {}))
    rec.add(Span("serve.tick", at(t0), at(t1), sid, None,
                 {"tick": n, "replica": replica, **attrs}))


def test_tick_attributes_equal_the_harness_reconstruction():
    from repro.configs import get_config
    from repro.core import params as P
    from repro.models import transformer as T
    from repro.serving import engine as E

    cfg = dataclasses.replace(get_config("tellme-0.7b", smoke=True),
                              dtype=jnp.float32)
    params = P.init_params(T.param_specs(cfg), jax.random.PRNGKey(0))
    eng = E.ServingEngine(params, cfg, slots=3, max_len=256, mode="eval",
                          eos_id=-2)
    ticks = harness.Ticks(eng)
    rec = tracing.configure(cfg.trace_ring_spans)
    rec.clear()
    rng = np.random.default_rng(0)
    # mixed chunk schedules (256 | 128 | 128+64 | 64): stalls and handoffs
    for i, n in enumerate((200, 70, 130, 20)):
        eng.submit(E.Request(rid=i, prompt=rng.integers(1, 200, n),
                             max_new=5))
    eng.run()
    spans = [r.attrs for r in rec.records() if r.name == "serve.tick"]
    rec.clear()
    assert len(spans) == len(ticks.records) == eng.tick_count
    for got, want in zip(spans, ticks.records):
        assert got["decoding"] == len(want["dec"])
        assert got["prefill_rows"] == sum(r for _, r in want["pre"])
        assert got["selected"] == len(want["pre"])
        assert got["emitted"] == want["emit"]
    assert any(a["prefilling"] > a["selected"] for a in spans)


def test_host_gap(ring):
    # replica 0: ticks 0-2 in the window; gaps 2 ms and 4 ms
    tick(ring, 0, 11.000, (11.001, 11.002), (11.002, 11.100), 11.101)
    tick(ring, 1, 11.101, (11.102, 11.103), (11.103, 11.200), 11.201)
    tick(ring, 2, 11.201, (11.204, 11.205), (11.205, 11.300), 11.301)
    # replica 1, tick 5 alone (no successor): no gap
    tick(ring, 5, 12.0, (12.001, 12.002), (12.002, 12.1), 12.1, replica=1)
    # a tick before the window opened does not count
    tick(ring, 9, 9.0, (9.001, 9.002), (9.002, 9.1), 9.1, replica=2)
    tick(ring, 10, 10.5, (10.501, 10.502), (10.502, 10.6), 10.6, replica=2)
    read = harness.reader("host_gap_ms.decode")
    assert read(ctx()) == pytest.approx(3.0)
    assert harness.reader("host_gap_ms.prefill") is not None
    # only ticks inside the traced span count: here ticks 1 and 2
    assert read(ctx(trace=(11.1005, 12.0))) == pytest.approx(4.0)
    assert read(ctx(trace=(11.2005, 12.0))) is None


def test_queue_wait_p50(ring):
    for rid, (a, b) in enumerate([(10.5, 10.6), (11.0, 11.2), (11.0, 11.3),
                                  (12.0, 12.05)]):
        ring.interval("serve.queue", at(a), at(b), rid=rid, replica=None,
                      requeued=False)
    ring.interval("serve.queue", at(13.0), at(14.0), rid=0, requeued=True)
    ring.interval("serve.queue", at(9.0), at(9.9), rid=9, requeued=False)
    assert harness.reader("queue_wait_p50_ms")(ctx()) == pytest.approx(150.0)


def test_admit_to_first_p50(ring):
    for rid, ms in enumerate([900, 1000, 850]):
        ring.interval("serve.prefill", at(12.0), at(12.0) + ms * MS, rid=rid)
    ring.interval("serve.prefill", at(19.5), at(20.5), rid=7)  # ends after
    assert harness.reader("admit_to_first_p50_ms")(ctx()) == \
        pytest.approx(900.0)


def test_deliver_p50(ring):
    for rid, ms in enumerate([2, 6, 3, 40]):
        ring.interval("serve.deliver", at(13.0), at(13.0) + ms * MS, rid=rid)
    ring.interval("serve.deliver", at(9.0), at(9.5), rid=8)  # ends before
    assert harness.reader("deliver_p50_ms")(ctx()) == pytest.approx(4.5)


def test_prefill_stall_share(ring):
    for n, (pre, sel) in enumerate([(3, 1), (2, 2), (1, 1), (0, 0)]):
        tick(ring, n, 11 + n, (11 + n, 11 + n), (11 + n, 11.5 + n),
             11.5 + n, prefilling=pre, selected=sel)
    assert harness.reader("prefill_stall_share")(ctx()) == \
        pytest.approx(100.0 * (1 - 4 / 6))
    ring.clear()
    tick(ring, 0, 11, (11, 11), (11, 11.5), 11.5, prefilling=0, selected=0)
    assert harness.reader("prefill_stall_share")(ctx()) is None


def test_prefill_stall_share_reads_a_failed_tick(monkeypatch):
    # a tick that fails before it plans still carries every attribute, so
    # the reader reads it (as no prefill work) and does not raise
    from repro.configs import get_config
    from repro.core import params as P
    from repro.models import transformer as T
    from repro.serving import engine as E

    cfg = dataclasses.replace(get_config("tellme-0.7b", smoke=True),
                              dtype=jnp.float32)
    params = P.init_params(T.param_specs(cfg), jax.random.PRNGKey(0))
    eng = E.ServingEngine(params, cfg, slots=2, max_len=128, mode="eval",
                          eos_id=-2)
    rec = tracing.configure(cfg.trace_ring_spans)
    rec.clear()
    rng = np.random.default_rng(1)
    t0 = time.perf_counter()
    for i, n in enumerate((100, 30)):  # both mid-prefill on the first tick
        eng.submit(E.Request(rid=i, prompt=rng.integers(1, 200, n),
                             max_new=3))
    eng.step()

    def boom():
        raise RuntimeError("tick lost")

    monkeypatch.setattr(eng, "_dispatch", boom)
    eng.step()
    win = harness.Window(t_open=t0, t_close=time.perf_counter() + 1.0)
    c = harness.Ctx(cell=None, seconds=1.0, setup_s=0.0, streams=[],
                    window=win, lateness=[])
    ticks = [r.attrs for r in rec.records() if r.name == "serve.tick"]
    share = harness.reader("prefill_stall_share")(c)
    rec.clear()
    assert [a["kind"] for a in ticks] == ["fused", "failed"]
    pre, sel = ticks[0]["prefilling"], ticks[0]["selected"]
    assert share == pytest.approx(100.0 * (1 - sel / pre))


NEW = ["host_gap_ms.decode", "queue_wait_p50_ms", "admit_to_first_p50_ms",
       "prefill_stall_share", "deliver_p50_ms"]


@pytest.mark.parametrize("name", NEW)
def test_dropped_records_in_the_window_read_nothing(ring, name):
    ring.resize(4)
    for n in range(3):
        tick(ring, n, 11 + n, (11.1 + n, 11.2 + n), (11.2 + n, 11.5 + n),
             11.6 + n, prefilling=2, selected=1)
        ring.interval("serve.queue", at(11 + n), at(11.1 + n), rid=n)
        ring.interval("serve.prefill", at(11.1 + n), at(11.5 + n), rid=n)
        ring.interval("serve.deliver", at(11.5 + n), at(11.51 + n), rid=n)
    assert ring.dropped and ring.lost_end_ns >= at(WINDOW[0])
    assert harness.reader(name)(ctx()) is None


@pytest.mark.parametrize("name", NEW)
def test_drops_before_the_window_still_read(ring, name):
    ring.resize(9)  # holds every record that ends in the window
    for i in range(20):  # all of these end before the window opens
        ring.interval("serve.queue", at(1 + i * 0.1), at(1.05 + i * 0.1))
    for n in range(2):
        tick(ring, n, 11 + n, (11.1 + n, 11.2 + n), (11.2 + n, 11.5 + n),
             11.6 + n, prefilling=2, selected=1)
    ring.interval("serve.queue", at(12.0), at(12.1), rid=1)
    ring.interval("serve.prefill", at(12.1), at(12.5), rid=1)
    ring.interval("serve.deliver", at(12.5), at(12.51), rid=1)
    assert ring.dropped and ring.lost_end_ns < at(WINDOW[0])
    assert harness.reader(name)(ctx()) is not None


@pytest.mark.parametrize("name", NEW)
def test_no_tracer_reads_nothing(monkeypatch, name):
    # a tree from before the tracer: the import fails, the reader is silent
    import repro.serving

    monkeypatch.delattr(repro.serving, "tracing")
    monkeypatch.setitem(sys.modules, "repro.serving.tracing", None)
    assert harness.reader(name)(ctx()) is None
