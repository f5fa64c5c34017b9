"""Spans and counters of the serving path: one process-wide recorder.

Each span is recorded to two places (DESIGN.md §tracing):

* a bounded in-memory ring of :class:`Span` records ``(name, start_ns,
  end_ns, span_id, parent_id, attrs)``, timed with
  ``time.perf_counter_ns()``. The ring holds ``cfg.trace_ring_spans``
  records (0 turns recording off); when full it drops the oldest record
  and counts it;
* for a span that opens and closes on one thread (:meth:`Recorder.span`),
  a ``jax.profiler.TraceAnnotation`` whose event stats are the span's
  attributes, so it lands on the profiler's host plane, on the device
  trace's clock. Outside a profiler session that costs next to nothing.

A span that crosses threads (a request's queue wait, prefill and
delivery) is recorded once it has ended, with :meth:`Recorder.interval`;
it goes to the ring only. A ``jax.monitoring`` listener counts every
backend compile under the ``replica`` of the ``serve.tick`` it ran inside
(``None`` outside a tick).

Attributes carry ``rid`` on request spans and ``replica`` on every span,
so the replicas of a pool stay apart. ``recorder()`` is the process-wide
instance; whoever owns the serving process (``ServingServer``,
``ReplicaPool``) sizes it once with :func:`configure`. Until then the ring
holds nothing.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import NamedTuple

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    span_id: int
    parent_id: int | None
    attrs: dict


def _stats(attrs: dict) -> dict:
    """Attributes as profiler event stats (a stat cannot be None)."""
    return {k: v for k, v in attrs.items() if v is not None}


class OpenSpan:
    """A span being recorded, used as a context manager. ``set`` adds
    attributes known only at its end; ``discard`` keeps it out of the ring
    (the profiler event, already open, still closes)."""

    __slots__ = ("_rec", "name", "attrs", "span_id", "parent_id",
                 "start_ns", "end_ns", "_ann", "_keep")

    def __init__(self, rec: "Recorder", name: str, attrs: dict):
        self._rec = rec
        self.name = name
        self.attrs = attrs
        self._keep = True
        self.end_ns = None

    def __enter__(self) -> "OpenSpan":
        stack = self._rec._stack()
        self.parent_id = stack[-1].span_id if stack else None
        self.span_id = next(self._rec._ids)
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name,
                                                 **_stats(self.attrs))
        self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)
        self._ann.set_metadata(**_stats(attrs))

    def discard(self) -> None:
        self._keep = False

    def __exit__(self, *exc) -> None:
        self.end_ns = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        self._rec._stack().pop()
        if self._keep:
            self._rec.add(Span(self.name, self.start_ns, self.end_ns,
                                self.span_id, self.parent_id, self.attrs))

    @property
    def duration_s(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


class Recorder:
    """The ring of spans plus its counters: ``dropped`` (records pushed out
    of a full ring), ``lost_end_ns`` (the latest end among them) and
    ``compiles`` (backend compiles seen, by the replica whose tick ran
    them)."""

    def __init__(self, capacity: int = 0):
        self._lock = threading.Lock()
        self._local = threading.local()
        self._ids = itertools.count(1)
        self.capacity = int(capacity)
        self._ring: collections.deque = collections.deque()
        self.dropped = 0
        self.lost_end_ns = -1
        self.compiles: collections.Counter = collections.Counter()

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def add(self, rec: Span) -> None:
        """Put a finished record in the ring."""
        if not self.capacity:
            return
        with self._lock:
            if len(self._ring) >= self.capacity:
                old = self._ring.popleft()
                self.dropped += 1
                self.lost_end_ns = max(self.lost_end_ns, old.end_ns)
            self._ring.append(rec)

    def resize(self, capacity: int) -> None:
        """Hold at most ``capacity`` records (0: record nothing), keeping
        the newest; records pushed out count as dropped."""
        with self._lock:
            self.capacity = int(capacity)
            while len(self._ring) > self.capacity:
                old = self._ring.popleft()
                self.dropped += 1
                self.lost_end_ns = max(self.lost_end_ns, old.end_ns)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.dropped = 0
            self.lost_end_ns = -1
            self.compiles.clear()

    def span(self, name: str, **attrs) -> OpenSpan:
        """A nested span on this thread (a context manager)."""
        return OpenSpan(self, name, attrs)

    def interval(self, name: str, t0_ns: int, t1_ns: int, **attrs) -> None:
        """A span that has ended, timed elsewhere (perf_counter ns)."""
        self.add(Span(name, int(t0_ns), int(t1_ns), next(self._ids), None,
                       attrs))

    def records(self) -> list[Span]:
        with self._lock:
            return list(self._ring)

    def lost_since(self, t_ns: int) -> bool:
        """Whether the ring dropped a record that ended at or after
        ``t_ns``: the records from ``t_ns`` on are then incomplete."""
        return self.lost_end_ns >= t_ns

    def stats(self) -> dict:
        """The process-wide counters: records kept and dropped, and every
        backend compile."""
        with self._lock:
            return {"kept": len(self._ring), "dropped": self.dropped,
                    "compiles": sum(self.compiles.values())}

    def compiles_in(self, replica) -> int:
        """Backend compiles that ran inside ``replica``'s ticks."""
        with self._lock:
            return self.compiles[replica]

    def on_event(self, event: str, duration_s: float, **_kw) -> None:
        """``jax.monitoring`` duration listener: counts a backend compile
        under the replica of the ``serve.tick`` open on this thread."""
        if event != COMPILE_EVENT:
            return
        tick = next((s.attrs for s in reversed(self._stack())
                     if s.name == "serve.tick"), {})
        with self._lock:
            self.compiles[tick.get("replica")] += 1


_RECORDER = Recorder()
_LISTEN_LOCK = threading.Lock()
_listening = False


def recorder() -> Recorder:
    return _RECORDER


def configure(capacity: int) -> Recorder:
    """Size the process-wide ring (``cfg.trace_ring_spans``) and listen for
    backend compiles (once). Called by the owner of the serving process,
    not by each engine: the ring is shared by every engine in it."""
    global _listening
    if _RECORDER.capacity != capacity:
        _RECORDER.resize(capacity)
    with _LISTEN_LOCK:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _RECORDER.on_event)
            _listening = True
    return _RECORDER


def span(name: str, **attrs) -> OpenSpan:
    return _RECORDER.span(name, **attrs)


def interval(name: str, t0_ns: int, t1_ns: int, **attrs) -> None:
    _RECORDER.interval(name, t0_ns, t1_ns, **attrs)
