"""The process's span ring: always recording, in memory, on two clocks.

Every hot loop of the program (``Scheduler.step``, ``InferenceEngine``'s
calls, ``BaseTrainer.train_iter``, the ``Recorder``'s segments, the
prefetcher's dequeue) opens its spans here, once.  A span is

- a record in a bounded ring (:data:`RING_SIZE` records, newest kept):
  ``id``, ``parent`` (the span open on the same thread when it started),
  ``name``, ``t0``/``t1`` (``time.perf_counter()``), a small dict of
  ``tags``, and ``seq``, its place in the ring's whole history;
- a ``jax.profiler.TraceAnnotation`` of the same name: nothing while no
  profiler trace runs, a host event on the profiler's clock, beside the
  device's ops, while one does.

Closing a span is two clock reads, one append and the annotation's exit:
no lock, no I/O, no thread.  A :class:`~theanompi_tpu.telemetry.core
.Telemetry`, where one was constructed, subscribes and writes each closed
record to its sink; with none the subscriber list is empty.

``jax`` is never imported from here (the telemetry package stays
importable without it): the annotation class and the ``jit.build``
listener are picked up the first time a span opens in a process that has
imported jax already.
"""

from __future__ import annotations

import itertools
import sys
import threading
import time
from collections import deque

RING_SIZE = 65536

#: the instant a ``jax.monitoring`` duration listener leaves for every
#: phase of building a program, parented to the span it happened under
JIT_BUILD = "jit.build"
#: jax's duration events -> the ``phase`` tag.  ``cache_load`` is the
#: persistent cache's retrieval and lies INSIDE ``compile_or_load``: sum
#: the first three for a program's whole build.  A ``trace`` that ran
#: inside another trace (an inner jit, a kernel's body) is tagged
#: ``nested``: its seconds are part of the outer one's
JIT_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile_or_load",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}


class Span:
    """One record of the ring, and the handle that closes it.

    ``with ring.span(name, **tags)`` opens on entry; ``ring.begin(...)``
    returns it open, for call sites that are a start and a stop.  An
    instant (``ring.mark``) is a record whose ``t1`` equals its ``t0``
    and whose ``instant`` is true.
    """

    __slots__ = ("seq", "id", "parent", "name", "t0", "t1", "tags",
                 "instant", "_ring", "_ann")

    def __init__(self, ring: "SpanRing", name: str, tags: dict):
        self._ring = ring
        self.name = name
        self.tags = tags
        self.seq = self.id = self.parent = self.t0 = self.t1 = None
        self.instant = False
        self._ann = None

    @property
    def dur(self) -> float:
        return self.t1 - self.t0

    def tag(self, **tags) -> None:
        """Add tags to a span that is still open."""
        self.tags.update(tags)

    def __enter__(self) -> "Span":
        ring = self._ring
        stack = ring._stack()
        self.id = next(ring._ids)
        self.parent = stack[-1].id if stack else None
        stack.append(self)
        annotation = _ANNOTATION or _find_jax()
        if annotation is not None:
            self._ann = annotation(self.name)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def end(self, fence=None, **tags) -> float:
        """Close and record, once; -> duration.  ``fence``: a jax array to
        block on before the close is stamped (a span around jax work
        measures dispatch unless something fences).  A second call, or the
        ``with`` block's exit after a manual ``end``, does nothing."""
        if self.t1 is not None:
            return 0.0
        if fence is not None:
            try:
                sys.modules["jax"].block_until_ready(fence)
            except BaseException as e:
                tags["error"] = type(e).__name__
                self._close(tags)
                raise
        return self._close(tags)

    def cancel(self) -> None:
        """Leave the span without a record (a wait that ended in
        ``StopIteration``, not in a batch)."""
        if self.t1 is None:
            self.t1 = self.t0
            self._leave()

    def _leave(self) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        stack = self._ring._stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:  # a start/stop pair closed out of order
            stack.remove(self)

    def _close(self, tags: dict) -> float:
        self.t1 = time.perf_counter()
        self._leave()
        if tags:
            self.tags.update(tags)
        self._ring._append(self)
        return self.t1 - self.t0

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None and self.t1 is None:
            self.tags["error"] = exc_type.__name__
        self.end()


class SpanRing:
    """A bounded ring of closed spans and instants, with per-thread
    parenting.  The process has one (:data:`RING`); tests make their own."""

    def __init__(self, maxlen: int = RING_SIZE):
        self._records: deque = deque(maxlen=maxlen)
        self._ids = itertools.count(1)
        self._seqs = itertools.count()
        self._open = threading.local()
        # replaced whole on (un)subscribe, so the append path reads it
        # without a lock
        self._subscribers: tuple = ()
        self._lock = threading.Lock()

    def _stack(self) -> list:
        try:
            return self._open.stack
        except AttributeError:
            stack = self._open.stack = []
            return stack

    def _append(self, record: Span) -> None:
        record.seq = next(self._seqs)
        self._records.append(record)
        for fn in self._subscribers:
            fn(record)

    # -- writers -------------------------------------------------------------
    def span(self, name: str, **tags) -> Span:
        return Span(self, name, tags)

    def begin(self, name: str, **tags) -> Span:
        return Span(self, name, tags).__enter__()

    def mark(self, name: str, **fields) -> Span:
        """An instant with its values, under the span open on this thread."""
        return self.record(name, time.perf_counter(), None, **fields)

    def record(self, name: str, t0: float, t1: float | None, **tags) -> Span:
        """A span that is already over (``t1`` None: an instant)."""
        rec = Span(self, name, tags)
        stack = self._stack()
        rec.id = next(self._ids)
        rec.parent = stack[-1].id if stack else None
        rec.t0 = t0
        rec.instant = t1 is None
        rec.t1 = t0 if t1 is None else t1
        self._append(rec)
        return rec

    # -- readers -------------------------------------------------------------
    def snapshot(self) -> list[Span]:
        """The records held, oldest first."""
        return list(self._records)

    def dropped(self) -> int:
        """How many records the ring has let go of.  A reader that needs a
        whole window refuses to read once this is not 0: ``seq`` of the
        oldest record held says where the ring's memory starts."""
        # a few records deep: two threads may append out of ``seq`` order
        head = itertools.islice(self._records, 8)
        return min((r.seq for r in head), default=0)

    def clear(self) -> None:
        """Forget every record and restart ``seq`` (tests; a reader that
        wants a window of its own)."""
        self._records.clear()
        self._seqs = itertools.count()

    def subscribe(self, fn) -> None:
        """``fn(record)`` on every record closed from now on, on the thread
        that closed it."""
        with self._lock:
            self._subscribers = (*self._subscribers, fn)

    def unsubscribe(self, fn) -> None:
        with self._lock:
            self._subscribers = tuple(s for s in self._subscribers
                                      if s != fn)


#: the process's ring
RING = SpanRing()
span, begin, mark, record = RING.span, RING.begin, RING.mark, RING.record
snapshot, dropped = RING.snapshot, RING.dropped
subscribe, unsubscribe = RING.subscribe, RING.unsubscribe

_ANNOTATION = None
_HOOKS_LOCK = threading.Lock()


def _find_jax():
    """``jax.profiler.TraceAnnotation`` once the process has jax (None
    until then), and, the first time, the ``jit.build`` listener."""
    global _ANNOTATION
    jax = sys.modules.get("jax")
    try:
        annotation = jax.profiler.TraceAnnotation
        monitoring = jax.monitoring
        register = monitoring.register_event_duration_secs_listener
    except AttributeError:  # no jax (yet), or still importing
        return None
    with _HOOKS_LOCK:
        if _ANNOTATION is None:
            register(_on_jax_duration)
            monitoring.register_scalar_listener(_on_jax_scalar)
            _ANNOTATION = annotation
    return _ANNOTATION


_TRACE_EVENT = next(e for e, p in JIT_PHASES.items() if p == "trace")
_tracing = threading.local()  # .depth: traces open on this thread


def _on_jax_scalar(event: str, value, **kw) -> None:
    """jax reports a phase's start as a scalar under the phase's name."""
    if event == _TRACE_EVENT:
        _tracing.depth = getattr(_tracing, "depth", 0) + 1


def _on_jax_duration(event: str, seconds: float, **kw) -> None:
    phase = JIT_PHASES.get(event)
    if phase is None:
        return
    tags = {"phase": phase, "seconds": float(seconds),
            "fn": str(kw.get("fun_name", ""))}
    if event == _TRACE_EVENT:
        _tracing.depth = depth = max(getattr(_tracing, "depth", 1) - 1, 0)
        if depth:
            tags["nested"] = True
    RING.mark(JIT_BUILD, **tags)


_find_jax()  # a process that imported jax first is listened to from here on
