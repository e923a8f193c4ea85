"""Telemetry front-end: the sink of the span ring, plus instants, counters,
gauges and metric flushes.

Design rules (ISSUE 1, restated by ISSUE 25):

- **Off means no sink.**  The hot loops record their spans in the
  process's in-memory ring (:mod:`theanompi_tpu.telemetry.spans`) whether
  or not a ``Telemetry`` exists, as the ``Recorder`` and the scheduler's
  latency lists always have.  A run that configured no directory
  constructs no ``Telemetry`` and writes nothing: no sink, no file, no
  thread (asserted by the tests).  A ``Telemetry`` that is constructed
  subscribes to the ring and writes each closed span as one ``span`` event.
- **Honest under async dispatch.**  A span around jax work measures
  *dispatch* unless something fences.  Spans accept the same optional
  ``fence`` the Recorder uses: ``end(fence=x)`` blocks on the array before
  stamping the close time.  The Recorder's segments ARE ring spans, so
  recorder spans and recorder histories are the same numbers by
  construction.
- **Monotonic time.**  All timestamps are ``time.perf_counter()``; the one
  wall-clock anchor is an ISO string in the session ``meta`` event.  While
  a ``jax.profiler`` trace runs, every ring span is also a
  ``TraceAnnotation`` on the profiler's clock.
"""

from __future__ import annotations

import os
import socket
import threading
import time
import weakref
from datetime import datetime, timezone

# analysis.interleave is stdlib-only and sits at the bottom of the
# import DAG — the one non-telemetry import the leaf wall permits
from theanompi_tpu.analysis.interleave import sp
from theanompi_tpu.telemetry import spans
from theanompi_tpu.telemetry.metrics import MetricsRegistry
from theanompi_tpu.telemetry.sink import EventSink
from theanompi_tpu.telemetry.spans import Span


class Telemetry:
    """One per process: owns the rank's sink and metrics registry."""

    def __init__(self, directory: str, rank: int | None = None,
                 host: str | None = None, max_bytes: int = 32 * 2**20,
                 keep: int = 3, health=None, flight_recorder: int = 0,
                 profile=None):
        if rank is None:
            try:
                import jax

                rank = jax.process_index()
            except Exception:  # lint: swallow-ok — pre-init default rank
                rank = 0
        self.rank = rank
        self.host = host or socket.gethostname()
        self.directory = directory
        self.sink = EventSink(directory, rank=rank, max_bytes=max_bytes,
                              keep=keep)
        self.metrics = MetricsRegistry()
        # ISSUE 13 live health: both default OFF — a Telemetry constructed
        # the pre-13 way makes zero health/flight-recorder calls (same
        # off-means-off contract the trainer holds for telemetry itself).
        # ``health`` accepts True (defaults), a HealthConfig, or a dict of
        # HealthConfig overrides; ``flight_recorder`` is the ring capacity.
        self.flight = None
        self.health = None
        self.prof = None
        self._health_stop: threading.Event | None = None
        self._health_thread: threading.Thread | None = None
        if flight_recorder:
            from theanompi_tpu.telemetry.flight_recorder import FlightRecorder

            self.flight = FlightRecorder(directory,
                                         capacity=int(flight_recorder),
                                         rank=rank)
        if health:
            from theanompi_tpu.telemetry.health import (HealthConfig,
                                                        HealthMonitor)

            cfg = (health if isinstance(health, HealthConfig)
                   else HealthConfig(**health) if isinstance(health, dict)
                   else HealthConfig())
            self.health = HealthMonitor(directory, cfg, rank=rank)
            self._health_stop = threading.Event()
            self._health_thread = threading.Thread(
                target=self._health_loop, name="telemetry-health",
                daemon=True)
            self._health_thread.start()
        if profile:
            # ISSUE 16 step attribution: same off-means-off contract — a
            # Telemetry constructed the pre-16 way makes zero calls here
            from theanompi_tpu.telemetry.profile import StepAttributor

            self.prof = StepAttributor(directory, rank=rank)
        self.emit("meta", "session",
                  wall_time=datetime.now(timezone.utc).isoformat(),
                  host=self.host, pid=os.getpid())
        # weakly: a Telemetry dropped without close() must not keep
        # writing (or living) because the ring remembers it
        self._on_record = _weak_subscriber(self)
        spans.subscribe(self._on_record)

    # -- raw emission --------------------------------------------------------
    def emit(self, kind: str, name: str, ts: float | None = None,
             **fields) -> None:
        event = {"ts": time.perf_counter() if ts is None else ts,
                 "kind": kind, "name": name, "rank": self.rank}
        event.update(fields)
        self.sink.emit(event)
        if self.flight is not None:
            self.flight.record(event)
        if self.health is not None:
            self.health.observe(event)
        if self.prof is not None:
            self.prof.observe(event)

    def _write_record(self, rec: Span) -> None:
        """The ring's subscriber: one event per closed span or instant, on
        the thread that closed it."""
        if rec.instant:
            self.emit("instant", rec.name, ts=rec.t0, id=rec.id,
                      parent=rec.parent, **rec.tags)
        else:
            self.emit("span", rec.name, ts=rec.t0, dur=rec.t1 - rec.t0,
                      tid=threading.get_ident(), id=rec.id,
                      parent=rec.parent, **rec.tags)

    # -- user surface --------------------------------------------------------
    # thin fronts of the ring for callers outside the hot loops
    # (checkpoint, validate, resilience): the span reaches this sink, and
    # any other, through the subscription
    def emit_span(self, name: str, t0: float, dur: float, **tags) -> None:
        spans.record(name, t0, t0 + dur, **tags)

    def span(self, name: str, **tags) -> Span:
        return spans.span(name, **tags)

    def instant(self, name: str, **fields) -> None:
        self.emit("instant", name, **fields)

    def count(self, name: str, value: float = 1.0, emit: bool = False,
              **tags) -> None:
        """Increment a counter.  By default accumulation-only (no I/O) —
        totals ride the next ``flush_metrics``; ``emit=True`` also writes a
        counter event now (used for one-per-exchange accounting)."""
        total = self.metrics.count(name, value)
        if emit:
            self.emit("counter", name, value=value, total=total, **tags)

    def gauge(self, name: str, value: float, **tags) -> None:
        """Set a gauge: registry (for the snapshot) + one gauge event.
        Gauges are set at flush boundaries, never per-iteration, so the
        event write is off the hot path."""
        self.metrics.gauge(name, value)
        self.emit("gauge", name, value=float(value), **tags)

    def observe(self, name: str, value: float) -> None:
        self.metrics.observe(name, value)

    def flush_metrics(self, step: int | None = None, **extra) -> None:
        """One ``metrics`` event carrying the registry snapshot."""
        snap = self.metrics.snapshot()
        if step is not None:
            snap["step"] = step
        snap.update(extra)
        self.emit("metrics", "metrics", **snap)

    def profile_flush(self, step: int | None = None) -> None:
        """Publish the attribution gauges + HBM watermarks and refresh
        ``ATTRIB.json`` — called at the trainer's fenced print boundary
        (ISSUE 16).  No-op unless ``profile=`` was configured.

        Gauge values are computed before any emission, so the attributor
        never holds its lock across an emit (lock-order discipline: no
        nesting with the sink's lock).
        """
        if self.prof is None:
            return
        gauges = dict(self.prof.gauges())
        gauges.update(self.prof.sample_memory())
        for name, value in gauges.items():
            self.gauge(name, value, step=step)
        try:
            self.prof.write()
        except OSError:
            pass  # lint: swallow-ok — advisory file; next flush retries

    def export_chrome_trace(self, path: str | None = None) -> str:
        """Write this rank's events as a Chrome trace-event JSON file."""
        from theanompi_tpu.telemetry.chrome_trace import export_chrome_trace
        from theanompi_tpu.telemetry.sink import sink_files

        path = path or os.path.join(self.directory,
                                    f"trace-rank{self.rank:05d}.json")
        return export_chrome_trace(
            sink_files(self.directory, rank=self.rank), path)

    # -- live health (ISSUE 13) ----------------------------------------------
    def _health_loop(self) -> None:
        """Daemon ticker: exists only when health is enabled.  Runs the
        time-based detectors and republishes ``HEALTH.json`` even while
        the main thread is wedged — which is exactly when the hang
        verdict matters."""
        while not self._health_stop.wait(self.health.config.tick_s):
            self._health_tick()

    def _health_tick(self) -> None:
        from theanompi_tpu.telemetry.metrics import HEALTH_INSTANTS

        sp("health.tick")
        changed = self.health.tick()
        for v in changed:
            # mirror severity *transitions* into the event stream (the
            # tick released the monitor's lock before we emit, so the
            # observe() this triggers cannot deadlock)
            self.instant(HEALTH_INSTANTS[1], detector=v.detector,
                         severity=v.severity, reason=v.reason)
        if self.flight is not None and any(
                v.detector == "hang" and v.severity == "critical"
                for v in changed):
            # last words while still alive: the supervisor answers a
            # critical hang with SIGKILL, which a wedged process cannot
            # dump under — so the ticker dumps the moment the verdict
            # turns, leaving the blackbox the harvest expects
            try:
                self.flight.dump("hang", health=self.health.verdicts())
            except OSError:
                pass  # lint: swallow-ok — advisory file; verdict stands
        try:
            self.health.write()
        except OSError:
            pass  # lint: swallow-ok — advisory file; next tick retries

    def close(self) -> None:
        sp("health.close")
        spans.unsubscribe(self._on_record)
        if self._health_thread is not None:
            self._health_stop.set()
            self._health_thread.join(timeout=5.0)
            self._health_thread = None
        self.flush_metrics()
        self.emit("meta", "session_end")
        if self.prof is not None:
            # final attribution summary: the per-run artifact the perf
            # ledger ingests (written before the sink closes so the last
            # buffered spans are counted)
            try:
                self.prof.write()
            except OSError:
                pass  # lint: swallow-ok — advisory file at shutdown
        if self.health is not None:
            # final publish AFTER session_end so the file's last word is
            # the disarmed, end-of-run state
            self.health.tick()
            try:
                self.health.write()
            except OSError:
                pass  # lint: swallow-ok — advisory file at shutdown
        self.sink.close()


def _weak_subscriber(tel: Telemetry):
    ref = weakref.ref(tel)

    def on_record(rec: Span) -> None:
        live = ref()
        if live is None:
            spans.unsubscribe(on_record)
        else:
            live._write_record(rec)

    return on_record
