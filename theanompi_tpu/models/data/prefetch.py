"""Background prefetch: the para_load equivalent (compute/input overlap).

Reference (unverified — SURVEY.md §2.1/§3.5): ``models/data/proc_load_mpi.py``
— each worker spawned a loader child via ``MPI.COMM_SELF.Spawn`` that read and
augmented the next ``.hkl`` batch while the GPU computed, handing batches over
an intercommunicator; the worker's ``train_iter`` "wait" segment measured the
residual stall.

TPU-native re-expression: no child processes or IPC — a daemon thread drains
the (numpy-producing, possibly augmenting) batch iterator into a small
bounded queue and eagerly ``device_put``s each batch onto the mesh, so host
read/augment/transfer overlaps device compute.  jax dispatch is async and
``device_put`` is thread-safe, which is all the machinery the reference's
process dance existed to obtain.  The trainer's "wait" segment still measures
the residual stall, keeping the Recorder's calc/comm/wait split comparable.
"""

from __future__ import annotations

import queue
import threading
import time

from theanompi_tpu.telemetry import spans
from theanompi_tpu.telemetry.metrics import PREFETCH_SPANS
from theanompi_tpu.utils.helper_funcs import shard_batch

(_SPAN_DEQUEUE,) = PREFETCH_SPANS

_END = object()


class PrefetchStallError(RuntimeError):
    """The source iterator produced nothing for ``stall_timeout`` seconds
    (ISSUE 4): the training thread gets a diagnosable error instead of a
    silent eternal block in ``queue.get`` — which a supervisor can restart
    and a watchdog would otherwise only catch by its coarser no-progress
    threshold."""


class Prefetcher:
    """Iterate ``it`` on a daemon thread, ``depth`` batches ahead.

    ``mesh`` set → batches are shard_batch'd (device transfer included in the
    overlap) and arrive as jax arrays; ``mesh=None`` → raw host batches.
    An exception in the source iterator is re-raised at the consuming site.

    ``stall_timeout`` (seconds, default None = block forever as before)
    bounds how long ``__next__`` waits on an empty queue before raising
    :class:`PrefetchStallError`.  ``fault_plan`` enables the deterministic
    ``prefetch:stall@N`` / ``prefetch:raise@N`` injection sites inside the
    worker (N = source batch ordinal).

    Checkpointable position (ISSUE 10): ``start_batch`` declares the global
    batch index of the FIRST item ``it`` will yield (the caller built the
    source fast-forwarded to that cursor), so fault-site ordinals stay
    global batch indices across a resume.  :meth:`state` reports
    ``consumed`` — the index of the first batch the *consumer* has not been
    handed yet.  Batches sitting in the queue (produced, possibly
    device-resident, but never returned from ``__next__``) are excluded by
    construction: a restore from this snapshot resumes at the first
    unconsumed batch, replaying nothing and skipping nothing.
    """

    def __init__(self, it, mesh=None, depth: int = 2, spec=None,
                 telemetry=None, stall_timeout: float | None = None,
                 fault_plan=None, start_batch: int = 0):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        if stall_timeout is not None and stall_timeout <= 0:
            raise ValueError(
                f"prefetch stall_timeout must be positive or None, "
                f"got {stall_timeout}")
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._it = it
        # optional telemetry sink (the prefetch.stall instant).  Each
        # dequeue is a span of the process's ring with the residual queue
        # depth, so a starving pipeline is visible in the trace as long
        # prefetch.dequeue spans at qsize 0
        self._telemetry = telemetry
        self._stall_timeout = stall_timeout
        self._err: BaseException | None = None
        self._stop = threading.Event()
        self._consumed = int(start_batch)

        def put(item) -> bool:
            """put that gives up when the consumer closed us."""
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def work():
            try:
                for i, item in enumerate(it, start=int(start_batch)):
                    if fault_plan is not None:
                        action = fault_plan.fire("prefetch", i)
                        if action == "stall":
                            # a hung source: produce nothing until closed
                            # (the consumer's stall_timeout is the witness)
                            while not self._stop.wait(0.05):
                                pass
                            return
                        if action == "raise":
                            from theanompi_tpu.resilience.faults import (
                                FaultInjected,
                            )

                            raise FaultInjected(
                                f"injected source failure at batch {i}")
                    if self._stop.is_set():
                        return
                    if mesh is not None:
                        item = shard_batch(mesh, item, spec=spec)
                    if not put(item):
                        return
            except BaseException as e:  # propagate to consumer
                self._err = e
            finally:
                put(_END)

        self._thread = threading.Thread(target=work, name="data-prefetch",
                                        daemon=True)
        self._thread.start()

    def __iter__(self):
        return self

    def _get(self):
        """Dequeue honoring ``stall_timeout`` (None = block forever)."""
        if self._stall_timeout is None:
            return self._q.get()
        deadline = time.perf_counter() + self._stall_timeout
        while True:
            remaining = deadline - time.perf_counter()
            if remaining <= 0:
                if self._telemetry is not None:
                    self._telemetry.instant(
                        "prefetch.stall", timeout_s=self._stall_timeout)
                raise PrefetchStallError(
                    f"no batch from the source iterator for "
                    f"{self._stall_timeout:g}s (loader thread alive: "
                    f"{self._thread.is_alive()}) — data pipeline stalled")
            try:
                # short slices so a concurrent close() is noticed promptly
                return self._q.get(timeout=min(0.25, remaining))
            except queue.Empty:
                continue

    def __next__(self):
        span = spans.begin(_SPAN_DEQUEUE)
        try:
            item = self._get()
        except BaseException as e:  # a stall stays visible, as long as it was
            span.end(error=type(e).__name__)
            raise
        if item is _END:
            span.cancel()  # the end of the epoch is not a batch
            self._thread.join()
            if self._err is not None:
                raise self._err
            raise StopIteration
        span.end(qsize=self._q.qsize())
        self._consumed += 1
        return item

    def state(self) -> dict:
        """Restart snapshot: ``consumed`` is the global index of the first
        batch the consumer has NOT received — in-flight queued batches are
        not counted, so restoring here neither replays nor skips data."""
        return {"consumed": self._consumed}

    def close(self) -> None:
        """Release the worker, drop queued (device-resident) batches, and
        CLOSE the source generator.

        Without this, an abandoned iterator leaves the thread blocked on a
        full queue with `depth` global batches pinned in HBM for the life
        of the process — and a generator-backed source (the shm worker
        ring holds its epoch lock while suspended at yield) would stay
        open until GC, blocking the next epoch.
        """
        self._stop.set()
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=5)
        if self._thread.is_alive():
            # closing a generator mid-execution from another thread raises,
            # so we cannot free the source here — say so instead of leaving
            # a silent mystery (a held shm-pool epoch lock surfaces later
            # as "already serving an epoch")
            import warnings

            warnings.warn(
                "Prefetcher.close(): worker still inside the source "
                "iterator after 5s; source generator left open",
                RuntimeWarning,
                stacklevel=2,
            )
            return
        close = getattr(self._it, "close", None)
        if close:
            close()


def prefetch(it, mesh=None, depth: int = 2, spec=None, telemetry=None,
             stall_timeout: float | None = None, fault_plan=None,
             start_batch: int = 0):
    """``depth=0`` disables prefetching (pass-through), else wraps in a
    :class:`Prefetcher`."""
    if depth == 0:
        return it
    return Prefetcher(it, mesh=mesh, depth=depth, spec=spec,
                      telemetry=telemetry, stall_timeout=stall_timeout,
                      fault_plan=fault_plan, start_batch=start_batch)
