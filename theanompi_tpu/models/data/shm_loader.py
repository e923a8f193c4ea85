"""Persistent process-parallel shard loader with shared-memory handoff.

The reference's ``para_load`` (SURVEY.md §3.5) was a long-lived loader
process filling pinned buffers behind a socket handshake so the GPU never
waited on JPEG/crop work.  This is its host-side analogue for the TPU
runtime: N worker processes each load one shard, run the C crop/mirror
kernel and the within-shard shuffle, and write the result straight into a
slot of one ``multiprocessing.shared_memory`` ring — no pickling of image
tensors (a plain ``Pool.imap`` pipes ~19 MB per shard through pickle and
measured SLOWER than inline; the ring costs one parent-side memcpy).

Design constraints this encodes:

- **spawn, not fork**: the parent is a JAX process with live XLA/dispatch
  threads; forking it risks the classic held-lock deadlock (Python warns
  exactly this).  Spawned workers start a fresh interpreter and re-import
  the data package (which imports jax but never opens a backend — the
  parent holds the chip, a worker must not ask for it), which is why the
  pool is **persistent**: created once per dataset, reused every epoch,
  closed by ``Dataset.cleanup()``.
- **slot flow control**: a slot is handed to a worker only after the
  consumer finished with it, so the ring bounds memory however far the
  workers run ahead.
- **determinism**: results are re-ordered to shard order and each task
  carries its own seed, so a fixed seed list reproduces the stream
  bit-for-bit regardless of worker timing.
"""

from __future__ import annotations

import multiprocessing as mp
import threading

import numpy as np


def _worker(task_q, result_q, shm_name, slot_nbytes, image_size):
    from multiprocessing import shared_memory

    from theanompi_tpu.models.data.imagenet import (
        _load_from_spec,
        random_crop_mirror,
    )

    shm = shared_memory.SharedMemory(name=shm_name)
    try:
        while True:
            task = task_q.get()
            if task is None:
                return
            idx, spec, seed, slot = task
            if spec[0] == "tokens":
                # token mode (stream.py warm-load): read one flat int32
                # token shard into the slot — no augmentation, no labels
                from theanompi_tpu.models.data.stream import (
                    load_token_shard,
                )

                toks = load_token_shard(spec[1])
                out = np.ndarray(toks.shape, np.int32,
                                 buffer=shm.buf[slot * slot_nbytes:])
                out[:] = toks
                result_q.put((idx, slot, toks.shape, "int32", None))
                continue
            x, y = _load_from_spec(spec)
            rng = np.random.RandomState(seed)
            x = random_crop_mirror(x, image_size, rng)
            per = rng.permutation(len(x))
            x, y = x[per], y[per]
            out = np.ndarray(x.shape, np.uint8,
                             buffer=shm.buf[slot * slot_nbytes:])
            out[:] = x
            # lint: donated-escape-ok — y is fancy-indexed above (y[per]):
            # a fresh host-owned array, never a device-buffer view
            result_q.put((idx, slot, x.shape, "uint8", np.asarray(y)))
    finally:
        shm.close()


class ShmShardPool:
    """Reusable worker ring: ``run(tasks)`` yields one epoch's augmented
    (x, y) shards in order; ``close()`` tears the workers down.

    ``tasks``: list of (spec, seed) with specs from
    ``_ShardSet.spec``/``_SyntheticShards.spec``, or ``("tokens", path)``
    specs (token shards for ``stream.py`` — yielded as (int32 tokens,
    None)).  Yielded ``x`` arrays are fresh copies (the ring slot is
    recycled immediately).  One epoch at a time: a second ``run`` while
    one is active raises (close the first generator — the prefetcher
    does).

    ``slot_nbytes`` overrides the image-shard slot-size formula for
    non-image payloads (the token mode).
    """

    def __init__(self, image_size: int, shard_size: int, workers: int,
                 slots: int | None = None, ctx_method: str = "spawn",
                 slot_nbytes: int | None = None):
        from multiprocessing import shared_memory

        self.image_size = image_size
        self.workers = max(1, workers)
        self.slots = slots or 2 * self.workers
        self.slot_nbytes = (slot_nbytes if slot_nbytes is not None
                            else shard_size * image_size * image_size * 3)
        self._shm = shared_memory.SharedMemory(
            create=True, size=max(1, self.slots * self.slot_nbytes))
        ctx = mp.get_context(ctx_method)
        self._task_q = ctx.Queue()
        self._result_q = ctx.Queue()
        self._procs = [
            ctx.Process(target=_worker, daemon=True,
                        args=(self._task_q, self._result_q, self._shm.name,
                              self.slot_nbytes, image_size))
            for _ in range(self.workers)
        ]
        for p in self._procs:
            p.start()
        self._closed = False
        self._broken = False
        self._busy = threading.Lock()

    def _get_result(self):
        """result_q.get with worker-liveness checks: a dead worker (OOM
        kill, exception on a corrupt shard) must raise, not hang the
        training loop forever."""
        import queue as _queue

        while True:
            try:
                return self._result_q.get(timeout=5)
            except _queue.Empty:
                dead = [p for p in self._procs if not p.is_alive()]
                if dead:
                    raise RuntimeError(
                        f"ShmShardPool: {len(dead)} worker(s) died "
                        f"(exitcodes {[p.exitcode for p in dead]}); "
                        "a shard load/augment likely raised — see worker "
                        "stderr"
                    ) from None

    def run(self, tasks):
        if self._closed or self._broken:
            raise RuntimeError("ShmShardPool is closed or broken")
        if not self._busy.acquire(blocking=False):
            raise RuntimeError(
                "ShmShardPool already serving an epoch; close the previous"
                " batch generator first"
            )
        try:
            tasks = list(tasks)
            free = list(range(self.slots))
            next_submit = 0

            def submit():
                nonlocal next_submit
                if next_submit < len(tasks) and free:
                    spec, seed = tasks[next_submit]
                    self._task_q.put(
                        (next_submit, spec, int(seed), free.pop()))
                    next_submit += 1

            for _ in range(min(self.slots, len(tasks))):
                submit()
            pending: dict[int, tuple] = {}
            served = 0
            try:
                for want in range(len(tasks)):
                    while want not in pending:
                        idx, slot, shape, dt, y = self._get_result()
                        pending[idx] = (slot, shape, dt, y)
                    slot, shape, dt, y = pending.pop(want)
                    view = np.ndarray(
                        shape, np.dtype(dt),
                        buffer=self._shm.buf[slot * self.slot_nbytes:])
                    x = view.copy()  # the slot is recycled right after
                    del view  # shm.buf views must die before close/unlink
                    free.append(slot)
                    submit()
                    served += 1
                    yield x, y
            finally:
                # early close (GeneratorExit): drain in-flight results so
                # the next epoch starts from an empty ring; if a worker
                # died, give up draining (the pool is broken either way)
                inflight = next_submit - served - len(pending)
                try:
                    for _ in range(inflight):
                        self._get_result()
                except RuntimeError:
                    # a worker died: mark broken (close() still tears the
                    # survivors + shm down — _closed would no-op it)
                    self._broken = True
                pending.clear()
        finally:
            self._busy.release()

    def close(self):
        if self._closed:
            return
        self._closed = True
        for _ in self._procs:
            self._task_q.put(None)
        for p in self._procs:
            p.join(timeout=10)
            if p.is_alive():
                p.terminate()
        try:
            self._shm.close()
            self._shm.unlink()
        except FileNotFoundError:  # lint: swallow-ok — already unlinked
            pass
