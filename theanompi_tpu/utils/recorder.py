"""Training recorder: per-iteration wall-clock splits + metric histories.

Reference (unverified — SURVEY.md §2.1/§5): ``theanompi/lib/recorder.py`` —
``Recorder.start/end`` wall-clock segments (calc / comm / wait) threaded
through ``train_iter``/``exchange``, train cost+error printed every N
iterations, epoch validation stats, ``.npy`` histories dumped to a record
dir.  The API is preserved; the TPU twist is honesty under async dispatch:
jax returns control before the device finishes, so ``end()`` accepts a
``fence`` array to ``block_until_ready`` — without it the calc/comm split is
meaningless (SURVEY.md §7 hard part 5).
"""

from __future__ import annotations

import json
import os
import time
from collections import defaultdict

import numpy as np

from theanompi_tpu.telemetry import spans
from theanompi_tpu.telemetry.metrics import RECORDER_SPANS

SEGMENTS = ("wait", "calc", "comm")


class Recorder:
    def __init__(self, print_freq: int = 40, save_dir: str | None = None,
                 rank: int = 0, verbose: bool = True, telemetry=None):
        self.print_freq = print_freq
        self.save_dir = save_dir
        self.verbose = verbose and rank == 0
        # optional telemetry sink (val_metrics instants).  The segments
        # themselves are spans of the process's ring whether or not a sink
        # exists: the same start/duration the histories record, so the
        # Perfetto view and the .npy splits are one set of numbers
        self.telemetry = telemetry
        self._open: dict[str, spans.Span] = {}
        self._iter_times: dict[str, float] = defaultdict(float)
        self.time_history: dict[str, list] = defaultdict(list)
        self.train_history: dict[str, list] = defaultdict(list)
        self.val_history: dict[str, list] = defaultdict(list)
        self._train_accum: dict[str, list] = defaultdict(list)
        self.epoch_start_time: float | None = None

    # -- wall-clock segments ------------------------------------------------
    def start(self, what: str = "calc") -> None:
        stale = self._open.pop(what, None)
        if stale is not None:  # restarted: the later start counts
            stale.cancel()
        self._open[what] = spans.begin(
            RECORDER_SPANS.get(what) or f"recorder.{what}")

    def end(self, what: str = "calc", fence=None) -> None:
        """Close segment ``what``; pass a jax array as ``fence`` to block on
        device completion so the split reflects device time, not dispatch."""
        span = self._open.pop(what, None)
        if span is None:
            raise RuntimeError(
                f"Recorder.end({what!r}): segment was never started "
                f"(open segments: {sorted(self._open) or 'none'}); "
                f"use cancel() to abandon a segment"
            )
        # an async device error surfacing at the fence (the one deliberate
        # sync point) propagates from here, not from some arbitrary later
        # sync with a misleading stack; the span closes tagged ``error``
        self._iter_times[what] += span.end(fence=fence)

    def cancel(self, what: str) -> None:
        """Abandon an open segment without recording it (e.g. the wait
        opened before a ``next()`` that raised StopIteration)."""
        span = self._open.pop(what, None)
        if span is not None:
            span.cancel()

    def end_iteration(self) -> None:
        for seg in SEGMENTS:
            self.time_history[seg].append(self._iter_times.get(seg, 0.0))
        self._iter_times.clear()

    # -- metrics ------------------------------------------------------------
    def train_metrics(self, **metrics) -> None:
        """Accumulate per-iteration metrics.

        Values may be device arrays; conversion to host floats is deferred to
        the print boundary so per-iteration recording never forces a device
        sync (which would serialize the dispatch pipeline on TPU).
        """
        for k, v in metrics.items():
            self._train_accum[k].append(v)

    def print_train_info(self, count: int) -> None:
        """Every ``print_freq`` iterations: averaged metrics + time split."""
        if count % self.print_freq != 0 or not self._train_accum:
            return
        # np.asarray(...).mean(): metrics may be per-worker vectors (the
        # async rules report without a cross-worker collective in the step)
        means = {
            k: float(np.mean([np.asarray(x).mean() for x in v]))
            for k, v in self._train_accum.items()
        }
        for k, v in means.items():
            self.train_history[k].append(v)
        self.train_history["iter"].append(count)
        if self.verbose:
            metric_s = " ".join(f"{k} {v:.4f}" for k, v in means.items())
            n = min(self.print_freq, len(self.time_history["calc"]) or 1)
            times = {
                seg: float(np.sum(self.time_history[seg][-n:]))
                for seg in SEGMENTS
            }
            time_s = " ".join(f"{s} {t:.3f}s" for s, t in times.items())
            print(f"iter {count}: {metric_s} | {time_s}", flush=True)
        self._train_accum.clear()

    def val_metrics(self, epoch: int, **metrics) -> None:
        self.val_history["epoch"].append(epoch)
        for k, v in metrics.items():
            self.val_history[k].append(float(v))
        if self.telemetry is not None:
            self.telemetry.instant(
                "val_metrics", epoch=epoch,
                **{k: float(v) for k, v in metrics.items()})
        if self.verbose:
            metric_s = " ".join(f"val_{k} {float(v):.4f}" for k, v in metrics.items())
            dur = (
                f" ({time.perf_counter() - self.epoch_start_time:.1f}s)"
                if self.epoch_start_time
                else ""
            )
            print(f"epoch {epoch}: {metric_s}{dur}", flush=True)

    def start_epoch(self) -> None:
        self.epoch_start_time = time.perf_counter()

    def latest_val(self, key: str = "cost"):
        vals = self.val_history.get(key)
        return vals[-1] if vals else None

    # -- persistence (reference dumped .npy histories into record/) ---------
    def history_snapshot(self) -> dict:
        """Point-in-time copy of the three histories as plain lists.

        Cheap (list copies on the calling thread), so the async checkpoint
        writer can serialize it off-thread without racing later iterations
        mutating the live defaultdicts (ISSUE 3 — the boundary pays neither
        the .npy nor the .npz write).
        """
        return {
            "time": {k: list(v) for k, v in self.time_history.items()},
            "train": {k: list(v) for k, v in self.train_history.items()},
            "val": {k: list(v) for k, v in self.val_history.items()},
        }

    def save(self, path: str | None = None) -> None:
        path = path or self.save_dir
        if path is None:
            return
        write_history_snapshot(self.history_snapshot(), path)

    def load(self, path: str | None = None) -> None:
        path = path or self.save_dir
        if path is None:
            return
        for name, hist in (
            ("time", self.time_history),
            ("train", self.train_history),
            ("val", self.val_history),
        ):
            p = os.path.join(path, f"{name}_history.npy")
            if os.path.exists(p):
                loaded = np.load(p, allow_pickle=True).item()
                hist.clear()
                # tolist(), not list(): numpy scalars (np.int64 epochs)
                # must not leak into the histories — a later save() would
                # fail json-serializing summary.json (resume, then train
                # more, then save — the supervisor's bread and butter)
                hist.update({k: np.asarray(v).tolist()
                             for k, v in loaded.items()})


def write_history_snapshot(snapshot: dict, path: str) -> None:
    """Serialize a :meth:`Recorder.history_snapshot` to ``path`` — the
    ``*_history.npy`` files + ``summary.json`` :meth:`Recorder.load` reads.
    Split out of :meth:`Recorder.save` so the async checkpoint writer can
    run it on the background thread against an immutable snapshot."""
    os.makedirs(path, exist_ok=True)
    for name in ("time", "train", "val"):
        hist = snapshot.get(name, {})
        # tmp + replace, like summary.json below: np.save straight to the
        # final name truncates first, and a SIGKILL inside that window left
        # an empty file every resume then died on (EOFError in
        # Recorder.load — seen in the supervised-SIGKILL e2e)
        npy = os.path.join(path, f"{name}_history.npy")
        with open(npy + ".tmp", "wb") as f:
            np.save(f, {k: np.asarray(v) for k, v in hist.items()},
                    allow_pickle=True)
        os.replace(npy + ".tmp", npy)
    spath = os.path.join(path, "summary.json")
    with open(spath + ".tmp", "w") as f:
        json.dump(
            {
                "iters": len(snapshot.get("time", {}).get("calc", ())),
                "last_val": {
                    k: v[-1]
                    for k, v in snapshot.get("val", {}).items() if v
                },
            },
            f,
        )
    os.replace(spath + ".tmp", spath)
