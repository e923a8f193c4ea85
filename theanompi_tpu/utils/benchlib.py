"""Shared measurement helpers for bench.py and the scaling harness.

Protocol (see bench.py's docstring for the full rationale): jax dispatch is
async, so a timed region must dispatch a chain of steps and synchronize
exactly once at the end — per-step syncs measure round-trip latency, not
throughput.  Runs are repeated and the best trial taken (min-time as the
capability estimator; whether that survives is the benchmark PR's call).
Keeping the loop here means bench.py and SCALING.json always measure under
the same protocol.
"""

from __future__ import annotations

import time

import numpy as np


def run_trial(trainer, batches, steps: int, feed_mode: str = "placed",
              lr: float = 0.01):
    """One timed trial.  -> (seconds, steps run, input-wait seconds).

    ``feed_mode='placed'``: ``batches`` are device-resident, cycled — times
    the training step itself.  ``'prefetch'``: host batches stream through
    the production Prefetcher (transfer included, overlapped), with the
    dequeue stall timed into the recorder's wait bucket exactly as
    ``BaseTrainer.run`` does.
    """
    rec = trainer.recorder
    rec.time_history.clear()
    if feed_mode == "prefetch":
        from theanompi_tpu.models.data.prefetch import prefetch

        rotation = (batches[i % len(batches)] for i in range(steps))
        feed = prefetch(rotation, mesh=trainer.mesh, depth=4,
                        spec=trainer.batch_spec)
    else:
        feed = [batches[i % len(batches)] for i in range(steps)]
    t0 = time.perf_counter()
    n = 0
    m = None
    it = iter(feed)
    try:
        while True:
            rec.start("wait")  # run()-loop parity: time the dequeue stall
            try:
                b = next(it)
            except StopIteration:
                rec.cancel("wait")
                break
            rec.end("wait")
            m = trainer.train_iter(b, lr=lr)
            n += 1
    finally:
        close = getattr(feed, "close", None)
        if close:
            close()
    float(m["cost"])  # the single sync: drains the dispatched chain
    dt = time.perf_counter() - t0
    return dt, n, float(np.sum(rec.time_history["wait"]))


def best_trial(trainer, batches, steps: int, trials: int,
               feed_mode: str = "placed", lr: float = 0.01):
    """-> ((best seconds, steps, wait seconds), all trial results)."""
    results = [run_trial(trainer, batches, steps, feed_mode, lr=lr)
               for _ in range(trials)]
    return min(results, key=lambda r: r[0] / r[1]), results


def slope_trial(trainer, batches, n_lo: int, n_hi: int,
                feed_mode: str = "placed", lr: float = 0.01):
    """One slope trial -> (sec/step, (dt_lo, dt_hi), wait seconds).

    Every chained trial's wall time carries a constant: the final scalar
    fetch's round trip, which inflates ``dt/n`` by ``RTT/n``.  Timing a
    SHORT chain and a LONG chain back-to-back and taking the slope
    cancels the constant; this is the protocol behind BASELINE.md's r4
    interleaved-window measurement (93.8 ms) that the chain-mode artifact
    (2484 img/s ≈ 103 ms) sat 10 % below.  A noisy trial can produce a
    negative/absurd slope — callers filter (``best_slope``).
    """
    if n_hi <= n_lo:
        raise ValueError(f"slope needs n_hi > n_lo, got {n_lo}..{n_hi}")
    dt_lo, n1, _ = run_trial(trainer, batches, n_lo, feed_mode, lr=lr)
    dt_hi, n2, w_hi = run_trial(trainer, batches, n_hi, feed_mode, lr=lr)
    step_s = (dt_hi - dt_lo) / (n2 - n1)
    # wait seconds of the HI chain only: it covers exactly n_hi steps, so
    # the caller's per-step wait stays comparable with chain-mode artifacts
    return step_s, (dt_lo, dt_hi), w_hi


def best_slope(trainer, batches, n_lo: int, n_hi: int, trials: int,
               feed_mode: str = "placed", lr: float = 0.01):
    """-> ((best sec/step, hi-chain wait seconds), trials, used_fallback).

    Best = the smallest POSITIVE slope (min-time capability estimator);
    non-positive slopes are excluded from "best" but stay in the returned
    list so the artifact's spread shows them.  If every slope is
    non-positive the chain estimate ``dt_hi/n_hi`` of the fastest trial
    substitutes — flagged via ``used_fallback`` so the artifact cannot
    pass an RTT-inflated chain number off as a slope measurement.
    """
    results = [slope_trial(trainer, batches, n_lo, n_hi, feed_mode, lr=lr)
               for _ in range(trials)]
    positive = [r for r in results if r[0] > 0]
    if positive:
        best = min(positive, key=lambda r: r[0])
        return (best[0], best[2]), results, False
    fallback = min(results, key=lambda r: r[1][1])
    return (fallback[1][1] / n_hi, fallback[2]), results, True
