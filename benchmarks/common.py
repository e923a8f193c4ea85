"""Small pieces both drivers share: files found by name, the clock's
percentile, host spans on the profiler's clock, the traced sub-window."""

from __future__ import annotations

import importlib
import json
import os
import shutil
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmarks")


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def import_generator(traffic: dict):
    """The traffic file names its generator; a new one is a new module."""
    return importlib.import_module(f"benchmarks.generators.{traffic['generator']}")


def model_config(cfg: dict) -> dict:
    """The program's ``TransformerLM`` config for a configuration file."""
    if cfg["n_inner"] != 4 * cfg["n_embd"]:
        raise ValueError("TransformerLM's FFN is 4 x dim; the file states "
                         f"n_inner {cfg['n_inner']} for n_embd {cfg['n_embd']}")
    return dict(dim=cfg["n_embd"], heads=cfg["n_head"], n_layers=cfg["n_layer"],
                seq_len=cfg["n_positions"], vocab=cfg["vocab_size"], dropout=0.0,
                precision=cfg["run"]["precision"], n_train=8, n_val=4, verbose=False)


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile of all the values."""
    if not len(values):
        raise ValueError("percentile of nothing")
    return float(np.percentile(np.asarray(values, float), q))


def annotate(name: str):
    """A host span written into the profiler's own trace (a no-op while no
    trace is running), so idle gaps on the device can be put down to what
    the host was doing on the same clock."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device, as the backend reports it."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else 0


class TraceWindow:
    """With ``--trace 1``: a profiler trace over the last ``length_s`` of
    the measured window, written inside the checkout and removed once read.
    It is stopped after the window has closed, so stopping (seconds of host
    time) costs the window nothing.  With ``--trace 0`` every method is a
    no-op and nothing is covered."""

    def __init__(self, ctx: dict, length_s: float):
        self.on = bool(ctx["trace"])
        self.length_s = length_s
        self.dir = os.path.join(ROOT, ".bench_tmp", f"trace-{ctx['workload']}")
        self.due = self.t_open = self.t_close = None
        #: the window up to here ran with no profiler attached (starting one
        #: stalls the host for seconds): rates of a traced run are read here
        self.untraced_until = float("inf")

    def arm(self, t_window_open: float, seconds: float):
        self.due = t_window_open + max(0.0, seconds - self.length_s)

    def poll(self):
        """Start the trace once its time has come (called between steps)."""
        if not self.on or self.t_open is not None \
                or time.perf_counter() < self.due:
            return
        import jax

        self.untraced_until = time.perf_counter()
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.t_open = time.perf_counter()

    def close(self, t_window_close: float):
        if not self.on or self.t_open is None or self.t_close is not None:
            return
        import jax

        self.t_close = t_window_close
        jax.profiler.stop_trace()

    def covers(self, t0: float, t1: float) -> bool:
        return bool(self.on and self.t_close is not None
                    and t0 >= self.t_open and t1 <= self.t_close)

    def reduce(self, n_devices: int, span_names, main_module=None) -> dict | None:
        """Read the trace, reduce it (:mod:`benchmarks.trace`), remove it."""
        if not self.on:
            return None
        from benchmarks import trace

        try:
            return trace.reduce(trace.load_events(self.dir, span_names),
                                n_devices, span_names, main_module)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
