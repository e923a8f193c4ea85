"""Serving driver for any architecture: :mod:`benchmarks.serve`'s measured
window — the same clock, token stamps, ``serve_tokens_per_s``,
``tpot_ms_p90`` (end to end only where the traffic file's ``end_to_end``
lists it), stationary start and sample of finished requests — with
everything that knows the model behind an adapter found by the
configuration's ``model_type``: ``benchmarks/arch/<model_type>.py`` gives
``build`` (model, engine and scheduler as ``tmserve`` builds them, holding
the seed's weights), ``vocab``, ``served_gaps`` (the plain reference over
what was served) and ``prefill_flops`` / ``decode_flops`` /
``decode_bytes`` (the work a step needs), and optionally
``served_record`` (a finished request -> what the program kept of how its
tokens were produced, handed to ``served_gaps`` beside prompt and tokens:
a model that commits tokens out of order needs the state each token was
chosen in, which the tokens alone do not say).  A new architecture adds an
adapter, not a driver.
"""

from __future__ import annotations

import gc
import importlib
import time

import jax
import numpy as np

from benchmarks.common import (TraceWindow, annotate, import_generator,
                               memory_peak_bytes, percentile)
from benchmarks.serve import _request, warm_up


def adapter_for(cfg: dict):
    return importlib.import_module(f"benchmarks.arch.{cfg['model_type']}")


class _EngineSpans:
    """Benchmark spans around the engine's two calls (instance attributes,
    so the scheduler's own ``self.engine.prefill/decode`` pass through
    them), with the adapter's count of the work each call needed.  Each
    step's share is drained by :meth:`take`."""

    def __init__(self, engine, cfg, arch):
        self.cfg, self.arch = cfg, arch
        self._prefill, self._decode = engine.prefill, engine.decode
        engine.prefill, engine.decode = self.prefill, self.decode
        self.reset()

    def reset(self):
        self.prefill_s, self.decode_s, self.n_prefill = 0.0, 0.0, 0
        self.flops, self.bytes, self.kv_tokens, self.slots = 0.0, 0.0, 0, 0
        self.prefill_ms: list[float] = []
        self.prefill_end: dict[int, float] = {}

    def prefill(self, table_row, tokens, temperature=0.0, rid=0, **kw):
        t0 = time.perf_counter()
        with annotate("engine.prefill"):
            out = self._prefill(table_row, tokens, temperature, rid, **kw)
        self.prefill_end[rid] = t1 = time.perf_counter()
        self.prefill_s += t1 - t0
        self.prefill_ms.append((t1 - t0) * 1e3)
        self.n_prefill += 1
        self.flops += self.arch.prefill_flops(self.cfg, len(tokens))
        return out

    def decode(self, tables, lengths, *a, **kw):
        t0 = time.perf_counter()
        with annotate("engine.decode"):
            out = self._decode(tables, lengths, *a, **kw)
        self.decode_s += time.perf_counter() - t0
        lengths = np.asarray(lengths)
        active = lengths > 0
        self.slots = int(active.sum())
        self.kv_tokens = int(lengths[active].sum()) + self.slots
        # attention FLOPs are linear in context: every slot at the mean context
        self.flops += self.slots * self.arch.decode_flops(
            self.cfg, self.kv_tokens / max(self.slots, 1))
        self.bytes += self.arch.decode_bytes(self.cfg, self.kv_tokens, self.slots)
        return out

    def take(self) -> dict:
        out = dict(prefill_s=self.prefill_s, decode_s=self.decode_s,
                   n_prefill=self.n_prefill, flops=self.flops, bytes=self.bytes,
                   kv_tokens=self.kv_tokens, slots=self.slots,
                   prefill_ms=self.prefill_ms, prefill_end=self.prefill_end)
        self.reset()
        return out


def served_sample(arch, picks) -> list[tuple]:
    """``(prompt, generated)`` of each picked request, in order, with the
    adapter's ``served_record(request)`` as a third entry where it has one."""
    record = getattr(arch, "served_record", None)
    if record is None:
        return [(list(r.prompt), list(r.generated)) for r in picks]
    return [(list(r.prompt), list(r.generated), record(r)) for r in picks]


def run(ctx: dict) -> dict:
    cfg, traffic, seed = ctx["cfg"], ctx["traffic"], ctx["seed"]
    seconds, marks = ctx["seconds"], ctx["marks"]
    arch = adapter_for(cfg)
    run_cfg = cfg["run"]
    requests = import_generator(traffic).generate(
        traffic, seed, vocab=arch.vocab(cfg), max_batch=run_cfg["max_batch"])
    over = [r["rid"] for r in requests
            if len(r["prompt"]) + r["max_new_tokens"] > run_cfg["max_context"]]
    if over:
        raise ValueError(f"requests {over[:5]} do not fit max_context "
                         f"{run_cfg['max_context']}")

    model, engine, sched = arch.build(cfg, seed)
    jax.block_until_ready(engine.params)
    marks["weights_s"] = time.perf_counter()
    spans = _EngineSpans(engine, cfg, arch)
    buckets = warm_up(sched, engine, requests, arch.vocab(cfg))
    marks["warm_up_s"] = time.perf_counter()

    by_rid = {}
    for r in requests:
        by_rid[r["rid"]] = _request(r)
        sched.submit(by_rid[r["rid"]])
    seen: dict[int, int] = {}
    stamps: dict[int, list[float]] = {}
    steps: list[dict] = []
    done_in_window: list = []
    pool_total = engine.num_blocks - 1

    def one_step(record: bool):
        t0 = time.perf_counter()
        with annotate("sched.step"):
            finished = sched.step()
        t1 = time.perf_counter()
        new = 0
        part = spans.take()
        prefilled = part.pop("prefill_end")
        for req in [*sched.slots, *finished]:
            if req is None:
                continue
            n = len(req.generated)
            k = n - seen.get(req.rid, 0)
            if k > 0:
                seen[req.rid] = n
                new += k
                if record:
                    # a prefill's token is stamped when the prefill returned
                    # it, a decode step's when the scheduler's step returned
                    first = [prefilled[req.rid]] if req.rid in prefilled else []
                    stamps.setdefault(req.rid, []).extend(
                        first + [t1] * (k - len(first)))
        if record:
            steps.append(dict(t0=t0, t1=t1, new=new,
                              used=pool_total - sched.pool.free_blocks, **part))
            done_in_window.extend(r for r in finished if r.state == "done")
        return t1

    spans.reset()
    lead_end = time.perf_counter() + float(traffic["lead_in_s"])
    while time.perf_counter() < lead_end:
        one_step(False)
    n_before = (len(sched.step_ms), len(sched.ttft_ms), sched.n_preemptions)
    trace = TraceWindow(ctx, float(traffic.get("trace_seconds", 10.0)))
    t_open = time.perf_counter()
    marks["window_open"], marks["compiles_open"] = t_open, ctx["compiles"].n
    trace.arm(t_open, seconds)
    t_close = t_open
    while t_close - t_open < seconds:
        t_close = one_step(True)
        trace.poll()
    marks["compiles_close"] = ctx["compiles"].n
    trace.close(t_close)
    window_s = t_close - t_open
    if not sched.queue:
        raise RuntimeError("the backlog emptied inside the window: the "
                           "traffic file needs more requests")
    engine.fence()
    peak = memory_peak_bytes(ctx["devices"])

    # -- end-to-end: every token stamped in the window, over the window ------
    n_tokens = sum(s["new"] for s in steps)
    tpots = [(ts[-1] - ts[0]) / (len(ts) - 1) * 1e3
             for ts in stamps.values() if len(ts) >= 17]  # >= 16 gaps
    if len(tpots) < 20:
        raise RuntimeError(f"only {len(tpots)} requests had 16 token gaps in "
                           f"the window; tpot_ms_p90 needs more")
    e2e = {"serve_tokens_per_s": n_tokens / window_s,
           "tpot_ms_p90": percentile(tpots, 90)}
    # a traffic file may name which of the two its cell reports end to end:
    # past saturation a tail swings with the smallest stall and belongs
    # among the per-layer metrics (the ``tpot_ms`` series below)
    e2e = {k: e2e[k] for k in traffic.get("end_to_end", list(e2e))}

    dec = [s for s in steps if s["slots"]]
    series = {
        "engine.decode_step_ms": sched.step_ms[n_before[0]:],
        "engine.prefill_ms": [m for s in steps for m in s["prefill_ms"]],
        "sched.ttft_ms": sched.ttft_ms[n_before[1]:],
        "sched.host_ms": [(s["t1"] - s["t0"] - s["prefill_s"] - s["decode_s"]) * 1e3
                          for s in steps],
        "tpot_ms": tpots,
    }
    clean = [s for s in steps if s["t1"] <= trace.untraced_until]
    lo, hi = cfg.get("experts_held", (0, 0))
    counters = {
        "window_s": window_s, "steps": len(steps), "tokens": n_tokens,
        "requests_done": len(done_in_window), "requests_tpot": len(tpots),
        "slot_steps": sum(s["slots"] for s in dec),
        "slot_capacity": len(dec) * run_cfg["max_batch"],
        "pool_peak_blocks": max(s["used"] for s in steps), "pool_blocks": pool_total,
        "preemptions": sched.n_preemptions - n_before[2],
        # the steps that ran while no profiler was attached, their time, the
        # FLOPs they needed and the least bytes their decode steps had to move
        "mfu_flops": sum(s["flops"] for s in clean),
        "hbm_bytes": sum(s["bytes"] for s in clean),
        "mfu_s": clean[-1]["t1"] - t_open if clean else 0.0,
        "prefills": sum(s["n_prefill"] for s in steps),
        "memory_peak_bytes": peak,
        "prefill_buckets": len(buckets),
        # what the span-tag readers divide by
        "moe_layers": cfg.get("hybrid_override_pattern", "").count("E"),
        "experts_held": hi - lo,
    }

    attempted = sum(r.t_first_token is not None for r in by_rid.values())
    failed = sched.n_expired + sched.n_shed + sched.n_failed

    # -- correct: the reference over a sample of what the window finished ----
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    done = sorted(done_in_window, key=lambda r: (len(r.prompt) + len(r.generated), r.rid))
    picks = [done[-1]] + [done[i] for i in rng.permutation(len(done) - 1)
                          [:int(traffic["check_requests"]) - 1]]
    sample = served_sample(arch, picks)
    short = [r.rid for r in done_in_window
             if len(r.generated) != r.max_new_tokens]
    del sched, engine, model, spans, by_rid, done, picks, done_in_window
    gc.collect()
    t_check = time.perf_counter()
    checks = arch.served_gaps(cfg, seed, sample)
    extra = {"tokens_compared": checks["tokens_compared"],
             "check_s": time.perf_counter() - t_check,
             # where the window went, by the benchmark's spans
             "steps": len(steps), "decode_s": sum(s["decode_s"] for s in steps),
             "prefill_s": sum(s["prefill_s"] for s in steps),
             "step_s": sum(s["t1"] - s["t0"] for s in steps)}
    if traffic.get("calibrate_control"):  # calibration runs only
        extra["control_fp8_widest_logit_gap"] = arch.served_gaps(
            cfg, seed, sample, control=True)["widest_logit_gap"]
    limits = ctx["cell"]["limits"]
    compared = [
        {"name": "widest_logit_gap", "value": checks["widest_logit_gap"],
         "limit": limits["widest_logit_gap"]},
        {"name": "requests_cut_short", "value": len(short), "limit": 0},
    ]
    return dict(e2e=e2e, series=series, counters=counters, attempted=attempted,
                failed=failed, compared=compared, trace=trace,
                extra=extra)
