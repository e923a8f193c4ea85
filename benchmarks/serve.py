"""Serving driver: a measured window over ``Scheduler.submit`` / ``.step``.

The engine and scheduler are built as ``serving/cli.py::_serve`` builds
them; the loop, the clock, the token stamps and the spans around the
engine's calls are the benchmark's own.
"""

from __future__ import annotations

import gc
import time

import jax
import numpy as np

from benchmarks import reference, weights, work
from benchmarks.common import (TraceWindow, annotate, import_generator,
                               memory_peak_bytes, model_config, percentile)


class _EngineSpans:
    """Benchmark spans around the engine's two calls, set as instance
    attributes so the scheduler's own ``self.engine.prefill/decode`` pass
    through them.  Each step's share is drained by :meth:`take`."""

    def __init__(self, engine, cfg):
        self.cfg = cfg
        self._prefill, self._decode = engine.prefill, engine.decode
        engine.prefill, engine.decode = self.prefill, self.decode
        self.reset()

    def reset(self):
        self.prefill_s, self.decode_s, self.n_prefill = 0.0, 0.0, 0
        self.flops, self.kv_tokens, self.slots, self.prefill_ms = 0.0, 0, 0, []
        self.prefill_end: dict[int, float] = {}

    def prefill(self, table_row, tokens, temperature=0.0, rid=0, **kw):
        t0 = time.perf_counter()
        with annotate("engine.prefill"):
            out = self._prefill(table_row, tokens, temperature, rid, **kw)
        self.prefill_end[rid] = t1 = time.perf_counter()
        dt = t1 - t0
        self.prefill_s += dt
        self.prefill_ms.append(dt * 1e3)
        self.n_prefill += 1
        self.flops += work.prefill_flops(self.cfg, len(tokens))
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
        self.flops += self.slots * work.decode_flops(
            self.cfg, self.kv_tokens / max(self.slots, 1))
        return out

    def take(self) -> dict:
        out = dict(prefill_s=self.prefill_s, decode_s=self.decode_s,
                   n_prefill=self.n_prefill, flops=self.flops,
                   kv_tokens=self.kv_tokens, slots=self.slots,
                   prefill_ms=self.prefill_ms, prefill_end=self.prefill_end)
        self.reset()
        return out


def build(cfg: dict, seed: int):
    from theanompi_tpu.models.transformer_lm import TransformerLM
    from theanompi_tpu.serving.engine import InferenceEngine
    from theanompi_tpu.serving.scheduler import Scheduler

    run = cfg["run"]
    model = TransformerLM(model_config(cfg))
    params = weights.seeded_params(model, cfg, seed)
    engine = InferenceEngine(model, params, block_size=run["block_size"],
                             num_blocks=run["num_blocks"],
                             max_batch=run["max_batch"], seed=int(seed) & 0x7FFFFFFF)
    return model, engine, Scheduler(engine)


def _request(r: dict):
    from theanompi_tpu.serving.scheduler import Request

    return Request(rid=r["rid"], prompt=r["prompt"],
                   max_new_tokens=r["max_new_tokens"], temperature=0.0)


def warm_up(sched, engine, requests, vocab: int) -> list[int]:
    """One short request per prefill bucket the traffic uses, then the one
    decode program; -> the buckets."""
    buckets = sorted({engine.pad_len(len(r["prompt"])) for r in requests})
    rng = np.random.Generator(np.random.PCG64(0))
    for i, b in enumerate(buckets):
        n = min(b, engine.max_context - 2)  # room for its two tokens
        sched.submit(_request({"rid": 10**6 + i, "max_new_tokens": 2,
                               "prompt": rng.integers(0, vocab, size=n).tolist()}))
    while not sched.idle:
        sched.step()
    return buckets


def served_gaps(cfg: dict, seed: int, sample: list, control: bool = False) -> dict:
    """Run the reference once over each sampled request's prompt and served
    tokens; -> the widest gap by which a served token's logit lies below
    the reference's best.  ``control``: instead of the served tokens, take
    at each position the token the fp8 control puts first."""
    n_pos, widest, n_tok = cfg["n_positions"], 0.0, 0
    for prompt, generated in sample:
        full = list(prompt) + list(generated)
        toks, served = np.zeros((2, n_pos), np.int32)
        toks[:len(full) - 1], served[:len(full) - 1] = full[:-1], full[1:]
        mask = np.zeros((n_pos,), bool)
        mask[len(prompt) - 1:len(full) - 1] = True  # the positions that were served
        widest = max(widest, reference.served_gap(cfg, seed, toks, served, mask,
                                                  control))
        n_tok += len(generated)
    return {"widest_logit_gap": widest, "tokens_compared": n_tok}


def run(ctx: dict) -> dict:
    cfg, traffic, seed = ctx["cfg"], ctx["traffic"], ctx["seed"]
    seconds, marks = ctx["seconds"], ctx["marks"]
    run_cfg = cfg["run"]
    requests = import_generator(traffic).generate(
        traffic, seed, vocab=cfg["vocab_size"], max_batch=run_cfg["max_batch"])

    model, engine, sched = build(cfg, seed)
    jax.block_until_ready(engine.params)
    marks["weights_s"] = time.perf_counter()
    spans = _EngineSpans(engine, cfg)
    buckets = warm_up(sched, engine, requests, cfg["vocab_size"])
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
    tpots = []
    for rid, ts in stamps.items():
        if len(ts) >= 17:  # at least 16 gaps inside the window
            tpots.append((ts[-1] - ts[0]) / (len(ts) - 1) * 1e3)
    if len(tpots) < 20:
        raise RuntimeError(f"only {len(tpots)} requests had 16 token gaps in "
                           f"the window; tpot_ms_p90 needs more")
    e2e = {"serve_tokens_per_s": n_tokens / window_s,
           "tpot_ms_p90": percentile(tpots, 90)}

    step_ms = sched.step_ms[n_before[0]:]
    dec = [s for s in steps if s["slots"]]
    series = {
        "engine.decode_step_ms": step_ms,
        "engine.prefill_ms": [m for s in steps for m in s["prefill_ms"]],
        "sched.ttft_ms": sched.ttft_ms[n_before[1]:],
        "sched.host_ms": [(s["t1"] - s["t0"] - s["prefill_s"] - s["decode_s"]) * 1e3
                          for s in steps],
        "tpot_ms": tpots,
    }
    clean = [s for s in steps if s["t1"] <= trace.untraced_until]
    counters = {
        "window_s": window_s, "steps": len(steps), "tokens": n_tokens,
        "requests_done": len(done_in_window), "requests_tpot": len(tpots),
        "slot_steps": sum(s["slots"] for s in dec),
        "slot_capacity": len(dec) * run_cfg["max_batch"],
        "pool_peak_blocks": max(s["used"] for s in steps), "pool_blocks": pool_total,
        "preemptions": sched.n_preemptions - n_before[2],
        # the steps that ran while no profiler was attached, and their time
        "mfu_flops": sum(s["flops"] for s in clean),
        "mfu_s": clean[-1]["t1"] - t_open if clean else 0.0,
        "prefills": sum(s["n_prefill"] for s in steps),
        "memory_peak_bytes": peak,
        "prefill_buckets": len(buckets),
    }
    traced = [s for s in dec if trace.covers(s["t0"], s["t1"])]
    if traced:  # the mean decode step the trace saw, for the kernel's roof
        counters["kernel_bytes_per_run"] = sum(
            work.paged_decode_bytes(cfg, s["kv_tokens"], s["slots"])
            for s in traced) / len(traced)
        counters["kernel_flops_per_run"] = sum(
            work.paged_decode_flops(cfg, s["kv_tokens"]) for s in traced) / len(traced)

    attempted = sum(r.t_first_token is not None for r in by_rid.values())
    failed = sched.n_expired + sched.n_shed + sched.n_failed

    # -- correct: the reference over a sample of what the window finished ----
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    done = sorted(done_in_window, key=lambda r: (len(r.prompt) + len(r.generated), r.rid))
    picks = [done[-1]] + [done[i] for i in rng.permutation(len(done) - 1)
                          [:int(traffic["check_requests"]) - 1]]
    sample = [(list(r.prompt), list(r.generated)) for r in picks]
    short = [r.rid for r in done_in_window
             if len(r.generated) != r.max_new_tokens]
    del sched, engine, model, spans, by_rid, done, picks, done_in_window
    gc.collect()
    t_check = time.perf_counter()
    checks = served_gaps(cfg, seed, sample)
    extra = {"tokens_compared": checks["tokens_compared"],
             "check_s": time.perf_counter() - t_check,
             # where the window went, by the benchmark's spans
             "steps": len(steps), "decode_s": sum(s["decode_s"] for s in steps),
             "prefill_s": sum(s["prefill_s"] for s in steps),
             "step_s": sum(s["t1"] - s["t0"] for s in steps)}
    if traffic.get("calibrate_control"):  # benchmarks/calibrate.py only
        extra["control_fp8_widest_logit_gap"] = served_gaps(
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
