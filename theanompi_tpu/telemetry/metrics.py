"""Metrics registry: counters, gauges, histograms + training-rate helpers.

The registry is host-side accumulation only — incrementing a counter or
observing a histogram sample is a dict update, never device work or I/O.
``snapshot()`` is called at flush boundaries (``print_freq`` in the
trainer) and its dict rides one ``metrics`` event through the sink.

The ``train.mfu`` gauge derives nothing itself: ``step_flops_estimate``
asks XLA's cost analysis through the trainer's ``compiled_step`` hook and
``peak_flops`` reads :data:`DEVICE_PEAKS`, this package's table of
published chip peaks.
"""

from __future__ import annotations

import re
from collections import defaultdict

import numpy as np

#: HLO collective op kinds, as spelled in compiled-module text
COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")

_COLL_DEF_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(-start|-done)?\(")


def hlo_collective_counts(hlo_text: str) -> dict[str, int]:
    """Count collective-op definitions per kind in compiled HLO text.

    The static cross-check for bucketed exchange (ISSUE 2): a fused-bucket
    step must compile to O(buckets) ``all-reduce`` ops, not O(leaves) — and
    ``zero1`` must show its ``reduce-scatter``/``all-gather`` pair.  Works
    on any backend, so CPU-mesh tests lint collective counts without TPU
    hardware (``tests/test_lint_collectives.py``); ``tmlint --hlo-audit``
    reports the same numbers per compiled step.  Async ``-start``/``-done``
    pairs count once;
    operand references never carry parens, so only definitions match.
    """
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        if " = " not in line:
            continue
        for m in _COLL_DEF_RE.finditer(line):
            if m.group(2) == "-done":
                continue
            counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    return counts


# -- serving metric names (ISSUE 6) ------------------------------------------
# The serving scheduler emits through these registered names ONLY (it
# imports them from here — one source of truth, so dashboards and the
# Chrome-trace test can't drift from what the code emits).  Span semantics:
# ``serve.prefill`` wraps one sequence's full-prompt forward (tags:
# ``request``, ``prompt``, ...); ``serve.decode`` wraps one fixed-batch
# decode step (tags: ``step``, ``batch``, ``requests`` — the per-request ids
# threaded through the trace).  Both close over materialized host results,
# so they measure execution, not dispatch; in the single-threaded serve loop
# they are disjoint by construction (locked by test).

SERVE_SPANS = ("serve.prefill", "serve.decode")
#: ISSUE 25 — the spans the serving loops open in the span ring
#: (``telemetry/spans.py``), beside the two above which the ENGINE opens
#: now (``serve.prefill`` tags: ``request``, ``prompt``, ``tokens`` — the
#: rows computed, ``bucket`` — their padded count, which picks the program,
#: ``prefix_len``; ``serve.decode``
#: tags: ``step`` — the engine's decode ordinal, ``batch``, ``requests``,
#: ``kv_tokens`` — the tokens the step's attention reads: each active
#: slot's context with the token it writes, counted on the host, for every
#: model (ISSUE 31);
#: from a model that counts them on the device (``HybridLM``) also the two
#: of :data:`SERVE_DECODE_MOE_TAGS` and, from a looped stack, the one of
#: :data:`SERVE_DECODE_LOOP_TAGS`, fetched with the step's tokens; both
#: spans carry :data:`SERVE_MOE_PRODUCT_TAGS` where the model has an expert
#: layer).
#: ``serve.step`` is one ``Scheduler.step`` (tags ``step``, ``batch``);
#: ``serve.admit`` the admission pass inside it (tag ``queued``; the
#: per-request ``serve.admit`` INSTANT keeps its name and kind).
SERVE_STEP_SPANS = ("serve.step", "serve.admit")
#: the decode call from inside, in order: the host-to-device puts, the
#: jitted call's dispatch, the wait for a launch's tokens (under a
#: scheduler the PREVIOUS call's launch, ISSUE 32: the device was given this
#: call's step before the wait began), and what else comes with them (tag
#: ``bytes``: the step's device counters; the logits too for a direct caller
#: of ``InferenceEngine.decode``, never on the scheduler's path)
SERVE_DECODE_SPANS = ("serve.decode.place", "serve.decode.dispatch",
                      "serve.decode.wait", "serve.decode.fetch")
#: the prefill call from inside, in order (ISSUE 35; ``serve.prefill``'s
#: tag ``tokens`` is the rows the program computes, ``prompt`` less
#: ``prefix_len``, and ``bucket`` their padded count): the host arrays and
#: their puts; the jitted call (a bucket's first call builds under it); the
#: rest of the decode step in flight — only where a launch is unread and
#: its tokens are not ready once the prefill has gone out: it ends when
#: that step ends, which is when the device starts the prefill program;
#: the wait for the sampled token, from there (or from the dispatch, on an
#: idle device) to the program's end: the prefill's DEVICE time as the host
#: sees it; and the last position's logits coming to the host (tag
#: ``bytes``)
SERVE_PREFILL_SPANS = ("serve.prefill.place", "serve.prefill.dispatch",
                       "serve.prefill.drain", "serve.prefill.wait",
                       "serve.prefill.fetch")
#: ``serve.decode`` tags of a model with expert layers, one value a step:
#: ``moe_local_hits`` — selected experts that this process holds, summed
#: over the expert layers and the active slots (over ``batch`` and the
#: layers: ``top_k x held / n_experts`` a token under an even router);
#: ``moe_load_peak`` — the most assignments any held expert received in
#: one expert layer of the step (the longest group of the grouped product)
SERVE_DECODE_MOE_TAGS = ("moe_local_hits", "moe_load_peak")
#: tags of ``serve.decode`` AND ``serve.prefill`` from an engine whose model
#: has an expert layer (ISSUE 28), the same in every call since the path is
#: resolved once: ``moe_products`` — grouped products in the program (two
#: an ``E`` layer); ``moe_kernel_products`` — those of them the Pallas
#: kernel runs (all of them, or 0 where ``lax.ragged_dot`` does)
SERVE_MOE_PRODUCT_TAGS = ("moe_products", "moe_kernel_products")
#: tags of ``serve.decode`` from an engine whose model keeps recurrent state
#: (ISSUE 30), the same in every call: ``state_updates`` — state layers in
#: the decode program; ``state_kernel_updates`` — those of them whose state
#: update the Pallas kernel runs (all of them, or 0 where the plain lines do)
SERVE_STATE_UPDATE_TAGS = ("state_updates", "state_kernel_updates")
#: ``serve.decode`` tag of a ``HybridLM`` whose pattern is applied ``loops``
#: > 1 times (ISSUE 31): ``loop_exit_steps`` — the sum over the active slots
#: of the 1-based loop step whose state the head read (over ``batch``:
#: ``loops`` at ``exit_threshold`` 1, where every token reads the last step)
SERVE_DECODE_LOOP_TAGS = ("loop_exit_steps",)
#: ``serve.decode`` tags of a ``HybridLM`` with window (``w``) layers
#: (ISSUE 33), counted on the host for the launched step: ``kv_full_tokens``
#: — the keys a full layer attends, each active slot's context with the
#: token it writes (``kv_tokens``); ``kv_window_tokens`` — the keys a window
#: layer attends, the same capped at the window a slot (their ratio: 1.0
#: for traffic that never leaves the window)
SERVE_DECODE_WINDOW_TAGS = ("kv_full_tokens", "kv_window_tokens")
#: ``serve.decode`` tags of the decode pipeline (ISSUE 32), on every call:
#: ``launched`` — 1, the step this call gave the device; ``ran_ahead`` — 1
#: where it went out with the previous launch unread, 0 where the pipeline
#: was empty (the first step, the one after a drain, a direct caller's);
#: ``overrun_slots`` — slots of the step this call READ that ran for a
#: request which had already ended on a stop token (their tokens are
#: dropped).  ``step`` / ``batch`` / ``kv_tokens`` / ``requests`` are the
#: launched step's; the device counters above are those of the step read,
#: one behind.  ``starved`` (ISSUE 35) — 1 where, as the jitted call went
#: out, the device had nothing of this engine's queued: no launch unread
#: (the pipeline was empty) or the unread launch's tokens ready already
#: (the call after a synchronous prefill), so the device idled through this
#: call's place and dispatch; 0 where the launch ran ahead of a step still
#: running
SERVE_DECODE_AHEAD_TAGS = ("launched", "ran_ahead", "overrun_slots",
                           "starved")
#: ``InferenceEngine.collect``: the wait for the launch a drain reads,
#: with no launch of its own (under ``serve.step``, or under nothing where
#: ``preempt_all`` / ``expire_all_active`` drained between steps); tag
#: ``overrun_slots`` as on a ``serve.decode`` that reads a launch
SERVE_COLLECT_SPAN = "serve.collect"
#: ``jax.named_scope`` names on the DEVICE (op metadata: they name rows of a
#: profiler trace, not ring records), by who opens them.  The engine:
#: ``recast`` (weight casts / dequantize), ``sample``; ``TransformerLM``:
#: ``embed``, ``block`` > ``attn`` / ``mlp``, ``head``, ``loss``; the
#: trainer: ``clip``, ``exchange``, ``optimizer``; the kernels name
#: themselves (``paged_decode``, ``flash_fwd``, ``flash_bwd_dq``,
#: ``flash_bwd_dkv``, and ``grouped_matmul``: the expert layer's grouped
#: product, ``ops/pallas_grouped_matmul.py``, whose custom call reads
#: ``custom-call/grouped_matmul`` under ``moe.experts`` in a trace — the
#: ``lax.ragged_dot`` it replaces carries no ``op_name`` and reads as
#: unscoped ``custom-call/ragged-dot-none`` — and ``mamba_state_update``:
#: the decode step's recurrent-state update, ``ops/pallas_state_update.py``,
#: ``custom-call/mamba_state_update`` under ``mamba`` — and
#: ``paged_decode_grouped``: the paged-decode kernel of a pool of fewer
#: K/V heads than query heads, ``custom-call/paged_decode_grouped`` under
#: ``attn``); ``HybridLM``:
#: ``embed``, ``mamba`` (a Mamba-2 mixer, projections and state update),
#: ``moe.route`` (router, top-k), ``moe.experts`` (latent projections,
#: sort, the kernel's schedule, grouped products), ``moe.shared`` (the
#: shared expert), ``attn`` (a ``*`` layer) and ``attn.window`` (a ``w``
#: layer), each with its projections, rotary, cache write, the attention
#: itself and the head gate inside, ``mlp`` (the ``-`` letter's gated FFN),
#: ``loop.exit`` (a looped stack's end of step: final norm, exit gate,
#: read-out), ``head``
DEVICE_SCOPES = {
    "engine": ("recast", "sample"),
    "TransformerLM": ("embed", "block", "attn", "mlp", "head", "loss"),
    "trainer": ("clip", "exchange", "optimizer"),
    "kernels": ("paged_decode", "paged_decode_grouped", "flash_fwd",
                "flash_bwd_dq", "flash_bwd_dkv", "grouped_matmul",
                "mamba_state_update"),
    "HybridLM": ("embed", "mamba", "moe.route", "moe.experts", "moe.shared",
                 "attn", "attn.window", "mlp", "loop.exit", "head",
                 "diffusion.select"),
}
#: one ``train_iter`` (tags ``step``, ``epoch``; ``loss`` at fenced steps)
TRAIN_SPANS = ("train.step",)
#: the Recorder's segments: a ring span each
RECORDER_SPANS = {"wait": "recorder.wait", "calc": "recorder.calc",
                  "comm": "recorder.comm"}
#: the prefetcher's dequeue (tag ``qsize``: what the queue still held)
PREFETCH_SPANS = ("prefetch.dequeue",)
SERVE_INSTANTS = ("serve.admit", "serve.preempt", "serve.finish")
#: histograms: per-token decode latency and time-to-first-token, both ms
SERVE_HISTOGRAMS = ("serve.token_ms", "serve.ttft_ms")
SERVE_GAUGES = ("serve.tokens_per_sec", "serve.active", "serve.free_blocks")
SERVE_COUNTERS = ("serve.tokens", "serve.preemptions", "serve.requests")

# -- serving request-lifecycle names (ISSUE 14) ------------------------------
# The hardened request lifecycle emits one instant per NON-done terminal
# transition (``done`` keeps the original ``serve.finish``):
# ``serve.expire``: a request blew its ttft/total deadline (tags: request,
# which — "ttft" | "total", where — "queued" | "active" | "drain");
# ``serve.shed``: admission-time load shedding or a drain refused the
# request before any work was done (tags: request, reason, est_wait_ms);
# ``serve.fail``: the livelock guard refused a request that can never fit
# the KV pool (tags: request, need_blocks, pool_blocks); ``serve.drain``:
# the graceful-drain path toggled (tags: phase = "begin" | "end",
# in_flight).  Matching counters below; emitted through these registered
# names ONLY (same one-source-of-truth contract as above).
SERVE_LIFECYCLE_INSTANTS = ("serve.expire", "serve.shed", "serve.fail",
                            "serve.drain")
SERVE_LIFECYCLE_COUNTERS = ("serve.expired", "serve.shed_total",
                            "serve.failed")

# -- decode-kernel names (ISSUE 18) ------------------------------------------
# The fused paged-attention decode path (``ops/pallas_paged_attention.py``)
# is a per-run static choice, so it emits exactly once per serve run:
# ``serve.decode_kernel`` instants at startup record the RESOLVED impl
# (tags: impl — "kernel" | "kernel_interpret" | "fallback", requested —
# the --decode-kernel flag value); ``serve.decode_kernel.step_p50_ms``
# gauges the per-decode-step wall p50 at close, next to the existing
# SERVE_GAUGES — tagged with the impl so an A/B pair in one telemetry dir
# stays attributable.  Emitted through these registered names ONLY (same
# one-source-of-truth contract as above).
SERVE_DECODE_KERNEL_INSTANTS = ("serve.decode_kernel",)
SERVE_DECODE_KERNEL_GAUGES = ("serve.decode_kernel.step_p50_ms",)

# -- prefix-cache names (ISSUE 17) -------------------------------------------
# The radix prefix cache over the paged KV pool accounts every hit exactly:
# ``serve.prefix_hit`` counts admissions whose prompt matched a cached
# full-block prefix; ``serve.prefix_tokens_saved`` accumulates the matched
# prefix lengths — prefill K/V the engine did NOT recompute (the SERVE.json
# ``prefill_tokens_saved`` field is this counter's end-of-run value).
# ``serve.prefix_invalidate`` fires when the engine's ``params_version``
# moved (a live rollout swap/rollback) and the whole tree was dropped —
# cached K/V under old weights is silently wrong under new ones (tags:
# params_version, dropped).  Emitted through these registered names ONLY
# (same one-source-of-truth contract as above).
SERVE_PREFIX_COUNTERS = ("serve.prefix_hit", "serve.prefix_tokens_saved")
SERVE_PREFIX_INSTANTS = ("serve.prefix_invalidate",)

# -- live weight-rollout names (ISSUE 14) ------------------------------------
# ``serve.rollout``: the checkpoint-dir watcher hot-swapped a newly
# VERIFIED checkpoint between scheduler steps (tags: from_epoch, to_epoch,
# preempted — active slots recompute under the new weights, no request is
# dropped); ``serve.rollout_refused``: the newest candidate did not verify
# — corrupt or half-published — so the old weights keep serving (tags:
# epoch, reason); ``serve.rollback``: the health monitor's SLO/throughput
# verdict turned critical inside the probation window, so the previous
# weights were restored (tags: from_epoch, to_epoch, detector, reason).
SERVE_ROLLOUT_INSTANTS = ("serve.rollout", "serve.rollout_refused",
                          "serve.rollback")

# -- elastic-resume instant names (ISSUE 8) ----------------------------------
# The checkpoint reshard path emits through these registered names ONLY
# (same one-source-of-truth contract as the serving names above).
# ``reshard.plan``: a topology mismatch was replanned from the manifest
# alone (tags: epoch, old_n, new_n, strategy, lr_scale, n_buckets);
# ``reshard.apply``: the re-laid-out state was restored onto the live mesh
# (tags: epoch, old_n, new_n).  Both also land as events in the shared
# ``resilience.json`` audit log via ``resilience/events.py``.
RESHARD_INSTANTS = ("reshard.plan", "reshard.apply")

# -- data-plane counter names (ISSUE 10) -------------------------------------
# ``data.retries``: one count per retried shard/token-file read inside
# ``models.data.base.read_with_retry`` (tags: what — the caller's label for
# the resource).  A rising rate is the early witness of a flaky data mount
# long before DataReadError escalates; emitted through this registered name
# ONLY (same one-source-of-truth contract as the serving/reshard names).
DATA_COUNTERS = ("data.retries",)

# -- fleet instant names (ISSUE 11) ------------------------------------------
# Scheduler lifecycle instants, mirrored from the fleet events log into the
# fleet telemetry dir through these registered names ONLY (same
# one-source-of-truth contract as the serving/reshard/data names).
# ``fleet.schedule``: a queued job was gang-allocated devices and launched
# (tags: job, devices, priority); ``fleet.preempt``: a running job was
# SIGTERMed to free devices for a higher-priority one (tags: job, victim_of);
# ``fleet.resume``: a preempted job relaunched elastically on the devices
# that remain (tags: job, devices); ``fleet.complete``/``fleet.fail``: a
# job's final episode ended (tags: job, exit_code); ``fleet.hang``: a
# running job's HEALTH.json published a critical hang verdict (ISSUE 13 —
# tags: job, reason, step; the job's supervisor does the kill+restart, this
# event is the fleet-level audit line); ``fleet.drain``: a serving replica
# was asked to drain and exit clean — the router's scale-down path, ISSUE
# 19 (tags: job).
FLEET_INSTANTS = ("fleet.schedule", "fleet.preempt", "fleet.resume",
                  "fleet.complete", "fleet.fail", "fleet.hang",
                  "fleet.drain")

# -- router names (ISSUE 19) --------------------------------------------------
# The multi-replica router emits through these registered names ONLY (same
# one-source-of-truth contract as every family above).
# ``router.dispatch``: a request was appended to a replica's durable queue
# (tags: request, replica, sticky — whether conversation affinity chose the
# target); ``router.redistribute``: a dead replica's unanswered rids were
# re-appended to survivors' queues (tags: replica, n); ``router.replica_dead``:
# a replica's fleet job turned terminal with work outstanding (tags: replica,
# status); ``router.scale_up``/``router.scale_down``: the autoscale policy
# grew/drained the pool (tags: replica, pressure_s, replicas);
# ``router.duplicate``: a rid reached a second terminal record across
# replicas — the first one won, this is the exactly-once audit witness
# (tags: request, replica).
ROUTER_INSTANTS = ("router.dispatch", "router.redistribute",
                   "router.replica_dead", "router.scale_up",
                   "router.scale_down", "router.duplicate")
#: live pool state, gauged each router tick: replica count, aggregate
#: queued-but-unanswered tokens, rolling router-visible p99 TTFT
ROUTER_GAUGES = ("router.replicas", "router.backlog_tokens",
                 "router.ttft_p99_ms")
#: totals: requests admitted into the router, rids redistributed off dead
#: replicas
ROUTER_COUNTERS = ("router.requests", "router.redistributed")

# -- resilience instant names (ISSUE 13) -------------------------------------
# The resilience layer emits through these registered names ONLY (same
# one-source-of-truth contract as the serving/reshard/data/fleet names, now
# lint-enforced by the ``telemetry-registered-names`` rule).
# ``watchdog.stall``: the adaptive watchdog flagged a stalled step (tags:
# step, stalled_s, threshold_s, escalate); ``sentinel.skip``: the on-device
# non-finite guard skipped a poisoned batch (tags: step, total_skips);
# ``sentinel.nonfinite``: a host-side sentinel policy fired (tags: step,
# policy).
RESILIENCE_INSTANTS = ("watchdog.stall", "sentinel.skip",
                       "sentinel.nonfinite")

# -- live-health names (ISSUE 13) --------------------------------------------
# ``train.boundary`` instants bracket the trainer's beat-free epoch-boundary
# work (validate / checkpoint / prefetcher build) so the arrival-clock hang
# detector suspends across it instead of flagging a healthy boundary (tags:
# epoch, phase = "begin" | "end").  ``health.verdict`` mirrors each non-ok
# verdict the in-process HealthMonitor writes to HEALTH.json into the event
# stream (tags: detector, severity, reason).  Emitted through these
# registered names ONLY (same one-source-of-truth contract as above).
HEALTH_INSTANTS = ("train.boundary", "health.verdict")

# -- overlapped-exchange / quantization-ramp names (ISSUE 12) -----------------
# ``exchange.overlap``: span around (re)arming the chained step fn when
# ``exch_overlap`` is on (tags: strategy) — the overlap itself runs inside
# the compiled program, so arming is the only host-observable moment.
# Emitted through these registered names ONLY (same one-source-of-truth
# contract as the serving/reshard/data/fleet names above).
EXCHANGE_SPANS = ("exchange.overlap",)
#: ``exchange.ramp_phase``: the active ``exch_ramp`` phase index, gauged at
#: each phase switch (tags: epoch); pairs with the ``exchange.ramp_switch``
#: instant (tags: epoch, strategy, phase) and a re-emitted
#: ``exchange.accounting`` instant so wire-byte accounting tracks the phase.
EXCHANGE_GAUGES = ("exchange.ramp_phase",)
EXCHANGE_INSTANTS = ("exchange.ramp_switch",)
#: per-round ICI payload counter (tags: step, and ``shift`` for gossip
#: rounds) — the static accounting every exchange-bearing trainer emits
EXCHANGE_COUNTS = ("exchange.wire_bytes",)

# -- async-rule names (ISSUE 20) ----------------------------------------------
# The straggler-tolerant rules emit ONE instant per exchange/gossip round
# through these registered names ONLY (same one-source-of-truth contract as
# every family above), carrying the fields the ``async_staleness`` health
# detector consumes.  ``easgd.exchange`` (tags: step, staleness — steps
# since the previous elastic round, expected — tau, stretch — wall interval
# of this round vs the rolling median of previous rounds, drift — worst
# per-worker ``max_i(norm(p_i - center)/norm(center))`` computed ON DEVICE
# inside the compiled exchange, so it costs nothing between rounds);
# ``gosgd.round`` (tags: step, staleness — the max over workers of steps
# since each last participated in a push, expected — 1/p_push, shift,
# dropped — an injected ``gosgd:gossip_drop`` skipped the collective).
ASYNC_INSTANTS = ("easgd.exchange", "gosgd.round")
#: flush-boundary gauges mirroring the newest round's fields: per-worker
#: staleness (EASGD rounds are mutually synchronous, so one number; GOSGD
#: gauges the max and mean over workers) and EASGD's relative center drift
ASYNC_GAUGES = ("easgd.staleness", "easgd.center_drift",
                "gosgd.staleness_max", "gosgd.staleness_mean")

# -- step-attribution names (ISSUE 16) ----------------------------------------
# The StepAttributor (``telemetry/profile.py``) publishes per-segment
# per-step p50 milliseconds through these registered names ONLY at flush
# boundaries (same one-source-of-truth contract as above, lint-enforced).
# Train segments: data (prefetch dequeue + recorder wait), compute (fenced
# step), comm (exchange overlap), validate / checkpoint (boundary spans),
# host (unattributed remainder).  Serve segments: queue_wait / prefill /
# decode / rollout_swap.  ``attr.step_ms`` is the wall p50 the segment
# rows partition.
ATTR_GAUGES = ("attr.data_ms", "attr.compute_ms", "attr.comm_ms",
               "attr.validate_ms", "attr.checkpoint_ms", "attr.host_ms",
               "attr.queue_wait_ms", "attr.prefill_ms", "attr.decode_ms",
               "attr.rollout_swap_ms", "attr.step_ms")
#: segment name -> registered gauge name (derived, one source of truth)
ATTR_GAUGE_BY_SEGMENT = {
    name[len("attr."):-len("_ms")]: name for name in ATTR_GAUGES
}
#: per-device HBM watermarks sampled at fenced flush boundaries (worst
#: device wins the gauge; the per-device dict rides ATTRIB.json):
#: peak = high-water ``peak_bytes_in_use``, live = last ``bytes_in_use``,
#: limit = smallest ``bytes_limit``.  Absent entirely on CPU backends.
PROF_GAUGES = ("prof.hbm_peak_bytes", "prof.hbm_live_bytes",
               "prof.hbm_limit_bytes")
#: ``prof.window``: the jax.profiler trace window opened/closed at the
#: configured ``profile_window`` iterations (tags: phase = "start" |
#: "stop", iteration) — the host-trace marker that aligns the device
#: trace with the event stream.
PROF_INSTANTS = ("prof.window",)
#: ``ledger.regression``: the HealthMonitor's perf detector mirrored a
#: regression verdict from TMPROF_LEDGER.jsonl (tags: metric, delta_pct).
LEDGER_INSTANTS = ("ledger.regression",)


class MetricsRegistry:
    """Named counters (monotonic totals), gauges (last value), histograms
    (bounded sample windows with percentile readout)."""

    def __init__(self, histogram_window: int = 1024):
        self.counters: dict[str, float] = defaultdict(float)
        self.gauges: dict[str, float] = {}
        self._hists: dict[str, list] = defaultdict(list)
        self._hist_window = histogram_window

    def count(self, name: str, value: float = 1.0) -> float:
        """Increment counter ``name``; -> new cumulative total."""
        self.counters[name] += value
        return self.counters[name]

    def gauge(self, name: str, value: float) -> None:
        self.gauges[name] = float(value)

    def observe(self, name: str, value: float) -> None:
        h = self._hists[name]
        h.append(float(value))
        if len(h) > self._hist_window:
            del h[: len(h) - self._hist_window]

    def percentiles(self, name: str, qs=(50, 95, 99)) -> dict[str, float]:
        h = self._hists.get(name)
        if not h:
            return {}
        arr = np.asarray(h)
        return {f"p{q}": float(np.percentile(arr, q)) for q in qs}

    def snapshot(self) -> dict:
        """Flush-boundary view: totals, gauges, histogram percentiles."""
        out: dict = {}
        if self.counters:
            out["counters"] = dict(self.counters)
        if self.gauges:
            out["gauges"] = dict(self.gauges)
        hists = {k: self.percentiles(k) for k in self._hists}
        hists = {k: v for k, v in hists.items() if v}
        if hists:
            out["histograms"] = hists
        return out


#: Published peaks of one chip, keyed by jax's ``device_kind``.  Source:
#: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 393 TOP/s
#: int8, 16 GB of HBM at 819 GB/s.  It lists the devices this repo has run
#: on and nothing else: a kind that is not here is an error, not a default
#: peak (the train.mfu gauge reads it).
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "int8_tops": 393.0,
                    "hbm_gbps": 819.0, "hbm_gb": 16.0},
}


def device_peaks(device_kind: str | None = None) -> dict:
    """The :data:`DEVICE_PEAKS` row of ``device_kind`` (default: the kind
    of jax's first device); ``KeyError`` naming the kind when unknown."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add its "
            f"row (with its source) to telemetry.metrics.DEVICE_PEAKS — a "
            f"utilisation against a guessed peak is not a number") from None


def peak_flops() -> float | None:
    """bf16 peak FLOP/s of the chip under this process, or None on the
    CPU backend (a utilisation is a device metric; a CPU run has none)."""
    import jax

    if jax.default_backend() == "cpu":
        return None
    return device_peaks()["bf16_tflops"] * 1e12


def step_flops_estimate(trainer, batch) -> float | None:
    """FLOPs per train step from XLA's cost analysis of the compiled step.

    Caveats of that accounting: Pallas custom-calls count zero and scan
    bodies count once.  Scaled by ``n_subb`` for gradient accumulation.
    Returns None when cost analysis is unavailable; callers then simply
    omit MFU.
    """
    try:
        fl = float(trainer.compiled_step(batch).cost_analysis()
                   .get("flops", 0.0))
        if fl <= 0:
            return None
        n_subb = int(trainer.model.config.get("n_subb", 1) or 1)
        return fl * n_subb if n_subb > 1 else fl
    except Exception:  # lint: swallow-ok — an optional gauge must not end training
        return None


def mfu(flops_per_step: float, step_time_s: float,
        peak: float | None) -> float | None:
    if not peak or step_time_s <= 0:
        return None
    return flops_per_step / step_time_s / peak


#: the memory_stats keys worth keeping (the rest are allocator internals)
_MEMORY_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")


def per_device_memory_stats() -> dict[int, dict]:
    """HBM stats for EVERY local device: ``{device_index: {bytes_in_use,
    peak_bytes_in_use, bytes_limit}}``.

    None-safe throughout (ISSUE 16): devices whose ``memory_stats()`` is
    missing, raises, or returns empty (the CPU backend) are skipped, so
    CPU-only processes get ``{}`` rather than an exception — a straggling
    device without stats never hides the ones that have them.
    """
    try:
        import jax

        devices = jax.local_devices()
    except Exception:  # lint: swallow-ok — no backend at all
        return {}
    out: dict[int, dict] = {}
    for i, dev in enumerate(devices):
        try:
            stats = dev.memory_stats()
        except Exception:  # lint: swallow-ok — backends without memory stats
            continue
        if not stats:
            continue
        out[i] = {k: int(stats[k]) for k in _MEMORY_KEYS if k in stats}
    return out


def device_memory_stats() -> dict | None:
    """HBM stats of local device 0 (None on backends without them — CPU).

    Kept for existing callers; the per-device form above is the ISSUE 16
    watermark source.
    """
    stats = per_device_memory_stats()
    return stats.get(0) or None
