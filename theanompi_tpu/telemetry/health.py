"""Streaming run-health detectors over the live event stream (ISSUE 13).

PR 1's telemetry is write-only: per-rank JSONL sinks aggregated *after*
the run ends, so a hung job burns its fleet lease until a blunt timeout
and a crashed one leaves only an exit code.  :class:`HealthMonitor` turns
the same event stream into *in-flight* typed verdicts:

- ``hang`` — the arrival-clock deadline: event ``ts`` values are
  per-process ``perf_counter`` epochs (not comparable across processes),
  so liveness is judged by *when events arrive on the monitor's own
  clock*.  Armed only after ``hang_warmup_steps`` ``train.step`` spans
  (compile-heavy first steps never trip it), suspended between
  ``train.boundary`` begin/end instants (validate/checkpoint are
  legitimately span-free), disarmed at ``session_end``.
- ``straggler`` — the incremental form of ``aggregate.summarize_events``'s
  step-skew math: per-rank ``train.step`` duration windows, skew over the
  steps every rank reported, worst-rank mean vs the fleet mean.  A
  single-process monitor only ever sees its own rank; the detector earns
  its keep when ``tmhealth`` replays a whole directory of ranks.
- ``loss`` — EWMA z-score on the ``loss`` tag of ``train.step`` spans;
  a non-finite loss is an immediate ``critical``, a spike past
  ``loss_z`` standard deviations is a ``warn``.
- ``throughput`` — recent median step duration vs a rolling baseline
  median; a ``throughput_factor`` slowdown is a ``warn``.
- ``checkpoint`` — checkpoints were happening and then stopped: once a
  ``checkpoint.*`` span has been seen, steps advancing for longer than
  ``checkpoint_deadline_s`` without another is a ``warn``.
- ``slo`` — serving SLO breach: p99 of the ``serve.ttft_ms`` histogram
  (carried by ``metrics`` flush events) above ``slo_ttft_p99_ms``; at
  ``slo_critical_factor`` x the SLO the verdict turns *critical*, which
  the serving rollout watcher's probation window treats as the
  roll-back-now signal (ISSUE 14).
- ``async_staleness`` — the async rules' graceful-degradation witness
  (ISSUE 20), fed by the per-round ``easgd.exchange`` / ``gosgd.round``
  instants: *warn* when staleness skews past the rule's expected cadence
  or the exchange wall-interval stretches past the rolling median for
  ``async_min_rounds`` consecutive rounds (a straggler the rule is
  absorbing — degraded, not broken); *critical* only when the relative
  center drift blows past ``async_drift_critical`` (the elastic coupling
  is no longer bounding divergence — correctness, not throughput).

Verdicts are written atomically to ``HEALTH.json`` in the telemetry
directory by the owning :class:`~theanompi_tpu.telemetry.core.Telemetry`'s
ticker thread; ``resilience/supervisor.py`` and ``fleet/scheduler.py``
consume the file with plain ``json`` (no import of this module needed in
the stdlib-only supervisor).  Off means off: no ``Telemetry`` → no
monitor; a ``Telemetry`` with ``health=None`` makes zero calls here.
"""

from __future__ import annotations

import json
import math
import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field

HEALTH_FILENAME = "HEALTH.json"

SEV_OK = "ok"
SEV_WARN = "warn"
SEV_CRITICAL = "critical"


@dataclass
class Verdict:
    """One detector's current judgement of the run."""

    detector: str
    severity: str   # ok | warn | critical
    reason: str
    step: int | None = None
    fields: dict = field(default_factory=dict)

    def to_dict(self) -> dict:
        out = {"detector": self.detector, "severity": self.severity,
               "reason": self.reason}
        if self.step is not None:
            out["step"] = int(self.step)
        if self.fields:
            out["fields"] = self.fields
        return out


@dataclass
class HealthConfig:
    """Detector thresholds.  Every deadline is in seconds on the
    monitor's own clock (event *arrival*, never event ``ts``)."""

    tick_s: float = 1.0
    hang_deadline_s: float = 60.0
    hang_warmup_steps: int = 3
    window: int = 64                  # per-rank step-duration window
    straggler_ratio: float = 1.5
    straggler_min_steps: int = 4
    loss_z: float = 6.0
    loss_warmup: int = 8
    loss_ewma_alpha: float = 0.1
    throughput_factor: float = 2.0
    throughput_min_steps: int = 16
    throughput_recent: int = 8
    checkpoint_deadline_s: float = 600.0
    slo_ttft_p99_ms: float | None = None
    #: ISSUE 14: a p99 at or past ``slo_critical_factor`` x the SLO is a
    #: CRITICAL verdict (not just a warn) — the serving rollout watcher's
    #: probation window rolls back on it
    slo_critical_factor: float = 2.0
    #: ISSUE 16: watch a ``TMPROF_LEDGER.jsonl`` for typed regression
    #: verdicts (``telemetry/ledger.py``) — None keeps the detector off.
    #: The file is re-checked only when its mtime moves, so an armed
    #: detector costs one ``stat`` per tick.
    perf_ledger_path: str | None = None
    perf_tolerance: float = 0.10
    #: ISSUE 20 async_staleness thresholds: a round is BAD when its
    #: staleness reaches ``async_staleness_factor`` x the rule's expected
    #: cadence, or its wall interval ``async_stretch_factor`` x the
    #: rolling median of previous rounds; ``async_min_rounds`` consecutive
    #: bad rounds make a warn.  Drift at/past ``async_drift_critical``
    #: (relative ``||p_i - center|| / ||center||``) is critical outright.
    async_staleness_factor: float = 3.0
    async_stretch_factor: float = 2.5
    async_min_rounds: int = 2
    async_drift_critical: float = 5.0


def _median(xs) -> float:
    s = sorted(xs)
    n = len(s)
    mid = n // 2
    return s[mid] if n % 2 else (s[mid - 1] + s[mid]) / 2.0


class HealthMonitor:
    """Feed it every emitted event (``observe``); poll it (``tick``).

    Thread-safe: the train loop observes while the Telemetry ticker
    thread ticks and writes.  ``tick`` returns the verdicts that
    *changed* since the last tick so the caller can mirror transitions
    into the event stream without holding the monitor's lock.
    """

    def __init__(self, directory: str, config: HealthConfig | None = None,
                 rank: int = 0, clock=time.perf_counter):
        self.directory = directory
        self.config = config or HealthConfig()
        self.rank = rank
        self._clock = clock
        self._lock = threading.Lock()
        self._verdicts: dict[str, Verdict] = {}
        self._published: dict[str, tuple] = {}  # detector -> (sev, reason)
        # hang state
        self._last_arrival = clock()
        self._steps = 0
        self._last_step: int | None = None
        self._boundary_depth = 0
        self._ended = False
        # straggler state: rank -> {step -> dur}, bounded per rank
        self._step_durs: dict[int, dict[int, float]] = {}
        # loss EWMA state
        self._loss_n = 0
        self._loss_mean = 0.0
        self._loss_var = 0.0
        # throughput state
        self._durs: deque = deque(maxlen=self.config.window)
        # checkpoint state
        self._last_ckpt: float | None = None
        self._steps_at_ckpt = 0
        # perf-ledger state (ISSUE 16): mtime cache so an unchanged
        # ledger costs one stat per tick, not a reparse
        self._perf_mtime: float | None = None
        # async-rule state (ISSUE 20): consecutive bad-round streak
        self._async_bad_rounds = 0

    # -- ingestion -----------------------------------------------------------
    def observe(self, event: dict, now: float | None = None) -> None:
        """Feed one emitted event.  O(window) worst case, dict updates
        typically — safe on the hot path."""
        now = self._clock() if now is None else now
        with self._lock:
            self._last_arrival = now
            kind = event.get("kind")
            name = event.get("name")
            if kind == "meta" and name == "session_end":
                self._ended = True
                self._set("hang", SEV_OK, "session ended cleanly")
            elif kind == "instant" and name == "train.boundary":
                if event.get("phase") == "begin":
                    self._boundary_depth += 1
                else:
                    self._boundary_depth = max(0, self._boundary_depth - 1)
            elif kind == "span" and name == "train.step":
                self._observe_step(event)
            elif kind == "instant" and name in ("easgd.exchange",
                                                "gosgd.round"):
                self._observe_async(event)
            elif name is not None and str(name).startswith("checkpoint."):
                self._last_ckpt = now
                self._steps_at_ckpt = self._steps
                self._set("checkpoint", SEV_OK, "checkpoint activity")
            elif kind == "metrics":
                self._observe_metrics(event)

    def _observe_step(self, event: dict) -> None:
        cfg = self.config
        self._steps += 1
        step = event.get("step")
        self._last_step = int(step) if step is not None else self._last_step
        dur = float(event.get("dur", 0.0))
        rank = int(event.get("rank", 0))
        if step is not None:
            durs = self._step_durs.setdefault(rank, {})
            durs[int(step)] = dur
            if len(durs) > cfg.window:
                del durs[min(durs)]
            self._eval_straggler()
        self._durs.append(dur)
        self._eval_throughput()
        if "loss" in event:
            self._eval_loss(float(event["loss"]))
        # a step arriving clears a previous hang verdict: the run moved
        if self._steps >= cfg.hang_warmup_steps:
            self._set("hang", SEV_OK, "events flowing")

    def _observe_metrics(self, event: dict) -> None:
        cfg = self.config
        if cfg.slo_ttft_p99_ms is None:
            return
        p99 = (event.get("histograms") or {}).get("serve.ttft_ms",
                                                  {}).get("p99")
        if p99 is None:
            return
        if p99 > cfg.slo_ttft_p99_ms:
            critical = p99 >= cfg.slo_ttft_p99_ms * cfg.slo_critical_factor
            self._set("slo", SEV_CRITICAL if critical else SEV_WARN,
                      f"serve.ttft_ms p99 {p99:.1f}ms breaches SLO "
                      f"{cfg.slo_ttft_p99_ms:.1f}ms"
                      + (f" by >= {cfg.slo_critical_factor:g}x"
                         if critical else ""),
                      fields={"p99_ms": round(float(p99), 3),
                              "slo_ms": cfg.slo_ttft_p99_ms})
        else:
            self._set("slo", SEV_OK, "serve.ttft_ms p99 within SLO")

    def _observe_async(self, event: dict) -> None:
        """ISSUE 20: one ``easgd.exchange`` / ``gosgd.round`` instant per
        exchange round.  Severity contract (the chaos acceptance leans on
        it): a straggler the rule absorbs is at most a WARN — sustained
        staleness skew or interval stretch says "degraded, still
        converging"; only a center-drift blow-up (the elastic coupling no
        longer bounds divergence, a correctness signal) is CRITICAL."""
        cfg = self.config
        step = event.get("step")
        step = int(step) if step is not None else self._last_step
        name = event.get("name")
        drift = event.get("drift")
        if drift is not None and float(drift) >= cfg.async_drift_critical:
            self._set("async_staleness", SEV_CRITICAL,
                      f"center drift {float(drift):.3g} at/past "
                      f"{cfg.async_drift_critical:g} — the elastic "
                      f"coupling is not bounding worker divergence",
                      step=step,
                      fields={"drift": round(float(drift), 6),
                              "critical_at": cfg.async_drift_critical})
            return
        staleness = float(event.get("staleness", 0.0) or 0.0)
        expected = max(float(event.get("expected", 1.0) or 1.0), 1.0)
        stretch = float(event.get("stretch", 0.0) or 0.0)
        stale_skew = staleness >= expected * cfg.async_staleness_factor
        stretched = stretch >= cfg.async_stretch_factor
        if stale_skew or stretched:
            self._async_bad_rounds += 1
        else:
            self._async_bad_rounds = 0
        fields = {"staleness": staleness, "expected": expected,
                  "stretch": round(stretch, 3),
                  "bad_rounds": self._async_bad_rounds}
        if drift is not None:
            fields["drift"] = round(float(drift), 6)
        if self._async_bad_rounds >= cfg.async_min_rounds:
            why = (f"staleness {staleness:g} is >= "
                   f"{cfg.async_staleness_factor:g}x the expected "
                   f"cadence {expected:g}" if stale_skew else
                   f"exchange interval stretched {stretch:.2f}x the "
                   f"rolling median")
            self._set("async_staleness", SEV_WARN,
                      f"{name}: {why} for {self._async_bad_rounds} "
                      f"consecutive round(s) — straggler being absorbed",
                      step=step, fields=fields)
        else:
            self._set("async_staleness", SEV_OK,
                      "async exchange cadence healthy", step=step,
                      fields=fields)

    # -- detectors -----------------------------------------------------------
    def _eval_straggler(self) -> None:
        cfg = self.config
        ranks = [r for r, d in self._step_durs.items() if d]
        if len(ranks) < 2:
            return
        common = set.intersection(*(set(self._step_durs[r]) for r in ranks))
        if len(common) < cfg.straggler_min_steps:
            return
        means = {r: sum(self._step_durs[r].values())
                 / len(self._step_durs[r]) for r in ranks}
        fleet = sum(means.values()) / len(means)
        worst = max(means, key=means.get)
        ratio = means[worst] / fleet if fleet else 0.0
        skews = [max(self._step_durs[r][s] for r in ranks)
                 - min(self._step_durs[r][s] for r in ranks)
                 for s in common]
        fields = {
            "rank": worst,
            "mean_step_ms": round(means[worst] * 1e3, 3),
            "vs_fleet_mean": round(ratio, 3),
            "step_skew_ms": {"mean": round(_median(skews) * 1e3, 3),
                             "max": round(max(skews) * 1e3, 3),
                             "steps_compared": len(skews)},
        }
        if ratio >= cfg.straggler_ratio:
            self._set("straggler", SEV_WARN,
                      f"rank {worst} runs {ratio:.2f}x the fleet mean "
                      f"step time", fields=fields)
        else:
            self._set("straggler", SEV_OK,
                      f"skew within {cfg.straggler_ratio}x", fields=fields)

    def _eval_loss(self, x: float) -> None:
        cfg = self.config
        if not math.isfinite(x):
            self._set("loss", SEV_CRITICAL, f"non-finite loss {x!r}",
                      step=self._last_step)
            return
        if self._loss_n >= cfg.loss_warmup:
            sd = math.sqrt(max(self._loss_var, 0.0))
            z = (x - self._loss_mean) / sd if sd > 1e-12 else 0.0
            if z > cfg.loss_z:
                self._set("loss", SEV_WARN,
                          f"loss {x:.4g} is {z:.1f} sigma above the EWMA "
                          f"{self._loss_mean:.4g}",
                          step=self._last_step,
                          fields={"z": round(z, 2),
                                  "ewma": round(self._loss_mean, 6)})
            else:
                self._set("loss", SEV_OK, "loss within band",
                          step=self._last_step)
        self._loss_n += 1
        diff = x - self._loss_mean
        incr = cfg.loss_ewma_alpha * diff
        self._loss_mean += incr
        self._loss_var = (1 - cfg.loss_ewma_alpha) * (self._loss_var
                                                      + diff * incr)

    def _eval_throughput(self) -> None:
        cfg = self.config
        n = len(self._durs)
        if n < max(cfg.throughput_min_steps, cfg.throughput_recent + 2):
            return
        durs = list(self._durs)
        recent = _median(durs[-cfg.throughput_recent:])
        baseline = _median(durs[:-cfg.throughput_recent])
        fields = {"recent_ms": round(recent * 1e3, 3),
                  "baseline_ms": round(baseline * 1e3, 3)}
        if baseline > 0 and recent > baseline * cfg.throughput_factor:
            self._set("throughput", SEV_WARN,
                      f"recent step time {recent * 1e3:.1f}ms is "
                      f"{recent / baseline:.2f}x the rolling baseline",
                      step=self._last_step, fields=fields)
        else:
            self._set("throughput", SEV_OK, "throughput holding baseline",
                      step=self._last_step, fields=fields)

    def _eval_perf(self) -> None:
        """ISSUE 16 perf detector: mirror the ledger's typed regression
        verdicts as a live ``warn``.  The check is the lock-free
        :func:`~theanompi_tpu.telemetry.ledger.check_ledger` read — no
        ledger lock ever nests inside the health lock."""
        cfg = self.config
        if cfg.perf_ledger_path is None:
            return
        try:
            mtime = os.path.getmtime(cfg.perf_ledger_path)
        except OSError:
            return  # no ledger yet — the detector stays silent
        if mtime == self._perf_mtime:
            return
        self._perf_mtime = mtime
        from theanompi_tpu.telemetry.ledger import check_ledger, regressions

        bad = regressions(check_ledger(cfg.perf_ledger_path,
                                       tolerance=cfg.perf_tolerance))
        if bad:
            worst = max(bad, key=lambda v: abs(v.get("delta_pct") or 0.0))
            self._set("perf", SEV_WARN,
                      f"{len(bad)} perf metric(s) regressed past "
                      f"{cfg.perf_tolerance:.0%}: {worst['metric']} "
                      f"{worst['delta_pct']:+.1f}% vs trailing median",
                      fields={"regressed": [v["metric"] for v in bad],
                              "worst_delta_pct": worst["delta_pct"],
                              "tolerance_pct": worst["tolerance_pct"]})
        else:
            self._set("perf", SEV_OK, "no perf regressions in ledger")

    def _set(self, detector: str, severity: str, reason: str,
             step: int | None = None, fields: dict | None = None) -> None:
        self._verdicts[detector] = Verdict(
            detector, severity, reason,
            step=step if step is not None else self._last_step,
            fields=fields or {})

    # -- polling -------------------------------------------------------------
    def tick(self, now: float | None = None) -> list[Verdict]:
        """Evaluate the time-based detectors; -> verdicts that changed
        severity-or-reason since the last tick (for event mirroring)."""
        now = self._clock() if now is None else now
        cfg = self.config
        with self._lock:
            stalled = now - self._last_arrival
            if (not self._ended and self._boundary_depth == 0
                    and self._steps >= cfg.hang_warmup_steps
                    and stalled > cfg.hang_deadline_s):
                self._set("hang", SEV_CRITICAL,
                          f"no events for {stalled:.1f}s "
                          f"(deadline {cfg.hang_deadline_s:g}s)",
                          fields={"stalled_s": round(stalled, 1),
                                  "deadline_s": cfg.hang_deadline_s})
            if (self._last_ckpt is not None and not self._ended
                    and self._steps > self._steps_at_ckpt
                    and now - self._last_ckpt > cfg.checkpoint_deadline_s):
                self._set("checkpoint", SEV_WARN,
                          f"steps advanced but no checkpoint for "
                          f"{now - self._last_ckpt:.0f}s",
                          fields={"since_s": round(now - self._last_ckpt, 1),
                                  "deadline_s": cfg.checkpoint_deadline_s})
            self._eval_perf()
            changed = []
            for det, v in self._verdicts.items():
                key = (v.severity, v.reason)
                if self._published.get(det, (SEV_OK, None))[0] != v.severity:
                    changed.append(v)
                self._published[det] = key
            return changed

    def verdicts(self) -> list[dict]:
        with self._lock:
            return [v.to_dict() for v in self._verdicts.values()]

    def worst_severity(self) -> str:
        order = {SEV_OK: 0, SEV_WARN: 1, SEV_CRITICAL: 2}
        with self._lock:
            sevs = [v.severity for v in self._verdicts.values()]
        return max(sevs, key=lambda s: order.get(s, 0), default=SEV_OK)

    # -- persistence ---------------------------------------------------------
    def write(self, path: str | None = None) -> str:
        """Atomically publish ``HEALTH.json`` (tmp + ``os.replace`` — a
        reader never sees a torn file)."""
        path = path or os.path.join(self.directory, HEALTH_FILENAME)
        payload = {
            # wall stamp: external consumers (supervisor, tmhealth, a
            # human) correlate it with their own clocks
            "updated": time.time(),  # lint: wall-ok — cross-process stamp
            "pid": os.getpid(),
            "rank": self.rank,
            "steps": self._steps,
            "verdicts": self.verdicts(),
        }
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1)
        os.replace(tmp, path)
        return path


def read_health(directory: str) -> dict | None:
    """Parse ``<directory>/HEALTH.json``; None when absent/unreadable."""
    path = os.path.join(directory, HEALTH_FILENAME)
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    return data if isinstance(data, dict) else None


def hung_verdict(health: dict | None) -> dict | None:
    """The critical ``hang`` verdict out of a ``HEALTH.json`` payload, or
    None.  Shared predicate for the supervisor/fleet consumers (they read
    the file with plain ``json`` but agree on the shape through this)."""
    if not health:
        return None
    for v in health.get("verdicts", ()):
        if (isinstance(v, dict) and v.get("detector") == "hang"
                and v.get("severity") == SEV_CRITICAL):
            return v
    return None


def replay_events(events, config: HealthConfig | None = None,
                  directory: str = "") -> HealthMonitor:
    """Run the streaming detectors over already-recorded events (the
    ``tmhealth`` offline path).  Arrival-clock detectors (hang) cannot
    fire meaningfully in a replay — the caller judges staleness from
    sink-file mtimes instead."""
    mon = HealthMonitor(directory, config)
    t = 0.0
    for ev in events:
        t += 1e-9  # synthetic strictly-increasing arrival clock
        mon.observe(ev, now=t)
    mon.tick(now=t)
    return mon
