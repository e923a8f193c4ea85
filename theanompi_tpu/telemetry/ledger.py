"""Durable perf-regression ledger (ISSUE 16).

Every report a run of this program leaves behind — ``tmserve``'s
``SERVE.json``, ``tmrouter``'s ``ROUTER.json``, each telemetry run's
``ATTRIB.json``, the convergence gate's ``CONVERGE.json`` — is a
write-once snapshot: one run's number says nothing about whether the next
one regressed.  :class:`PerfLedger` turns them into one append-only
trajectory:

- ``TMPROF_LEDGER.jsonl`` — one normalized record per measurement,
  appended (never rewritten) with a line-granular crash contract: a torn
  final line is skipped on read, everything before it survives.  Each
  record carries a content fingerprint so re-ingesting the same artifact
  (a re-run backfill) is idempotent.
- ``TMPROF_LEDGER.json`` — an atomically-replaced (tmp + ``os.replace``)
  per-metric summary snapshot for dashboards that want one file.
- ``check()`` — typed regression verdicts per metric: the latest point
  vs the trailing median of the previous ``window`` points, with the
  tolerance stated in the verdict.  Direction is inferred from the unit
  (``ms`` down is good, ``/sec``/``mfu``/``efficiency`` up is good).
  A record without a metric or a value never enters a baseline.

Consumers: ``tmprof --ledger`` drives update/check/backfill from the
CLI, and the HealthMonitor's ``perf`` detector surfaces regressions as
live ``warn`` verdicts (ISSUE 13 plumbing, new detector).

The file is this program's own trajectory and is named apart from the
driver's ``PERF_LEDGER.jsonl`` at the repo root, which no code here reads
or writes.
"""

from __future__ import annotations

import glob
import hashlib
import json
import os
import threading
import time

LEDGER_FILENAME = "TMPROF_LEDGER.jsonl"
SNAPSHOT_FILENAME = "TMPROF_LEDGER.json"

#: default trailing-median window and relative tolerance for check()
DEFAULT_WINDOW = 5
DEFAULT_TOLERANCE = 0.10

#: artifact glob patterns backfill() ingests, in trajectory order
BACKFILL_PATTERNS = ("SERVE*.json", "ROUTER*.json", "ATTRIB.json",
                     "CONVERGE*.json")

#: unit substrings that mean lower-is-better; everything else (rates,
#: mfu, efficiency, shares) improves upward
_LOWER_BETTER_UNITS = ("ms", "seconds")


def _fingerprint(record: dict) -> str:
    """Content hash over the identity fields — the idempotency key."""
    ident = {k: record.get(k) for k in
             ("source", "kind", "metric", "run_id", "value")}
    return hashlib.sha1(
        json.dumps(ident, sort_keys=True).encode()).hexdigest()[:16]


def make_record(source: str, kind: str, metric: str | None,
                value: float | None, unit: str = "",
                run_id: str | None = None, **extra) -> dict:
    rec = {
        "schema": 1,
        # wall stamp: trajectories correlate runs across machines/processes
        "ts": time.time(),  # lint: wall-ok — cross-process trajectory stamp
        "source": source,
        "kind": kind,
        "metric": metric,
        "value": None if value is None else float(value),
        "unit": unit,
        "run_id": run_id,
    }
    if extra:
        rec["extra"] = extra
    rec["fp"] = _fingerprint(rec)
    return rec


def lower_is_better(metric: str, unit: str) -> bool:
    m = (metric or "").lower()
    u = (unit or "").lower()
    if m.endswith("_ms") or "step_ms" in m or "ttft" in m or "latency" in m:
        return True
    return any(x == u or u.endswith(x) for x in _LOWER_BETTER_UNITS)


# -- artifact classifiers ----------------------------------------------------

def classify_artifact(name: str, payload: dict) -> list[dict]:
    """Normalize one known artifact into ledger records.

    Unknown shapes yield nothing rather than noise — the ledger only
    tracks metrics something can be held to.
    """
    if not isinstance(payload, dict):
        return []
    base = os.path.basename(name)
    run_id = payload.get("run_id")
    # SERVE.json: the tmserve report (serve_report()).
    if base.startswith("SERVE"):
        recs = []
        tps = payload.get("value", payload.get("tokens_per_sec"))
        if tps is not None:
            recs.append(make_record(base, "serve", "serve.tokens_per_sec",
                                    tps, "tokens/sec", run_id=run_id))
        for key in ("ttft", "token"):
            pcts = payload.get(f"{key}_ms")
            pcts = pcts if isinstance(pcts, dict) else {}
            for p in ("p50", "p99"):
                val = pcts.get(p, payload.get(f"{key}_{p}_ms"))
                if val is not None:
                    recs.append(make_record(base, "serve",
                                            f"serve.{key}_{p}_ms", val,
                                            "ms", run_id=run_id))
        # ISSUE 18 decode-kernel A/B: per-step wall is keyed BY VARIANT so
        # a kernel-on run never regresses against a fallback baseline
        variant = payload.get("decode_kernel")
        step_pcts = payload.get("decode_step_ms")
        if variant and isinstance(step_pcts, dict):
            for p in ("p50", "p99"):
                if step_pcts.get(p) is not None:
                    recs.append(make_record(
                        base, "serve",
                        f"serve.decode.{variant}.step_{p}_ms",
                        step_pcts[p], "ms", run_id=run_id))
        # prefix-cache accounting (ISSUE 17): only a cache-on run enters
        # the trajectory — cache-off zeros would poison the baseline
        if payload.get("prefix_cache"):
            for field, unit in (("prefix_hit_rate", "rate"),
                                ("prefill_tokens_saved", "tokens")):
                if payload.get(field) is not None:
                    recs.append(make_record(base, "serve",
                                            f"serve.{field}",
                                            payload[field], unit,
                                            run_id=run_id))
        return recs
    # ROUTER.json: the tmrouter multi-replica report (ISSUE 19).
    if base.startswith("ROUTER"):
        recs = []
        tps = payload.get("value", payload.get("tokens_per_sec"))
        if tps is not None:
            recs.append(make_record(base, "router",
                                    "router.tokens_per_sec", tps,
                                    "tokens/sec", run_id=run_id))
        pcts = payload.get("ttft_ms")
        pcts = pcts if isinstance(pcts, dict) else {}
        for p in ("p50", "p99"):
            if pcts.get(p) is not None:
                recs.append(make_record(base, "router",
                                        f"router.ttft_{p}_ms", pcts[p],
                                        "ms", run_id=run_id))
        if payload.get("replicas_peak") is not None:
            recs.append(make_record(base, "router", "router.replicas_peak",
                                    payload["replicas_peak"], "replicas",
                                    run_id=run_id))
        return recs
    # CONVERGE.json: utils/converge.py gate report (ISSUE 20 trending).
    # Each row's margin (target_error - best_val_error) enters the
    # trajectory as a higher-is-better point, so a rule that still
    # passes but with shrinking headroom shows up in check() before it
    # ever fails the gate.  Async rules (EASGD/GOSGD) ride the same
    # branch — the rule name is carried in extra for filtering.
    if base.startswith("CONVERGE") and isinstance(
            payload.get("results"), list):
        recs = []
        for row in payload["results"]:
            if not isinstance(row, dict):
                continue
            target = row.get("target_error")
            best = row.get("best_val_error")
            if target is None or best is None:
                continue
            name_key = row.get("model", "model")
            recs.append(make_record(
                base, "converge", f"converge.{name_key}.margin",
                float(target) - float(best), "margin", run_id=run_id,
                rule=row.get("rule"), passed=row.get("passed"),
                epochs_to_target=row.get("epochs_to_target")))
        return recs
    # ATTRIB.json: per-run attribution summary (telemetry/profile.py)
    if "per_rank" in payload:
        recs = []
        rid = run_id or (f"pid{payload['pid']}" if "pid" in payload
                         else None)
        for rank, res in sorted(payload["per_rank"].items()):
            mode = res.get("mode", "train")
            wall = (res.get("wall_step") or {}).get("p50_ms")
            if wall is not None:
                recs.append(make_record(base, "attrib",
                                        f"attrib.{mode}.step_ms", wall,
                                        "ms", run_id=rid, rank=rank))
            for seg, st in sorted((res.get("segments") or {}).items()):
                if st.get("share") is not None:
                    recs.append(make_record(
                        base, "attrib", f"attrib.{mode}.{seg}_share",
                        st["share"], "share", run_id=rid, rank=rank))
        return recs
    return []


# -- reading -----------------------------------------------------------------

def read_ledger(path: str) -> list[dict]:
    """All well-formed records, append order.  A torn final line (the
    crash contract of an append-only log) is skipped, as are foreign
    lines — readers never fail on a half-written ledger."""
    records: list[dict] = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict) and rec.get("schema") == 1:
                    records.append(rec)
    except OSError:
        return []
    return records


def trajectories(records: list[dict]) -> dict[str, list[dict]]:
    """metric -> append-ordered measurable points.  A record without a
    metric or a value stays in the log but never enters a baseline."""
    out: dict[str, list[dict]] = {}
    for rec in records:
        metric, value = rec.get("metric"), rec.get("value")
        if metric is None or value is None:
            continue
        out.setdefault(metric, []).append(rec)
    return out


def _median(xs) -> float:
    s = sorted(xs)
    n = len(s)
    mid = n // 2
    return s[mid] if n % 2 else (s[mid - 1] + s[mid]) / 2.0


def check_records(records: list[dict],
                  tolerance: float = DEFAULT_TOLERANCE,
                  window: int = DEFAULT_WINDOW) -> list[dict]:
    """Typed per-metric verdicts: latest vs trailing median.

    ``regression`` — latest is worse than the median of the previous
    ``window`` points by more than ``tolerance`` (relative);
    ``improvement`` — better by more than ``tolerance``; ``ok`` —
    within band; ``insufficient_history`` — fewer than 2 points.
    """
    verdicts = []
    for metric, points in sorted(trajectories(records).items()):
        latest = points[-1]
        unit = latest.get("unit", "")
        down = lower_is_better(metric, unit)
        base = {"metric": metric, "unit": unit,
                "direction": "lower_is_better" if down
                else "higher_is_better",
                "latest": latest["value"], "n_points": len(points),
                "tolerance_pct": round(tolerance * 100, 2)}
        if len(points) < 2:
            verdicts.append({**base, "verdict": "insufficient_history",
                             "baseline": None, "delta_pct": None})
            continue
        baseline = _median([p["value"] for p in points[:-1]][-window:])
        delta = ((latest["value"] - baseline) / baseline if baseline
                 else 0.0)
        worse = delta > tolerance if down else delta < -tolerance
        better = delta < -tolerance if down else delta > tolerance
        verdict = ("regression" if worse
                   else "improvement" if better else "ok")
        verdicts.append({**base, "verdict": verdict,
                         "baseline": round(baseline, 6),
                         "delta_pct": round(delta * 100, 2)})
    return verdicts


def check_ledger(path: str, tolerance: float = DEFAULT_TOLERANCE,
                 window: int = DEFAULT_WINDOW) -> list[dict]:
    """Read + check in one lock-free call — the HealthMonitor's perf
    detector uses this so no ledger lock nests inside the health lock."""
    return check_records(read_ledger(path), tolerance, window)


def regressions(verdicts: list[dict]) -> list[dict]:
    return [v for v in verdicts if v["verdict"] == "regression"]


# -- the writer --------------------------------------------------------------

class PerfLedger:
    """Append-only writer + snapshot publisher for one ledger file.

    Thread-safe: concurrent appenders (the CLI, a run's close path) are
    serialized.  Appends are line-granular (single ``write`` of
    complete lines, flushed) so a crash tears at most the final line,
    which :func:`read_ledger` skips.
    """

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()

    def records(self) -> list[dict]:
        with self._lock:
            return read_ledger(self.path)

    def append(self, records: list[dict], dedup: bool = True) -> list[dict]:
        """Append normalized records; -> those actually written.

        ``dedup`` skips records whose fingerprint is already in the log,
        making artifact ingestion idempotent across re-runs.
        """
        if not records:
            return []
        with self._lock:
            if dedup:
                seen = {r.get("fp") for r in read_ledger(self.path)}
                records = [r for r in records if r.get("fp") not in seen]
            if not records:
                return []
            parent = os.path.dirname(self.path)
            if parent:
                os.makedirs(parent, exist_ok=True)
            payload = "".join(json.dumps(r) + "\n" for r in records)
            # heal a crash-torn tail: without the newline the first new
            # record would concatenate onto the torn line and both lines
            # would be unreadable forever
            try:
                with open(self.path, "rb") as f:
                    f.seek(-1, os.SEEK_END)
                    if f.read(1) != b"\n":
                        payload = "\n" + payload
            except OSError:  # lint: swallow-ok — no file yet / empty: nothing to heal
                pass
            # append-only journal: the log IS the artifact, rewriting it
            # via tmp+replace would lose concurrent writers' lines — the
            # torn-tail-skipping reader is the crash contract instead
            with open(self.path, "a") as f:  # lint: atomic-publish-ok — append-only JSONL journal; readers skip a torn tail
                f.write(payload)
                f.flush()
                os.fsync(f.fileno())
        return records

    def ingest_artifact(self, path: str) -> list[dict]:
        """Classify + append one artifact file; -> records written."""
        try:
            with open(path) as f:
                payload = json.load(f)
        except (OSError, ValueError):
            return []
        return self.append(classify_artifact(path, payload))

    def check(self, tolerance: float = DEFAULT_TOLERANCE,
              window: int = DEFAULT_WINDOW) -> list[dict]:
        return check_records(self.records(), tolerance, window)

    def snapshot(self, path: str | None = None,
                 tolerance: float = DEFAULT_TOLERANCE) -> str:
        """Atomically publish the per-metric summary JSON (tmp +
        ``os.replace`` — a reader never sees a torn file)."""
        records = self.records()
        verdicts = check_records(records, tolerance)
        path = path or os.path.join(
            os.path.dirname(self.path) or ".", SNAPSHOT_FILENAME)
        payload = {
            "updated": time.time(),  # lint: wall-ok — cross-process stamp
            "ledger": os.path.basename(self.path),
            "n_records": len(records),
            "verdicts": verdicts,
        }
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1)
        os.replace(tmp, path)
        return path

    def backfill(self, root: str) -> list[dict]:
        """One-shot ingest of every known artifact under ``root`` (the
        repo dir), in trajectory order.  Idempotent via fingerprints."""
        written: list[dict] = []
        for pattern in BACKFILL_PATTERNS:
            for path in sorted(glob.glob(os.path.join(root, pattern))):
                written.extend(self.ingest_artifact(path))
        return written
