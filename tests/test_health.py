"""ISSUE 13: live run-health watchdog, crash flight recorder, tmhealth.

Detector units run the streaming :class:`HealthMonitor` on an injected
clock (no sleeps, fully deterministic); the integration half drives the
ticker thread, the supervisor health-kill, and the fleet ``fleet.hang``
audit + ledger failure-cause path with millisecond ``python -c`` fakes.
The real-launcher hang e2e (``prefetch:stall`` fault -> hung verdict ->
supervised restart) is marked slow; the in-process crash test asserts a
crashed run leaves a parseable ``blackbox.json``.
"""

import json
import os
import sys
import threading
import time

import pytest

from theanompi_tpu.telemetry import (
    EventSink,
    FlightRecorder,
    HealthConfig,
    HealthMonitor,
    Telemetry,
    hung_verdict,
    read_blackbox,
    read_events,
    read_health,
    replay_events,
    sink_files,
    tail_events,
)
from theanompi_tpu.telemetry import cli as health_cli
from theanompi_tpu.telemetry.aggregate import summarize_events
from theanompi_tpu.telemetry.chrome_trace import to_trace_events
from theanompi_tpu.telemetry.flight_recorder import blackbox_path

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mon(tmp_path, **cfg):
    """Monitor on a frozen injected clock: every observe/tick passes an
    explicit ``now``."""
    return HealthMonitor(str(tmp_path), HealthConfig(**cfg),
                         clock=lambda: 0.0)


def _step(mon, step, now, dur=0.01, rank=0, **tags):
    mon.observe({"ts": now, "kind": "span", "name": "train.step",
                 "dur": dur, "rank": rank, "tid": 1, "step": step, **tags},
                now=now)


def _by_detector(verdicts):
    return {v["detector"]: v for v in verdicts}


# -- hang (arrival clock) -----------------------------------------------------

def test_hang_arms_only_after_warmup_steps(tmp_path):
    mon = _mon(tmp_path, hang_deadline_s=10.0, hang_warmup_steps=3)
    _step(mon, 0, 1.0)
    _step(mon, 1, 2.0)
    # 2 steps < warmup: a long silence is still "compiling", not hung
    assert mon.tick(now=100.0) == []
    assert "hang" not in _by_detector(mon.verdicts())
    _step(mon, 2, 101.0)  # third step arms the detector
    changed = mon.tick(now=112.0)  # 11s > 10s deadline
    assert [v.detector for v in changed] == ["hang"]
    hang = _by_detector(mon.verdicts())["hang"]
    assert hang["severity"] == "critical"
    assert hang["fields"]["deadline_s"] == 10.0
    assert mon.worst_severity() == "critical"
    # unchanged severity is not re-reported on the next tick
    assert mon.tick(now=113.0) == []


def test_hang_suspended_in_boundary_and_disarmed_at_session_end(tmp_path):
    mon = _mon(tmp_path, hang_deadline_s=5.0, hang_warmup_steps=1)
    _step(mon, 0, 1.0)
    mon.observe({"kind": "instant", "name": "train.boundary",
                 "phase": "begin", "rank": 0}, now=2.0)
    # inside a boundary (validate/checkpoint) silence is legitimate
    assert mon.tick(now=60.0) == []
    mon.observe({"kind": "instant", "name": "train.boundary",
                 "phase": "end", "rank": 0}, now=60.0)
    assert mon.tick(now=61.0) == []          # clock restarted at the end
    changed = mon.tick(now=70.0)             # 10s > 5s: now it is a hang
    assert [v.detector for v in changed] == ["hang"]
    # a new step clears it...
    _step(mon, 1, 70.5)
    ok = [v for v in mon.tick(now=71.0) if v.detector == "hang"]
    assert ok and ok[0].severity == "ok"
    # ...and session_end disarms for good
    mon.observe({"kind": "meta", "name": "session_end", "rank": 0}, now=72.0)
    assert mon.tick(now=500.0) == []
    assert _by_detector(mon.verdicts())["hang"]["severity"] == "ok"


# -- straggler ----------------------------------------------------------------

def test_straggler_flags_slow_rank_against_fleet_mean(tmp_path):
    mon = _mon(tmp_path, straggler_ratio=1.5, straggler_min_steps=4)
    for s in range(4):
        _step(mon, s, float(s), dur=0.010, rank=0)
        _step(mon, s, float(s) + 0.5, dur=0.030, rank=1)
    v = _by_detector(mon.verdicts())["straggler"]
    # rank 1 at 0.030 vs fleet mean 0.020 -> ratio 1.5 >= threshold
    assert v["severity"] == "warn"
    assert v["fields"]["rank"] == 1
    assert v["fields"]["step_skew_ms"]["steps_compared"] == 4
    assert v["fields"]["step_skew_ms"]["max"] == pytest.approx(20.0)


def test_straggler_needs_common_steps_and_two_ranks(tmp_path):
    mon = _mon(tmp_path, straggler_min_steps=4)
    for s in range(8):
        _step(mon, s, float(s), dur=0.010, rank=0)
    assert "straggler" not in _by_detector(mon.verdicts())
    # rank 1 reports DIFFERENT steps: no common window, no verdict
    for s in range(100, 103):
        _step(mon, s, float(s), dur=0.050, rank=1)
    assert "straggler" not in _by_detector(mon.verdicts())


# -- loss ---------------------------------------------------------------------

def test_loss_nan_is_immediately_critical(tmp_path):
    mon = _mon(tmp_path)
    _step(mon, 0, 1.0, loss=float("nan"))
    v = _by_detector(mon.verdicts())["loss"]
    assert v["severity"] == "critical" and "non-finite" in v["reason"]
    assert v["step"] == 0


def test_loss_spike_warns_only_after_warmup(tmp_path):
    mon = _mon(tmp_path, loss_warmup=8, loss_z=6.0)
    for s in range(7):
        _step(mon, s, float(s), loss=1.0 + 0.01 * s)
    assert _by_detector(mon.verdicts()).get(
        "loss", {"severity": "ok"}).get("severity") != "warn"
    _step(mon, 7, 7.0, loss=1.07)
    _step(mon, 8, 8.0, loss=1e6)  # past warmup: a huge spike is a warn
    v = _by_detector(mon.verdicts())["loss"]
    assert v["severity"] == "warn"
    assert v["fields"]["z"] > 6.0
    assert v["step"] == 8
    _step(mon, 9, 9.0, loss=1.0)  # hmm -- back in band relative to EWMA
    assert _by_detector(mon.verdicts())["loss"]["severity"] == "ok"


# -- throughput ---------------------------------------------------------------

def test_throughput_regression_warns_on_recent_median(tmp_path):
    mon = _mon(tmp_path, throughput_min_steps=16, throughput_recent=8,
               throughput_factor=2.0)
    for s in range(16):
        _step(mon, s, float(s), dur=0.010)
    v = _by_detector(mon.verdicts())["throughput"]
    assert v["severity"] == "ok"
    for s in range(16, 24):
        _step(mon, s, float(s), dur=0.050)  # 5x the baseline
    v = _by_detector(mon.verdicts())["throughput"]
    assert v["severity"] == "warn"
    assert v["fields"]["recent_ms"] == pytest.approx(50.0)
    assert v["fields"]["baseline_ms"] == pytest.approx(10.0)


# -- checkpoint cadence -------------------------------------------------------

def test_checkpoint_stall_warns_then_clears(tmp_path):
    mon = _mon(tmp_path, checkpoint_deadline_s=10.0, hang_warmup_steps=99)
    # no checkpoint ever seen: detector stays silent no matter how long
    _step(mon, 0, 1.0)
    assert mon.tick(now=1000.0) == []
    mon.observe({"kind": "span", "name": "checkpoint.write", "dur": 0.1,
                 "rank": 0}, now=1001.0)
    assert _by_detector(mon.verdicts())["checkpoint"]["severity"] == "ok"
    # steps advance past the deadline with no new checkpoint
    _step(mon, 1, 1002.0)
    changed = mon.tick(now=1015.0)
    assert [v.detector for v in changed] == ["checkpoint"]
    assert changed[0].severity == "warn"
    mon.observe({"kind": "span", "name": "checkpoint.write", "dur": 0.1,
                 "rank": 0}, now=1016.0)
    cleared = [v for v in mon.tick(now=1017.0) if v.detector == "checkpoint"]
    assert cleared and cleared[0].severity == "ok"


# -- serving SLO --------------------------------------------------------------

def test_slo_breach_from_metrics_histograms(tmp_path):
    mon = _mon(tmp_path, slo_ttft_p99_ms=50.0)
    mon.observe({"kind": "metrics", "name": "metrics", "rank": 0,
                 "histograms": {"serve.ttft_ms": {"p50": 10.0, "p99": 80.0}}},
                now=1.0)
    v = _by_detector(mon.verdicts())["slo"]
    assert v["severity"] == "warn"
    assert v["fields"] == {"p99_ms": 80.0, "slo_ms": 50.0}
    mon.observe({"kind": "metrics", "name": "metrics", "rank": 0,
                 "histograms": {"serve.ttft_ms": {"p50": 5.0, "p99": 20.0}}},
                now=2.0)
    assert _by_detector(mon.verdicts())["slo"]["severity"] == "ok"


def test_slo_detector_off_without_configured_target(tmp_path):
    mon = _mon(tmp_path)  # slo_ttft_p99_ms defaults to None
    mon.observe({"kind": "metrics", "name": "metrics", "rank": 0,
                 "histograms": {"serve.ttft_ms": {"p99": 1e9}}}, now=1.0)
    assert "slo" not in _by_detector(mon.verdicts())


# -- HEALTH.json + shared predicates ------------------------------------------

def test_health_json_roundtrip_and_hung_predicate(tmp_path):
    mon = _mon(tmp_path, hang_deadline_s=1.0, hang_warmup_steps=1)
    _step(mon, 0, 1.0)
    mon.tick(now=10.0)
    path = mon.write()
    assert os.path.basename(path) == "HEALTH.json"
    health = read_health(str(tmp_path))
    assert health["pid"] == os.getpid() and health["steps"] == 1
    assert abs(health["updated"] - time.time()) < 60
    hung = hung_verdict(health)
    assert hung is not None and hung["severity"] == "critical"
    assert hung_verdict(None) is None
    assert hung_verdict({"verdicts": [{"detector": "loss",
                                       "severity": "critical"}]}) is None
    assert read_health(str(tmp_path / "nope")) is None


def test_replay_events_runs_detectors_offline(tmp_path):
    events = [{"kind": "span", "name": "train.step", "dur": 0.01,
               "rank": 0, "step": s, "loss": float("nan") if s == 3 else 1.0}
              for s in range(4)]
    mon = replay_events(events, directory=str(tmp_path))
    verdicts = _by_detector(mon.verdicts())
    assert verdicts["loss"]["severity"] == "critical"
    # the arrival-clock hang detector cannot fire in a replay
    assert verdicts.get("hang", {}).get("severity", "ok") == "ok"


# -- flight recorder ----------------------------------------------------------

def test_flight_recorder_ring_and_blackbox_payload(tmp_path):
    with pytest.raises(ValueError):
        FlightRecorder(str(tmp_path), capacity=0)
    fr = FlightRecorder(str(tmp_path), capacity=4, rank=0)
    fr.set_fingerprint({"mesh": "2x2", "model": "WideResNet"})
    for i in range(10):
        fr.record({"kind": "instant", "name": "x", "i": i})
    path = fr.dump("crash", error="ValueError: boom",
                   health={"verdicts": []})
    assert path == blackbox_path(str(tmp_path), 0)
    bb = read_blackbox(str(tmp_path))
    assert bb["reason"] == "crash" and bb["error"] == "ValueError: boom"
    assert bb["fingerprint"]["model"] == "WideResNet"
    assert bb["n_events"] == 4  # ring bounded: only the newest survive
    assert [e["i"] for e in bb["events"]] == [6, 7, 8, 9]
    assert bb["pid"] == os.getpid() and bb["rank"] == 0
    # last dump wins (the outermost handler has the best error)
    fr.dump("hang")
    assert read_blackbox(str(tmp_path))["reason"] == "hang"
    assert "error" not in read_blackbox(str(tmp_path))


def test_flight_recorder_per_rank_paths(tmp_path):
    assert blackbox_path(str(tmp_path), 0).endswith("blackbox.json")
    assert blackbox_path(str(tmp_path), 3).endswith("blackbox-rank00003.json")
    fr = FlightRecorder(str(tmp_path), capacity=2, rank=3)
    fr.record({"kind": "instant", "name": "x"})
    fr.dump("sigterm")
    assert read_blackbox(str(tmp_path), rank=3)["reason"] == "sigterm"
    assert read_blackbox(str(tmp_path)) is None  # rank 0 never dumped


# -- Telemetry integration ----------------------------------------------------

def test_telemetry_ticker_publishes_hang_and_mirrors_transition(tmp_path):
    d = str(tmp_path)
    tel = Telemetry(d, rank=0, health={"tick_s": 0.05, "hang_deadline_s": 0.3,
                                       "hang_warmup_steps": 1},
                    flight_recorder=16)
    assert tel.health is not None and tel.flight is not None
    tel.emit_span("train.step", 0.0, 0.01, step=0, loss=1.0)
    deadline = time.time() + 20.0
    hung = None
    while time.time() < deadline:
        hung = hung_verdict(read_health(d))
        if hung is not None:
            break
        time.sleep(0.02)
    assert hung is not None, "ticker never published the hang verdict"
    assert "no events" in hung["reason"]
    tel.close()
    events = [e for p in sink_files(d) for e in read_events(p)]
    mirrored = [e for e in events if e.get("name") == "health.verdict"]
    assert any(e.get("detector") == "hang" and e.get("severity") == "critical"
               for e in mirrored)
    # close() emitted session_end -> the final published state is disarmed
    assert hung_verdict(read_health(d)) is None
    assert any(e.get("name") == "session_end" for e in events)


def test_disabled_telemetry_makes_zero_health_calls(tmp_path, monkeypatch):
    """A Telemetry without the opt-ins must never touch the monitor or
    the flight recorder (the ISSUE 13 off-means-off criterion)."""
    import theanompi_tpu.telemetry.flight_recorder as fr_mod
    import theanompi_tpu.telemetry.health as health_mod

    def bomb(*a, **k):
        raise AssertionError("health/flight call on a disabled run")

    for obj, meth in [(health_mod.HealthMonitor, "__init__"),
                      (health_mod.HealthMonitor, "observe"),
                      (health_mod.HealthMonitor, "tick"),
                      (health_mod.HealthMonitor, "write"),
                      (fr_mod.FlightRecorder, "__init__"),
                      (fr_mod.FlightRecorder, "record"),
                      (fr_mod.FlightRecorder, "dump")]:
        monkeypatch.setattr(obj, meth, bomb)
    tel = Telemetry(str(tmp_path))  # defaults: health off, recorder off
    assert tel.health is None and tel.flight is None
    with tel.span("train.step", step=0, loss=1.0):
        pass
    tel.instant("train.boundary", phase="begin")
    tel.count("bytes", 10, emit=True)
    tel.flush_metrics(step=0)
    tel.close()
    assert read_health(str(tmp_path)) is None
    assert read_blackbox(str(tmp_path)) is None


def test_rule_config_wires_health_and_blackbox_keys(tmp_path):
    from theanompi_tpu import BSP

    tel = BSP(config={"telemetry_dir": str(tmp_path / "on"),
                      "verbose": False}).make_telemetry()
    assert tel.health is not None           # default-on when telemetry is on
    assert tel.flight is not None and tel.flight.capacity == 256
    tel.close()
    tel = BSP(config={"telemetry_dir": str(tmp_path / "off"),
                      "verbose": False, "telemetry_health": False,
                      "telemetry_blackbox": 0}).make_telemetry()
    assert tel.health is None and tel.flight is None
    tel.close()
    tel = BSP(config={"telemetry_dir": str(tmp_path / "cfg"),
                      "verbose": False,
                      "telemetry_health": {"hang_deadline_s": 5.0},
                      }).make_telemetry()
    assert tel.health.config.hang_deadline_s == 5.0
    tel.close()


# -- tail_events (satellite: live tailing) ------------------------------------

def test_tail_events_never_consumes_a_partial_line(tmp_path):
    path = str(tmp_path / "events-rank00000.jsonl")
    with open(path, "wb") as f:
        f.write(b'{"a": 1}\n{"b"')
    events, off = tail_events(path)
    assert events == [{"a": 1}] and off == 9
    with open(path, "ab") as f:
        f.write(b': 2}\n')
    events, off = tail_events(path, off)
    assert events == [{"b": 2}]
    assert tail_events(path, off) == ([], off)
    assert tail_events(str(tmp_path / "missing.jsonl"), 7) == ([], 7)


def test_tail_events_races_a_live_writer_without_loss(tmp_path):
    """A tailer polling while the sink thread writes sees every event
    exactly once, in order — the contract tmhealth --follow leans on."""
    sink = EventSink(str(tmp_path), rank=0)
    n = 400

    def writer():
        for i in range(n):
            sink.emit({"kind": "instant", "name": "tick", "seq": i})
            if i % 50 == 0:
                time.sleep(0.002)
        sink.close()

    t = threading.Thread(target=writer)
    t.start()
    seen, offset = [], 0
    deadline = time.time() + 30.0
    while time.time() < deadline:
        events, offset = tail_events(sink.path, offset)
        seen.extend(events)
        if not t.is_alive() and not events and len(seen) >= n:
            break
        time.sleep(0.001)
    t.join()
    assert [e["seq"] for e in seen] == list(range(n))


# -- chrome trace two-rank alignment (satellite) ------------------------------

def test_chrome_trace_aligns_ranks_with_different_clock_epochs():
    """Per-rank ``ts`` values are per-process perf_counter epochs; the
    exporter must normalize each rank to its own start so two ranks render
    side by side at t=0 with durations preserved exactly."""
    events = []
    for rank, epoch in ((0, 100.0), (1, 5000.0)):
        for s in range(3):
            events.append({"kind": "span", "name": "train.step",
                           "ts": epoch + 0.1 * s, "dur": 0.02,
                           "rank": rank, "tid": 1, "step": s})
    trace = to_trace_events(events)
    spans = [t for t in trace if t.get("ph") == "X"]
    by_pid = {}
    for t in spans:
        by_pid.setdefault(t["pid"], []).append(t)
    assert set(by_pid) == {0, 1}
    for pid, ts in by_pid.items():
        starts = sorted(t["ts"] for t in ts)
        assert starts[0] == pytest.approx(0.0, abs=1e-6)
        # relative spacing survives (0.1s steps -> 1e5us apart)
        assert starts[1] == pytest.approx(1e5, rel=1e-6)
        assert all(t["dur"] == pytest.approx(2e4, rel=1e-6) for t in ts)


# -- aggregate partial fleets (satellite) -------------------------------------

def _span(rank, step, dur, ts=None):
    return {"kind": "span", "name": "train.step", "rank": rank, "tid": 1,
            "ts": 1.0 * step if ts is None else ts, "dur": dur, "step": step}


def test_summarize_partial_fleet_missing_ranks(tmp_path):
    # ranks 0 and 2 reported; rank 1's sink never made it back
    events = ([_span(0, s, 0.010) for s in range(4)]
              + [_span(2, s, 0.020) for s in range(2)])
    summary = summarize_events(events)
    assert summary["n_ranks"] == 2
    assert set(summary["per_rank"]) == {"0", "2"}
    assert summary["per_rank"]["0"]["steps"] == 4
    assert summary["per_rank"]["2"]["steps"] == 2
    # skew only over the steps BOTH ranks reported
    assert summary["step_skew_ms"]["steps_compared"] == 2
    assert summary["straggler"]["rank"] == 2


def test_summarize_rank_with_zero_steps_is_not_divided_by(tmp_path):
    events = [_span(0, s, 0.010) for s in range(3)]
    events.append({"kind": "instant", "name": "resilience.watchdog_stall",
                   "rank": 1, "ts": 0.5})
    summary = summarize_events(events)
    assert summary["n_ranks"] == 2
    assert summary["per_rank"]["1"]["steps"] == 0
    assert "step_ms" not in summary["per_rank"]["1"]
    # a zero-step rank suppresses the cross-rank skew, not the summary
    assert "step_skew_ms" not in summary
    assert summary["straggler"]["rank"] == 0  # judged over stepped ranks
    # no metrics event ever flushed -> no counters key anywhere
    assert "counters" not in summary["per_rank"]["0"]


def test_summarize_no_events_at_all():
    summary = summarize_events([])
    assert summary["n_ranks"] == 0 and summary["per_rank"] == {}


# -- tmhealth CLI -------------------------------------------------------------

def test_tmhealth_cli_exit_codes_and_json(tmp_path, capsys):
    assert health_cli.main([str(tmp_path / "missing")]) == 2
    capsys.readouterr()

    live = tmp_path / "live"
    live.mkdir()
    mon = HealthMonitor(str(live), HealthConfig(), clock=lambda: 0.0)
    for s in range(10):  # past loss warmup: an "ok" loss verdict exists
        _step(mon, s, float(s), loss=1.0)
    mon.write()
    assert health_cli.main([str(live)]) == 0
    out = capsys.readouterr().out
    assert "HEALTH.json" in out and "loss" in out

    mon2 = _mon(tmp_path / "live", hang_deadline_s=1.0, hang_warmup_steps=1)
    _step(mon2, 0, 1.0)
    mon2.tick(now=10.0)
    mon2.write()
    assert health_cli.main([str(live), "--json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    rep = doc["reports"][0]
    assert rep["source"] == "HEALTH.json"
    assert hung_verdict(rep) is not None


def test_tmhealth_replays_events_and_flags_stale_runs(tmp_path, capsys):
    d = tmp_path / "old"
    d.mkdir()
    sink = EventSink(str(d), rank=0)
    for s in range(3):
        sink.emit(_span(0, s, 0.01))
    sink.close()  # no session_end meta, no HEALTH.json: a pre-13 run
    stale = time.time() - 120.0
    for p in sink_files(str(d)):
        os.utime(p, (stale, stale))
    assert health_cli.main([str(d), "--stale-hang-s", "60"]) == 1
    out = capsys.readouterr().out
    assert "[replay" in out and "hang" in out
    # a generous staleness budget keeps the same directory healthy
    assert health_cli.main([str(d), "--stale-hang-s", "99999"]) == 0


def test_tmhealth_fleet_mode_scans_per_job_dirs(tmp_path, capsys):
    fleet = tmp_path / "fleet"
    assert health_cli.main([str(fleet / "nope"), "--fleet"]) == 2
    capsys.readouterr()
    for jid in ("a", "b"):
        jdir = fleet / "jobs" / jid / "telemetry"
        jdir.mkdir(parents=True)
        mon = HealthMonitor(str(jdir), HealthConfig(), clock=lambda: 0.0)
        _step(mon, 0, 1.0, loss=1.0)
        mon.write()
    assert health_cli.main([str(fleet), "--fleet", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert len(doc["reports"]) == 2
    assert all(r["source"] == "HEALTH.json" for r in doc["reports"])


# -- supervisor health-kill ---------------------------------------------------

def _hang_child(tmp_path, tdir):
    """A child that fakes a hung trainer on its first attempt: publishes a
    critical HEALTH.json + a blackbox, then sleeps; the resumed attempt
    exits clean.  (The real publication path is covered by the ticker and
    launcher tests — here the timing must be deterministic.)"""
    body = """
import json, os, sys, time
tdir = TDIR
marker = os.path.join(STATE, "hung_once")
if not os.path.exists(marker):
    open(marker, "w").close()
    os.makedirs(tdir, exist_ok=True)
    bb = {"wall_time": time.time(), "reason": "hang", "pid": os.getpid(),
          "rank": 0, "fingerprint": {"mesh": "fake"}, "n_events": 1,
          "events": [{"kind": "instant", "name": "x", "rank": 0, "ts": 0.0}]}
    json.dump(bb, open(os.path.join(tdir, "blackbox.json"), "w"))
    health = {"updated": time.time(), "pid": os.getpid(), "rank": 0,
              "steps": 7, "verdicts": [
                  {"detector": "hang", "severity": "critical",
                   "reason": "no events for 9.0s (deadline 3s)"}]}
    json.dump(health, open(os.path.join(tdir, "HEALTH.json"), "w"))
    time.sleep(120)
    sys.exit(1)
sys.exit(0)
"""
    body = body.replace("STATE", repr(str(tmp_path))).replace(
        "TDIR", repr(tdir))
    return [sys.executable, "-c", body]


def test_supervisor_kills_child_on_fresh_hung_verdict(tmp_path):
    from theanompi_tpu.resilience.supervisor import Supervisor

    tdir = str(tmp_path / "telemetry")
    sup = Supervisor(_hang_child(tmp_path, tdir), max_restarts=2,
                     backoff_base=0.01, jitter=0.0, poll_s=0.05,
                     telemetry_dir=tdir,
                     resilience_path=str(tmp_path / "resilience.json"),
                     resume_args=())
    assert sup.run() == 0
    rep = json.load(open(tmp_path / "resilience.json"))
    causes = [a["cause"] for a in rep["attempts"]]
    assert causes == ["hang", "clean"]
    first = rep["attempts"][0]
    assert first["exit_code"] < 0  # killed by signal, not a clean exit
    # the blackbox + health verdicts were harvested into the attempt
    assert first["blackbox"]["reason"] == "hang"
    assert first["blackbox"]["fingerprint"] == {"mesh": "fake"}
    assert "events" not in first["blackbox"]  # summary only, ring dropped
    assert any(v["detector"] == "hang" and v["severity"] == "critical"
               for v in first["health"])


def test_supervisor_ignores_stale_health_from_a_previous_run(tmp_path):
    from theanompi_tpu.resilience.supervisor import Supervisor

    tdir = tmp_path / "telemetry"
    tdir.mkdir()
    health = {"updated": time.time(), "pid": 1, "rank": 0, "steps": 3,
              "verdicts": [{"detector": "hang", "severity": "critical",
                            "reason": "stale"}]}
    json.dump(health, open(tdir / "HEALTH.json", "w"))
    time.sleep(0.05)  # the file's mtime predates the supervisor's start
    sup = Supervisor([sys.executable, "-c", "import time; time.sleep(2.2)"],
                     max_restarts=0, poll_s=0.05, telemetry_dir=str(tdir),
                     resilience_path=str(tmp_path / "resilience.json"))
    assert sup.run() == 0
    rep = json.load(open(tmp_path / "resilience.json"))
    assert [a["cause"] for a in rep["attempts"]] == ["clean"]


# -- fleet: hang audit + failure cause ----------------------------------------

def test_fleet_records_hang_cause_in_ledger_and_events(tmp_path):
    from theanompi_tpu.fleet import (
        DeviceLedger,
        FleetScheduler,
        JobSpec,
        job_dir,
        read_fleet_events,
        read_record,
    )
    from theanompi_tpu.resilience.codes import EXIT_CRASH

    d = str(tmp_path / "fleet")
    jdir = job_dir(d, "wedged")
    tdir = os.path.join(jdir, "telemetry")
    body = """
import json, os, time
tdir = TDIR
os.makedirs(tdir, exist_ok=True)
bb = {"wall_time": time.time(), "reason": "hang", "pid": os.getpid(),
      "rank": 0, "fingerprint": {}, "n_events": 0, "events": []}
json.dump(bb, open(os.path.join(tdir, "blackbox.json"), "w"))
health = {"updated": time.time(), "pid": os.getpid(), "rank": 0, "steps": 5,
          "verdicts": [{"detector": "hang", "severity": "critical",
                        "reason": "no events for 9.0s"}]}
json.dump(health, open(os.path.join(tdir, "HEALTH.json"), "w"))
time.sleep(120)
""".replace("TDIR", repr(tdir))
    sched = FleetScheduler(d, 4, poll_s=0.02, telemetry=False)
    sched.submit(JobSpec(job_id="wedged", max_restarts=1,
                         argv=[sys.executable, "-c", body]))
    box = {}
    t = threading.Thread(target=lambda: box.setdefault("rc", sched.run()))
    t.start()
    t.join(60)
    assert not t.is_alive(), "fleet scheduler hung"
    assert box["rc"] == EXIT_CRASH

    rec = read_record(d, "wedged")
    assert rec.status == "failed"
    assert rec.failure_cause["cause"] == "hang"
    assert rec.failure_cause["blackbox"]["reason"] == "hang"
    assert any(v["detector"] == "hang"
               for v in rec.failure_cause["health"])
    # the ledger remembers WHY long after the record is gone
    led = DeviceLedger(d)
    assert led.last_failure("wedged")["cause"] == "hang"
    events = read_fleet_events(d)
    hangs = [e for e in events if e["event"] == "fleet.hang"]
    assert len(hangs) == 1 and hangs[0]["job"] == "wedged"
    fails = [e for e in events if e["event"] == "fleet.fail"]
    assert fails and fails[0]["cause"] == "hang" and fails[0]["blackbox"]


# -- crash blackbox (in-process, real trainer) --------------------------------

@pytest.mark.faultinject
def test_crashed_run_leaves_parseable_blackbox(tmp_path):
    from theanompi_tpu import BSP
    from theanompi_tpu.resilience import FaultInjected

    d = str(tmp_path / "telemetry")
    # 2 steps/epoch at global batch 16: step:raise@1 fires on the second
    cfg = {"depth": 10, "widen": 1, "batch_size": 4, "image_size": 8,
           "n_train": 32, "n_val": 8, "n_epochs": 1, "precision": "fp32"}
    rule = BSP(config={"verbose": False, "telemetry_dir": d,
                       "fault_plan": "step:raise@1"})
    rule.init(4, "theanompi_tpu.models.wide_resnet", "WideResNet", cfg)
    with pytest.raises(FaultInjected):
        rule.wait()
    bb = read_blackbox(d)
    assert bb is not None, "crash left no blackbox.json"
    assert bb["reason"] == "crash"
    assert "FaultInjected" in bb["error"]
    assert bb["fingerprint"], "fingerprint missing from blackbox"
    assert bb["n_events"] == len(bb["events"]) > 0
    assert all("name" in e for e in bb["events"])
    # the health monitor published alongside (hang never fired: no
    # warmup-steps-then-silence on a fast crash)
    health = read_health(d)
    assert health is not None and hung_verdict(health) is None


# -- launcher hang e2e (slow) -------------------------------------------------

TINY_ARGS = ["--set", "depth=10", "--set", "widen=1", "--set",
             "batch_size=4", "--set", "image_size=8", "--set", "n_train=32",
             "--set", "n_val=16", "--set", "n_epochs=2", "--set",
             "precision=fp32"]


def _child_env(**extra):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
                "JAX_THREEFRY_PARTITIONABLE": "true",
                "PYTHONPATH": REPO})
    env.pop("THEANOMPI_FAULT_PLAN", None)
    env.update(extra)
    return env


@pytest.mark.slow
@pytest.mark.faultinject
def test_launcher_hang_is_detected_killed_and_restarted(tmp_path,
                                                        subproc_compile_cache):
    """THE acceptance e2e: a prefetch stall wedges the real trainer after
    its first step; the in-process watchdog publishes the hung verdict,
    the supervisor health-kills the child citing it, and the resumed
    attempt finishes the job clean."""
    import subprocess

    tdir = str(tmp_path / "telemetry")
    cmd = [sys.executable, "-m", "theanompi_tpu.launcher",
           "--rule", "BSP", "--devices", "4",
           "--modelfile", "theanompi_tpu.models.wide_resnet",
           "--modelclass", "WideResNet", *TINY_ARGS, "--quiet",
           "--telemetry-dir", tdir,
           "--rule-set",
           "telemetry_health={'hang_deadline_s': 3.0, "
           "'hang_warmup_steps': 1, 'tick_s': 0.25}",
           "--checkpoint-dir", str(tmp_path / "ckpt"),
           "--supervise", "--max-restarts", "2", "--backoff-base", "0.1"]
    env = _child_env(THEANOMPI_FAULT_PLAN="prefetch:stall@1@1")
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=900, cwd=str(tmp_path))
    rep = json.load(open(tmp_path / "ckpt" / "resilience.json"))
    causes = [a["cause"] for a in rep["attempts"]]
    assert causes == ["hang", "clean"], (causes, proc.stdout[-2000:],
                                         proc.stderr[-2000:])
    assert proc.returncode == 0
    first = rep["attempts"][0]
    assert any(v["detector"] == "hang" and v["severity"] == "critical"
               for v in first["health"])
    assert first["blackbox"]["reason"] == "hang"


# -- perf ledger detector (ISSUE 16) ------------------------------------------

def _perf_ledger(path, values):
    from theanompi_tpu.telemetry.ledger import PerfLedger, make_record

    led = PerfLedger(str(path))
    led.append([make_record("seed", "bench", "bench.imgs_per_sec", v,
                            "images/sec", run_id=f"r{i}")
                for i, v in enumerate(values)])
    return led


def test_perf_detector_warns_on_ledger_regression(tmp_path):
    ledger = tmp_path / "TMPROF_LEDGER.jsonl"
    _perf_ledger(ledger, [100.0, 101.0, 99.0, 100.0, 70.0])
    mon = _mon(tmp_path, perf_ledger_path=str(ledger),
               hang_warmup_steps=99)
    mon.tick(now=1.0)
    v = _by_detector(mon.verdicts())["perf"]
    assert v["severity"] == "warn"
    assert "bench.imgs_per_sec" in v["reason"]
    assert "-30" in v["reason"]  # the worst delta is stated


def test_perf_detector_clears_on_recovery(tmp_path):
    ledger = tmp_path / "TMPROF_LEDGER.jsonl"
    led = _perf_ledger(ledger, [100.0, 101.0, 99.0, 100.0, 70.0])
    mon = _mon(tmp_path, perf_ledger_path=str(ledger),
               hang_warmup_steps=99)
    mon.tick(now=1.0)
    assert _by_detector(mon.verdicts())["perf"]["severity"] == "warn"
    # a recovered point lands; force a distinct mtime so the gate reopens
    from theanompi_tpu.telemetry.ledger import make_record

    led.append([make_record("seed", "bench", "bench.imgs_per_sec", 100.0,
                            "images/sec", run_id="r5")])
    os.utime(str(ledger), (1.0, 2.0))
    mon.tick(now=2.0)
    assert _by_detector(mon.verdicts())["perf"]["severity"] == "ok"


def test_perf_detector_mtime_gated(tmp_path, monkeypatch):
    """An armed detector costs one stat per tick — the ledger is only
    re-read when its mtime moves."""
    ledger = tmp_path / "TMPROF_LEDGER.jsonl"
    _perf_ledger(ledger, [100.0, 100.0])
    os.utime(str(ledger), (1.0, 1.0))
    mon = _mon(tmp_path, perf_ledger_path=str(ledger),
               hang_warmup_steps=99)
    mon.tick(now=1.0)
    calls = []
    import theanompi_tpu.telemetry.ledger as ledger_mod

    real = ledger_mod.check_ledger
    monkeypatch.setattr(ledger_mod, "check_ledger",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    mon.tick(now=2.0)
    mon.tick(now=3.0)
    assert calls == []  # unchanged mtime -> no re-read
    os.utime(str(ledger), (1.0, 9.0))
    mon.tick(now=4.0)
    assert calls == [1]


def test_perf_detector_off_without_ledger(tmp_path):
    # unconfigured (default): detector never appears
    mon = _mon(tmp_path, hang_warmup_steps=99)
    mon.tick(now=1.0)
    assert "perf" not in _by_detector(mon.verdicts())
    # configured but no ledger file yet: stays silent, does not raise
    mon = _mon(tmp_path, perf_ledger_path=str(tmp_path / "nope.jsonl"),
               hang_warmup_steps=99)
    mon.tick(now=1.0)
    assert "perf" not in _by_detector(mon.verdicts())
