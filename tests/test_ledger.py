"""ISSUE 16 durable perf-regression ledger.

Pure-python on synthetic records plus the repo's own committed
artifacts: classification of every known artifact shape, fingerprint
idempotence, the torn-tail crash contract, trailing-median regression
verdicts in both metric directions, value-less records kept out of
baselines, and the ``tmprof --ledger`` exit contract (0 clean / 1
regression / 2 usage).  The acceptance fixture seeds a throughput
collapse and must exit 1; the repo's real backfilled artifacts must
exit 0.
"""

import json
import os

import pytest

from theanompi_tpu.telemetry import PerfLedger, check_ledger, read_ledger
from theanompi_tpu.telemetry import prof
from theanompi_tpu.telemetry.ledger import (
    LEDGER_FILENAME,
    check_records,
    classify_artifact,
    lower_is_better,
    make_record,
    regressions,
    trajectories,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _seed(path, metric, values, unit="images/sec"):
    led = PerfLedger(str(path))
    for i, v in enumerate(values):
        led.append([make_record("seed", "bench", metric, v, unit,
                                run_id=f"r{i}")])
    return led


# -- records & fingerprints ---------------------------------------------------

def test_make_record_fingerprint_stable():
    a = make_record("s", "bench", "m", 1.5, "ms", run_id="r1")
    b = make_record("s", "bench", "m", 1.5, "ms", run_id="r1")
    c = make_record("s", "bench", "m", 1.6, "ms", run_id="r1")
    assert a["fp"] == b["fp"] != c["fp"]
    assert a["schema"] == 1 and a["value"] == 1.5


def test_lower_is_better_inference():
    assert lower_is_better("bench.step_ms", "ms")
    assert lower_is_better("serve.ttft_p99_ms", "ms")
    assert lower_is_better("attrib.train.step_ms", "ms")
    assert not lower_is_better("bench.imgs_per_sec", "images/sec")
    assert not lower_is_better("train.mfu", "mfu")
    assert not lower_is_better("converge.wrn.margin", "margin")


# -- artifact classification --------------------------------------------------

def test_classify_attrib():
    attrib = {"pid": 7, "per_rank": {"0": {
        "mode": "train", "wall_step": {"p50_ms": 12.5},
        "segments": {"compute": {"share": 0.8},
                     "host": {"share": 0.2}}}}}
    recs = classify_artifact("ATTRIB.json", attrib)
    by_metric = {r["metric"]: r for r in recs}
    assert by_metric["attrib.train.step_ms"]["value"] == 12.5
    assert by_metric["attrib.train.compute_share"]["value"] == 0.8
    assert by_metric["attrib.train.step_ms"]["run_id"] == "pid7"


def test_classify_serve_report():
    """SERVE.json: throughput, the nested latency percentiles and the
    ISSUE 17 prefix-cache accounting all enter the trajectory."""
    serve = {"metric": "serve_tokens_per_sec", "value": 812.5,
             "unit": "tokens/sec", "run_id": "r9",
             "ttft_ms": {"p50": 11.0, "p99": 30.5},
             "token_ms": {"p50": 2.0, "p99": 4.5},
             "decode_kernel": "kernel", "decode_step_ms": {"p50": 1.8,
                                                           "p99": 3.9},
             "prefix_cache": True, "prefix_hit_rate": 0.72,
             "prefill_tokens_saved": 4096}
    by_metric = {r["metric"]: r for r in
                 classify_artifact("SERVE.json", serve)}
    # ISSUE 18: decode-step wall is keyed by the served variant so the
    # kernel-on trajectory never checks against a fallback baseline
    assert by_metric["serve.decode.kernel.step_p99_ms"]["value"] == 3.9
    assert by_metric["serve.decode.kernel.step_p99_ms"]["unit"] == "ms"
    pre18 = {m for m in by_metric if "decode." in m}
    assert {r["metric"] for r in classify_artifact(
        "SERVE.json", {k: v for k, v in serve.items()
                       if not k.startswith("decode")})}.isdisjoint(pre18)
    assert by_metric["serve.tokens_per_sec"]["value"] == 812.5
    assert by_metric["serve.tokens_per_sec"]["kind"] == "serve"
    assert by_metric["serve.ttft_p99_ms"]["value"] == 30.5
    assert by_metric["serve.ttft_p99_ms"]["unit"] == "ms"
    assert by_metric["serve.token_p50_ms"]["value"] == 2.0
    assert by_metric["serve.prefix_hit_rate"]["value"] == 0.72
    assert by_metric["serve.prefill_tokens_saved"]["value"] == 4096
    assert all(r["run_id"] == "r9" for r in by_metric.values())
    # cache-off runs keep the prefix metrics OUT of the trajectory (their
    # zeros would poison the baseline median)
    off = {r["metric"] for r in classify_artifact(
        "SERVE.json", {**serve, "prefix_cache": False})}
    assert not any("prefix" in m for m in off)
    assert "serve.tokens_per_sec" in off
    # direction inference: hit rate and tokens saved improve upward
    assert not lower_is_better("serve.prefix_hit_rate", "rate")
    assert not lower_is_better("serve.prefill_tokens_saved", "tokens")


def test_classify_unknown_shape_yields_nothing():
    assert classify_artifact("WHAT.json", {"stuff": 1}) == []
    assert classify_artifact("X.json", ["not", "a", "dict"]) == []


# -- the writer & crash contract ----------------------------------------------

def test_append_dedup_idempotent(tmp_path):
    led = PerfLedger(str(tmp_path / LEDGER_FILENAME))
    recs = [make_record("s", "bench", "m", 1.0, "ms", run_id="r1")]
    assert len(led.append(recs)) == 1
    assert led.append(recs) == []  # same fingerprint -> skipped
    assert len(led.records()) == 1


def test_torn_tail_skipped(tmp_path):
    path = str(tmp_path / LEDGER_FILENAME)
    _seed(path, "m", [1.0, 2.0], unit="ms")
    with open(path, "a") as f:
        f.write('{"schema": 1, "metric": "m", "val')  # the crash tear
    recs = read_ledger(path)
    assert [r["value"] for r in recs] == [1.0, 2.0]
    # appending after the tear still works; reader drops only the tear
    PerfLedger(path).append(
        [make_record("s", "bench", "m", 3.0, "ms", run_id="r2")])
    assert len(read_ledger(path)) == 3


def test_read_ledger_missing_and_foreign_lines(tmp_path):
    assert read_ledger(str(tmp_path / "nope.jsonl")) == []
    path = str(tmp_path / LEDGER_FILENAME)
    with open(path, "w") as f:
        f.write("not json\n")
        f.write(json.dumps({"schema": 99, "metric": "x"}) + "\n")
        f.write(json.dumps(make_record("s", "bench", "m", 1.0)) + "\n")
    assert len(read_ledger(path)) == 1


def test_snapshot_atomic(tmp_path):
    path = str(tmp_path / LEDGER_FILENAME)
    led = _seed(path, "m", [1.0, 2.0])
    out = led.snapshot()
    data = json.load(open(out))
    assert data["n_records"] == 2
    assert data["verdicts"][0]["metric"] == "m"
    assert not [p for p in os.listdir(str(tmp_path)) if ".tmp." in p]


# -- verdicts -----------------------------------------------------------------

def test_regression_throughput_collapse(tmp_path):
    """The acceptance fixture: healthy throughput then a 30% drop."""
    led = _seed(tmp_path / "l.jsonl", "bench.imgs_per_sec",
                [100.0, 101.0, 99.0, 100.0, 70.0])
    (v,) = led.check()
    assert v["verdict"] == "regression"
    assert v["direction"] == "higher_is_better"
    assert v["delta_pct"] == pytest.approx(-30.0, abs=1.0)
    assert regressions([v]) == [v]


def test_regression_latency_direction(tmp_path):
    up = _seed(tmp_path / "up.jsonl", "serve.ttft_p99_ms",
               [10.0, 10.5, 9.9, 14.0], unit="ms")
    (v,) = up.check()
    assert v["verdict"] == "regression"  # latency UP is a regression
    down = _seed(tmp_path / "down.jsonl", "serve.ttft_p99_ms",
                 [10.0, 10.5, 9.9, 7.0], unit="ms")
    (v,) = down.check()
    assert v["verdict"] == "improvement"


def test_within_tolerance_is_ok(tmp_path):
    led = _seed(tmp_path / "l.jsonl", "m", [100.0, 101.0, 95.0])
    (v,) = led.check(tolerance=0.10)
    assert v["verdict"] == "ok"
    (v,) = led.check(tolerance=0.01)
    assert v["verdict"] == "regression"  # tolerance is stated, not fixed


def test_single_point_insufficient_history(tmp_path):
    led = _seed(tmp_path / "l.jsonl", "m", [100.0])
    (v,) = led.check()
    assert v["verdict"] == "insufficient_history"
    assert v["baseline"] is None and v["delta_pct"] is None


def test_valueless_records_never_enter_baselines(tmp_path):
    path = str(tmp_path / LEDGER_FILENAME)
    led = _seed(path, "m", [100.0, 100.0])
    led.append([make_record("SERVE.json", "serve", "m", None,
                            run_id="r04")])
    led.append([make_record("s", "bench", "m", 99.0, "images/sec",
                            run_id="r5")])
    traj = trajectories(led.records())
    assert list(traj) == ["m"] and len(traj["m"]) == 3
    (v,) = led.check()
    assert v["verdict"] == "ok"  # the gap is not a 0-valued baseline
    # but the log keeps the record as the gap's witness
    assert sum(1 for r in led.records() if r["value"] is None) == 1


def test_trailing_window_bounds_baseline(tmp_path):
    # 10 old slow points, then 5 recent fast ones: the window must
    # baseline on the recent regime, so the latest fast point is "ok"
    led = _seed(tmp_path / "l.jsonl", "m",
                [10.0] * 10 + [100.0] * 5 + [101.0])
    (v,) = led.check(window=5)
    assert v["verdict"] == "ok"
    assert v["baseline"] == pytest.approx(100.0)


def test_check_records_empty():
    assert check_records([]) == []
    assert check_ledger("/nonexistent/ledger.jsonl") == []


# -- backfill over the repo's committed artifacts -----------------------------

def test_backfill_repo_artifacts_idempotent(tmp_path):
    led = PerfLedger(str(tmp_path / LEDGER_FILENAME))
    written = led.backfill(REPO)
    # CONVERGE.json is the one committed report the ledger reads
    assert {r["kind"] for r in written} == {"converge"}
    assert len(written) >= 6, "repo artifacts did not classify"
    assert led.backfill(REPO) == []  # fingerprint-idempotent
    assert not regressions(led.check()), \
        "repo's own artifacts must not read as a regression"


# -- tmprof --ledger exit contract --------------------------------------------

def test_tmprof_check_exits_1_on_regression(tmp_path, capsys):
    path = str(tmp_path / "l.jsonl")
    _seed(path, "bench.imgs_per_sec", [100.0, 101.0, 99.0, 100.0, 70.0])
    rc = prof.main(["--ledger", "check", "--ledger-path", path])
    out = capsys.readouterr().out
    assert rc == 1
    assert "regression" in out and "bench.imgs_per_sec" in out


def test_tmprof_check_exits_0_on_repo_artifacts(tmp_path, capsys):
    """A ledger backfilled from the repo's committed artifacts checks
    clean — built in a fixture dir: the repo-root ``PERF_LEDGER.jsonl``
    is the driver's file, which nothing here reads or writes."""
    path = str(tmp_path / LEDGER_FILENAME)
    assert LEDGER_FILENAME != "PERF_LEDGER.jsonl"
    assert prof.main(["--ledger", "backfill", REPO,
                      "--ledger-path", path]) == 0
    rc = prof.main(["--ledger", "check", "--ledger-path", path])
    capsys.readouterr()
    assert rc == 0


def test_tmprof_check_json(tmp_path, capsys):
    path = str(tmp_path / "l.jsonl")
    _seed(path, "m", [100.0, 100.0, 100.0])
    rc = prof.main(["--ledger", "check", "--ledger-path", path, "--json"])
    data = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert data["verdicts"][0]["verdict"] == "ok"


def test_tmprof_update_and_show(tmp_path, capsys):
    art = tmp_path / "SERVE.json"
    art.write_text(json.dumps(
        {"metric": "serve_tokens_per_sec", "value": 100.0,
         "unit": "tokens/sec", "run_id": "r1"}))
    path = str(tmp_path / "l.jsonl")
    rc = prof.main(["--ledger", "update", str(art), "--ledger-path", path])
    assert rc == 0
    assert "ingested 1 new record(s)" in capsys.readouterr().out
    assert os.path.exists(str(tmp_path / "TMPROF_LEDGER.json"))
    rc = prof.main(["--ledger", "show", "--ledger-path", path])
    assert rc == 0
    assert "serve.tokens_per_sec" in capsys.readouterr().out


def test_tmprof_ledger_usage_errors(tmp_path, capsys):
    # update without artifacts; missing artifact; check without a ledger
    assert prof.main(["--ledger", "update",
                      "--ledger-path", str(tmp_path / "l.jsonl")]) == 2
    assert prof.main(["--ledger", "update", str(tmp_path / "nope.json"),
                      "--ledger-path", str(tmp_path / "l.jsonl")]) == 2
    assert prof.main(["--ledger", "check",
                      "--ledger-path", str(tmp_path / "nope.jsonl")]) == 2
    assert prof.main(["--ledger", "backfill", str(tmp_path / "nodir"),
                      "--ledger-path", str(tmp_path / "l.jsonl")]) == 2
    capsys.readouterr()


def test_tmprof_backfill_cli(tmp_path, capsys):
    art = tmp_path / "CONVERGE.json"
    art.write_text(json.dumps(
        {"results": [{"model": "wrn", "target_error": 0.5,
                      "best_val_error": 0.4}]}))
    path = str(tmp_path / "l.jsonl")
    rc = prof.main(["--ledger", "backfill", str(tmp_path),
                    "--ledger-path", path])
    assert rc == 0
    assert "backfilled 1 record(s)" in capsys.readouterr().out


def test_classify_converge_margin_records():
    """ISSUE 20: CONVERGE.json rows become higher-is-better margin
    records (target_error - best_val_error) the trend gate can hold a
    chaos acceptance to; rows without both numbers are skipped."""
    conv = {"run_id": "r20", "results": [
        {"model": "wrn_easgd", "rule": "EASGD", "target_error": 0.50,
         "best_val_error": 0.42, "passed": True, "epochs_to_target": 3},
        {"model": "incomplete", "target_error": 0.5},
        "not-a-row",
    ]}
    (rec,) = classify_artifact("CONVERGE.json", conv)
    assert rec["metric"] == "converge.wrn_easgd.margin"
    assert rec["value"] == pytest.approx(0.08)
    assert rec["kind"] == "converge" and rec["extra"]["rule"] == "EASGD"
    assert rec["extra"]["passed"] is True
    assert rec["extra"]["epochs_to_target"] == 3
    # margin trends UPWARD: a shrinking margin is the regression
    assert not lower_is_better("converge.wrn_easgd.margin", "margin")
    # the backfill sweep picks the artifact up
    from theanompi_tpu.telemetry.ledger import BACKFILL_PATTERNS
    import fnmatch
    assert any(fnmatch.fnmatch("CONVERGE.json", p)
               for p in BACKFILL_PATTERNS)
