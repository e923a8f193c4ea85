"""ISSUE 11: multi-job fleet orchestration on the elastic supervisor.

Unit matrix on milliseconds-fast fakes: the device-pool ledger (gang
alloc, crash-safe two-generation persistence, the ``ledger_torn_write``
fault site), the priority queue, spec validation, child-command
construction, and the scheduler lifecycle driven by ``python -c``
children (completion, priority preemption + requeue + elastic resume,
``kill_job`` fault absorbed by the per-job supervisor, crash -> failed).
The ``tmfleet`` CLI contract (submit/status/run, tmlauncher exit codes)
runs on the same fakes.

THE acceptance e2e drives two REAL ``tmlauncher`` jobs through one
mesh8 pool: contention, priority preemption (exit 75 + cadence
checkpoint), elastic resume on the 4 devices that remain, completion —
with final params of BOTH jobs bit-equal to uncontended single-job runs
and a gap-free concatenated data trace (the PR 9 witness).
"""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from theanompi_tpu.fleet import (
    DeviceLedger,
    FleetScheduler,
    JobQueue,
    JobRecord,
    JobSpec,
    JobSpecError,
    LedgerError,
    build_child_cmd,
    job_dir,
    read_fleet_events,
    read_record,
    write_record,
)
from theanompi_tpu.fleet import cli as fleet_cli
from theanompi_tpu.resilience import (
    EXIT_CLEAN,
    EXIT_CONFIG,
    EXIT_CRASH,
    EXIT_PREEMPTED,
    FaultInjected,
    FaultPlan,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the resilience-e2e tiny config — fleet children reuse these shapes so
#: every subprocess hits the session compile cache other files warmed
TINY_CFG = {"depth": 10, "widen": 1, "batch_size": 4, "image_size": 8,
            "n_train": 32, "n_val": 16, "n_epochs": 2, "precision": "fp32"}


def _trace(path):
    """-> [(epoch, batch_index)] consumed-step witness lines."""
    if not os.path.exists(path):
        return []
    return [tuple(int(v) for v in line.split())
            for line in open(path) if line.strip()]


def _assert_ckpt_equal(path_a, path_b):
    with np.load(path_a) as a, np.load(path_b) as b:
        assert set(a.files) == set(b.files)
        for k in a.files:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)


# -- device-pool ledger -------------------------------------------------------

def test_ledger_gang_alloc_all_or_nothing(tmp_path):
    led = DeviceLedger(str(tmp_path), 8)
    assert led.free == 8
    assert led.alloc("a", 5)
    assert led.free == 3 and led.lease_of("a") == 5
    assert not led.alloc("b", 4)  # all-or-nothing: nothing changed
    assert led.free == 3 and led.lease_of("b") == 0
    assert led.alloc("b", 3)
    assert led.free == 0
    with pytest.raises(LedgerError, match="already holds"):
        led.alloc("a", 1)
    with pytest.raises(LedgerError, match="pool"):
        led.alloc("c", 9)  # impossible even on an empty pool
    with pytest.raises(LedgerError, match="pool"):
        led.alloc("c", 0)
    assert led.release("a") == 5
    assert led.free == 5
    assert led.release("a") == 0  # idempotent, not an error


def test_ledger_persists_reopens_and_probes(tmp_path, monkeypatch):
    d = str(tmp_path / "pool")
    led = DeviceLedger(d, 8)
    led.alloc("a", 3)
    re = DeviceLedger(d)  # size + leases come from the persisted state
    assert re.pool_size == 8 and re.lease_of("a") == 3 and re.free == 5
    with pytest.raises(LedgerError, match="conflicts"):
        DeviceLedger(d, 4)
    # fresh pool with no explicit size: the elastic probe seam (PR 8's
    # env override route — instant, no subprocess)
    monkeypatch.setenv("THEANOMPI_ELASTIC_DEVICES", "6")
    assert DeviceLedger(str(tmp_path / "fresh")).pool_size == 6
    monkeypatch.delenv("THEANOMPI_ELASTIC_DEVICES")
    with pytest.raises(LedgerError, match="pool"):
        DeviceLedger(str(tmp_path / "zero"), 0)


@pytest.mark.faultinject
def test_ledger_torn_write_recovers_previous_generation(tmp_path):
    d = str(tmp_path / "pool")
    # persist ordinal 0 is the fresh-pool publish; the alloc's persist
    # (ordinal 1) tears the just-committed main file in half
    plan = FaultPlan.parse("fleet:ledger_torn_write@1")
    led = DeviceLedger(d, 8, fault_plan=plan)
    led.alloc("a", 2)
    with pytest.raises(ValueError):
        json.load(open(os.path.join(d, "ledger.json")))  # really torn
    # the next load steps back one generation instead of crashing
    rec = DeviceLedger(d)
    assert rec.pool_size == 8
    assert rec.free == 8  # generation 0 predates the lease
    # every generation unreadable -> typed refusal
    for p in ("ledger.json", "ledger.json.prev"):
        with open(os.path.join(d, p), "w") as f:
            f.write("{torn")
    with pytest.raises(LedgerError, match="unreadable"):
        DeviceLedger(d)


@pytest.mark.faultinject
def test_fleet_fault_actions_count_separate_ordinals(tmp_path):
    """The action filter on FaultPlan.fire: a kill_job spec at ordinal 0
    must NOT be consumed by the ledger's persist counter (the two fleet
    actions count different ordinal spaces)."""
    plan = FaultPlan.parse("fleet:kill_job@0")
    led = DeviceLedger(str(tmp_path), 4, fault_plan=plan)
    led.alloc("a", 1)  # persists 0 and 1: neither may tear nor consume
    assert json.load(open(led.path))["leases"] == {"a": 1}
    assert plan.fire("fleet", 0, action="kill_job") == "kill_job"


# -- specs, records, queue ----------------------------------------------------

def test_job_spec_validation():
    with pytest.raises(JobSpecError, match="invalid job id"):
        JobSpec(job_id="-bad").validate()
    with pytest.raises(JobSpecError, match="invalid job id"):
        JobSpec(job_id="a b").validate()
    with pytest.raises(JobSpecError, match="min_devices"):
        JobSpec(job_id="a", min_devices=0).validate()
    with pytest.raises(JobSpecError, match="max_devices"):
        JobSpec(job_id="a", min_devices=4, max_devices=2).validate()
    JobSpec(job_id="ok.job-1_x", min_devices=2, max_devices=2).validate()


def test_job_record_roundtrip_and_unknown_keys(tmp_path):
    spec = JobSpec(job_id="j", priority=3, min_devices=2,
                   model_config={"depth": 10}, env={"K": "v"})
    rec = JobRecord(spec=spec, status="preempted", preemptions=1,
                    preempt_exits=[75])
    write_record(str(tmp_path), rec)
    back = read_record(str(tmp_path), "j")
    assert back == rec
    with pytest.raises(JobSpecError, match="unknown job-spec keys"):
        JobSpec.from_dict({"job_id": "j", "nope": 1})
    with pytest.raises(JobSpecError, match="unknown job status"):
        JobRecord.from_dict({"spec": spec.to_dict(), "status": "zombie"})


def test_job_queue_priority_then_fifo():
    q = JobQueue()
    for jid, pri in (("a", 0), ("b", 5), ("c", 5), ("d", 1)):
        q.push(JobSpec(job_id=jid, priority=pri))
    assert [s.job_id for s in q.ordered()] == ["b", "c", "d", "a"]
    with pytest.raises(JobSpecError, match="already queued"):
        q.push(JobSpec(job_id="b", priority=5))
    q.remove("b")
    assert len(q) == 3 and "b" not in q
    # a requeued victim keeps its original submit sequence: it does not
    # jump peers that were already waiting at its priority
    q2 = JobQueue()
    q2.push(JobSpec(job_id="x", priority=0))
    q2.push(JobSpec(job_id="y", priority=0))
    q2.remove("x")          # x runs, then is preempted...
    q2.push(JobSpec(job_id="x", priority=0))  # ...and re-enters
    assert [s.job_id for s in q2.ordered()] == ["x", "y"]


def test_build_child_cmd_launcher_and_argv_seam(tmp_path):
    spec = JobSpec(job_id="j", model_config={"depth": 10,
                                             "precision": "fp32"},
                   rule_config={"exch_strategy": "zero1"},
                   extra_args=["--quiet2"])
    cmd = build_child_cmd(spec, 4, str(tmp_path))
    assert cmd[:4] == [sys.executable, "-m", "theanompi_tpu.launcher",
                       "--rule"]
    assert "--devices" in cmd and cmd[cmd.index("--devices") + 1] == "4"
    # values ride the launcher's --set literal grammar via repr
    assert "depth=10" in cmd and "precision='fp32'" in cmd
    assert "exch_strategy='zero1'" in cmd
    assert "--resume" not in cmd
    resumed = build_child_cmd(spec, 2, str(tmp_path), resume=True)
    assert resumed[-2:] == ["--resume", "--resume-reshard"]
    # the argv test seam bypasses the launcher entirely
    fake = JobSpec(job_id="j", argv=["echo", "hi"])
    assert build_child_cmd(fake, 4, str(tmp_path), resume=True) == \
        ["echo", "hi"]


# -- scheduler on python -c fakes --------------------------------------------

#: a cooperative victim: SIGTERM -> exit 75, like a supervised trainer
#: whose preemption handler checkpointed; sleeps long on its first
#: episode (so a preemption can land), finishes fast on the second
_COOP = r'''
import os, signal, sys, time
signal.signal(signal.SIGTERM, lambda s, f: sys.exit(75))
marker = os.environ["FLEET_TEST_MARKER"]
open(marker, "a").write("ep\n")
time.sleep(6.0 if len(open(marker).readlines()) < 2 else 0.05)
'''


def _fake(job_id, body, **kw):
    return JobSpec(job_id=job_id, argv=[sys.executable, "-c", body],
                   max_restarts=kw.pop("max_restarts", 0), **kw)


def _run_sched(sched, timeout=60):
    box = {}
    t = threading.Thread(target=lambda: box.setdefault("rc", sched.run()))
    t.start()
    t.join(timeout)
    assert not t.is_alive(), "scheduler hung"
    return box["rc"]


def test_scheduler_runs_jobs_to_completion_and_frees_pool(tmp_path):
    d = str(tmp_path / "fleet")
    sched = FleetScheduler(d, 8, poll_s=0.01, telemetry=False)
    sched.submit(_fake("a", "pass", min_devices=2, max_devices=2))
    sched.submit(_fake("b", "pass", min_devices=2, max_devices=2))
    assert _run_sched(sched) == EXIT_CLEAN
    for jid in ("a", "b"):
        rec = read_record(d, jid)
        assert rec.status == "done" and rec.episodes == 1
        assert rec.devices is None and rec.last_exit == 0
    assert sched.ledger.free == 8  # every lease returned
    names = [e["event"] for e in read_fleet_events(d)]
    assert names.count("fleet.schedule") == 2
    assert names.count("fleet.complete") == 2


def test_scheduler_submit_rejects_bad_and_duplicate(tmp_path):
    sched = FleetScheduler(str(tmp_path), 4, telemetry=False)
    sched.submit(_fake("a", "pass"))
    with pytest.raises(JobSpecError, match="already exists"):
        sched.submit(_fake("a", "pass"))
    with pytest.raises(JobSpecError, match="pool has only"):
        sched.submit(_fake("big", "pass", min_devices=5))


def test_scheduler_crash_is_failed_and_exit_crash(tmp_path):
    d = str(tmp_path / "fleet")
    sched = FleetScheduler(d, 4, poll_s=0.01, telemetry=False)
    sched.submit(_fake("bad", "import sys; sys.exit(3)"))
    sched.submit(_fake("good", "pass"))
    assert _run_sched(sched) == EXIT_CRASH
    assert read_record(d, "bad").status == "failed"
    assert read_record(d, "bad").last_exit == 3
    assert read_record(d, "good").status == "done"
    fails = [e for e in read_fleet_events(d) if e["event"] == "fleet.fail"]
    assert fails and fails[0]["cause"] == "crash"


def test_scheduler_priority_preemption_and_elastic_resume(tmp_path):
    """The fake-child lifecycle: A (low priority) holds all 8; B (high
    priority, needs 4) preempts it; A exits 75, is requeued, and resumes
    on the 4 devices B left — the event log records the whole story in
    order."""
    d = str(tmp_path / "fleet")
    marker = str(tmp_path / "marker")
    sched = FleetScheduler(d, 8, poll_s=0.01, telemetry=False,
                           env={"FLEET_TEST_MARKER": marker})
    sched.submit(_fake("low", _COOP, priority=0, min_devices=1))
    box = {}
    t = threading.Thread(target=lambda: box.setdefault("rc", sched.run()))
    t.start()
    deadline = time.perf_counter() + 30
    while time.perf_counter() < deadline and not os.path.exists(marker):
        time.sleep(0.005)
    assert os.path.exists(marker), "job 'low' never started"
    sched.submit(_fake("high", "pass", priority=5,
                       min_devices=4, max_devices=4))
    t.join(60)
    assert not t.is_alive() and box["rc"] == EXIT_CLEAN

    low, high = read_record(d, "low"), read_record(d, "high")
    assert low.status == "done" and high.status == "done"
    assert low.preemptions == 1 and low.episodes == 2
    assert low.preempt_exits == [75]  # cooperative, not SIGKILLed
    assert high.preemptions == 0 and high.episodes == 1
    ev = read_fleet_events(d)
    story = [(e["event"], e["job"]) for e in ev]
    assert story[:4] == [("fleet.schedule", "low"),
                         ("fleet.preempt", "low"),
                         ("fleet.schedule", "high"),
                         ("fleet.resume", "low")]
    assert sorted(story[4:]) == [("fleet.complete", "high"),
                                 ("fleet.complete", "low")]
    assert ev[0]["devices"] == 8
    assert ev[1]["victim_of"] == "high"
    assert ev[2]["devices"] == 4
    assert ev[3]["devices"] == 4  # elastic: resumed on what remained


@pytest.mark.faultinject
def test_scheduler_kill_job_fault_absorbed_by_supervisor(tmp_path):
    """fleet:kill_job@0 SIGKILLs the first launched child; the JOB's own
    supervisor classifies a crash and restarts it in place — the fleet
    sees one episode, and the per-job resilience.json records both
    attempts."""
    d = str(tmp_path / "fleet")
    body = ("import os, sys, time\n"
            "time.sleep(30 if os.environ['THEANOMPI_ATTEMPT'] == '1' "
            "else 0)\n")
    sched = FleetScheduler(d, 4, poll_s=0.01, telemetry=False,
                           fault_plan="fleet:kill_job@0")
    sched.submit(_fake("j", body, max_restarts=2, backoff_base=0.0))
    assert _run_sched(sched) == EXIT_CLEAN
    rec = read_record(d, "j")
    assert rec.status == "done"
    assert rec.episodes == 1 and rec.preemptions == 0
    art = json.load(open(os.path.join(job_dir(d, "j"), "resilience.json")))
    assert [a["cause"] for a in art["attempts"]] == ["crash", "clean"]


def test_scheduler_picks_up_live_submits_and_preempts(tmp_path):
    """The BASELINE step-8 flow: `tmfleet submit` publishes a queued
    job.json into the fleet dir WHILE `tmfleet run` owns the pool — the
    running scheduler must adopt it on its next pass and let it contend
    (here: preempt the incumbent).  An unschedulable live submit is
    marked failed on disk instead of wedging the loop."""
    d = str(tmp_path / "fleet")
    marker = str(tmp_path / "marker")
    sched = FleetScheduler(d, 8, poll_s=0.01, telemetry=False,
                           env={"FLEET_TEST_MARKER": marker})
    sched.submit(_fake("low", _COOP, priority=0, min_devices=1))
    box = {}
    t = threading.Thread(target=lambda: box.setdefault("rc", sched.run()))
    t.start()
    deadline = time.perf_counter() + 30
    while time.perf_counter() < deadline and not os.path.exists(marker):
        time.sleep(0.005)
    assert os.path.exists(marker), "job 'low' never started"
    # the other-process half: a bare queued record on disk, NOT submit()
    write_record(d, JobRecord(spec=_fake("high", "pass", priority=5,
                                         min_devices=4, max_devices=4)))
    write_record(d, JobRecord(spec=_fake("toobig", "pass",
                                         min_devices=99)))
    t.join(60)
    assert not t.is_alive()
    assert read_record(d, "low").preemptions == 1
    assert read_record(d, "low").status == "done"
    assert read_record(d, "high").status == "done"
    assert read_record(d, "toobig").status == "failed"
    fails = [e for e in read_fleet_events(d) if e["event"] == "fleet.fail"]
    assert fails and fails[0]["job"] == "toobig"
    assert "config" in fails[0]["cause"]


def test_scheduler_adopts_records_from_a_dead_scheduler(tmp_path):
    """A fleet dir whose scheduler died mid-flight: running/preempting
    records re-enter as preempted (their cadence checkpoints are on
    disk), queued ones re-queue, terminal ones are left alone."""
    d = str(tmp_path / "fleet")
    DeviceLedger(d, 4).alloc("was-running", 4)  # the dead owner's lease
    for status in ("running", "queued", "done"):
        write_record(d, JobRecord(
            spec=_fake(f"was-{status}", "pass"), status=status,
            devices=4 if status == "running" else None))
    sched = FleetScheduler(d, 4, poll_s=0.01, telemetry=False)
    from theanompi_tpu.fleet.jobs import list_records

    for rec in list_records(d):
        if rec.status not in ("done", "failed"):
            sched.adopt(rec)
    assert sched.ledger.free == 4  # the stale lease was released
    assert _run_sched(sched) == EXIT_CLEAN
    assert read_record(d, "was-running").status == "done"
    assert read_record(d, "was-queued").status == "done"


# -- tmfleet CLI --------------------------------------------------------------

def test_tmfleet_submit_and_status_contract(tmp_path, capsys):
    d = str(tmp_path / "fleet")
    rc = fleet_cli.main([
        "submit", "--fleet-dir", d, "--job-id", "a", "--priority", "2",
        "--min-devices", "2", "--max-devices", "4",
        "--set", "depth=10", "--set", "precision='fp32'",
        "--rule-set", "exch_strategy='zero1'",
        "--extra-arg=--record-dir=/rec"])
    assert rc == EXIT_CLEAN
    assert "queued 'a'" in capsys.readouterr().out
    rec = read_record(d, "a")
    assert rec.status == "queued" and rec.spec.priority == 2
    # the --set literal grammar: ints stay ints, strings stay strings
    assert rec.spec.model_config == {"depth": 10, "precision": "fp32"}
    assert rec.spec.rule_config == {"exch_strategy": "zero1"}
    assert rec.spec.extra_args == ["--record-dir=/rec"]
    # duplicate + invalid specs take the launcher's config exit code
    assert fleet_cli.main(["submit", "--fleet-dir", d,
                           "--job-id", "a"]) == EXIT_CONFIG
    assert fleet_cli.main(["submit", "--fleet-dir", d, "--job-id", "b",
                           "--min-devices", "0"]) == EXIT_CONFIG
    err = capsys.readouterr().err
    assert "tmfleet: error: config:" in err
    assert fleet_cli.main(["status", "--fleet-dir", d]) == EXIT_CLEAN
    out = json.loads(capsys.readouterr().out)
    assert [j["spec"]["job_id"] for j in out["jobs"]] == ["a"]
    assert out["pool"] is None  # no scheduler has sized the pool yet
    # argparse usage errors keep argparse's own exit code
    assert fleet_cli.main(["submit"]) == 2
    assert fleet_cli.main(["bogus-subcommand"]) == 2


def test_tmfleet_run_drives_persisted_jobs(tmp_path, capsys):
    """``tmfleet run`` adopts every persisted non-terminal record —
    including a dead scheduler's in-flight job — and returns the fleet
    verdict; a bad --fault-plan is a config error."""
    d = str(tmp_path / "fleet")
    write_record(d, JobRecord(spec=_fake("q", "pass")))
    write_record(d, JobRecord(
        spec=_fake("inflight", "pass"), status="running", devices=2))
    rc = fleet_cli.main(["run", "--fleet-dir", d, "--pool-size", "4",
                         "--poll-s", "0.01"])
    assert rc == EXIT_CLEAN
    out = json.loads(capsys.readouterr().out)
    assert {j["status"] for j in out["jobs"]} == {"done"}
    assert out["pool"]["pool_size"] == 4 and out["pool"]["leases"] == {}
    assert fleet_cli.main(["run", "--fleet-dir", d, "--pool-size", "4",
                           "--fault-plan", "fleet:bogus@1"]) == EXIT_CONFIG
    # a failed job flips the verdict to the crash exit code
    d2 = str(tmp_path / "fleet2")
    write_record(d2, JobRecord(spec=_fake("bad", "import sys; sys.exit(9)")))
    assert fleet_cli.main(["run", "--fleet-dir", d2, "--pool-size", "2",
                           "--poll-s", "0.01", "--quiet"]) == EXIT_CRASH


def test_fleet_telemetry_names_registered():
    from theanompi_tpu.telemetry.metrics import FLEET_INSTANTS

    assert set(FLEET_INSTANTS) == {"fleet.schedule", "fleet.preempt",
                                   "fleet.resume", "fleet.complete",
                                   "fleet.fail", "fleet.hang",
                                   "fleet.drain"}


def test_fleet_fault_grammar():
    plan = FaultPlan.parse("fleet:kill_job@1;fleet:ledger_torn_write@2")
    assert plan.fire("fleet", 1, action="ledger_torn_write") is None
    assert plan.fire("fleet", 1, action="kill_job") == "kill_job"
    assert plan.fire("fleet", 1, action="kill_job") is None  # one-shot
    assert plan.fire("fleet", 2, action="ledger_torn_write") == \
        "ledger_torn_write"
    with pytest.raises(Exception, match="invalid for site"):
        FaultPlan.parse("fleet:stall@1")


# -- THE acceptance e2e -------------------------------------------------------

def _child_env():
    return {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "JAX_THREEFRY_PARTITIONABLE": "true",
        "PYTHONPATH": REPO,
    }


def _bsp(devices, ck, n_epochs=2, model_over=None, **cfg):
    from theanompi_tpu import BSP

    rule = BSP(config={"verbose": False, "checkpoint_dir": ck, **cfg})
    rule.init(devices=devices, modelfile="theanompi_tpu.models.wide_resnet",
              modelclass="WideResNet",
              model_config={**TINY_CFG, "n_epochs": n_epochs,
                            **(model_over or {})})
    return rule


def _find_split(lines, n_train, gb_hi, gb_lo, n_epochs):
    """The unique index splitting a concatenated two-episode trace into
    the big-batch prefix (episode 1) and small-batch suffix (the elastic
    resume): the one split whose sample spans tile every epoch's
    [0, n_train) exactly, in order — the PR 9 no-replay/no-skip witness
    generalized across a global-batch change."""
    def valid(split):
        pos, epoch = 0, 0
        for i, (e, c) in enumerate(lines):
            gb = gb_hi if i < split else gb_lo
            if pos == n_train:
                epoch, pos = epoch + 1, 0
            if e != epoch or c * gb != pos:
                return False
            pos += gb
        return epoch == n_epochs - 1 and pos == n_train
    hits = [s for s in range(len(lines) + 1) if valid(s)]
    assert len(hits) == 1, f"ambiguous or impossible trace split: {hits}"
    return hits[0]


def test_fleet_two_job_contention_preempt_elastic_resume_bit_equal(
        tmp_path, monkeypatch, subproc_compile_cache):
    """THE acceptance scenario, end to end on the CPU mesh8 pool:

    Job A (low priority, zero1, takes all 8) is preempted by job B
    (high priority, needs exactly 4), exits 75 with a cadence
    checkpoint, and resumes **elastically** on the 4 devices B left via
    ``--resume --resume-reshard``.  Both jobs complete; B's final
    checkpoint is bit-equal to an uncontended single-job run of the same
    config, and A's is bit-equal to a single-job run driven through the
    SAME transition (stop after the k steps episode 1 completed, then a
    mesh4 resharded resume) — the fleet added zero numerical
    perturbation, and the concatenated data trace is gap-free."""
    monkeypatch.delenv("THEANOMPI_DATA_TRACE", raising=False)
    monkeypatch.delenv("THEANOMPI_FAULT_PLAN", raising=False)
    fleet_dir = str(tmp_path / "fleet")
    trace_a = str(tmp_path / "trace_a")
    trace_b = str(tmp_path / "trace_b")
    # A: mesh8 2 steps/epoch at GB=32; after the shrink, mesh4 4 at 16.
    # Synchronous every-iter cadence saves make the preemption point an
    # exact checkpoint (same determinism note as the PR 9 runbook).
    spec_a = JobSpec(
        job_id="big-lowpri", priority=0, min_devices=2,
        model_config={**TINY_CFG, "n_train": 64, "n_epochs": 3},
        rule_config={"exch_strategy": "zero1",
                     "checkpoint_every_n_iters": 1,
                     "checkpoint_async": False},
        env={**_child_env(), "THEANOMPI_DATA_TRACE": trace_a},
        max_restarts=3, backoff_base=0.1)
    spec_b = JobSpec(
        job_id="urgent", priority=10, min_devices=4, max_devices=4,
        model_config=dict(TINY_CFG),
        env={**_child_env(), "THEANOMPI_DATA_TRACE": trace_b},
        max_restarts=3, backoff_base=0.1)

    sched = FleetScheduler(fleet_dir, 8, poll_s=0.05)
    sched.submit(spec_a)
    box = {}
    t = threading.Thread(target=lambda: box.setdefault("rc", sched.run()))
    t.start()
    # contend only once A has really trained a step — the preemption must
    # interrupt work, and the trace line is the witness a step completed
    deadline = time.perf_counter() + 300
    while time.perf_counter() < deadline and not _trace(trace_a):
        time.sleep(0.02)
    assert _trace(trace_a), "job A never completed a step"
    sched.submit(spec_b)
    t.join(600)
    assert not t.is_alive(), "fleet scheduler hung"
    assert box["rc"] == EXIT_CLEAN

    # -- lifecycle: contention, cooperative exit 75, elastic resume ----------
    rec_a = read_record(fleet_dir, "big-lowpri")
    rec_b = read_record(fleet_dir, "urgent")
    assert rec_a.status == "done" and rec_b.status == "done"
    assert rec_a.preemptions == 1 and rec_a.episodes == 2
    assert rec_a.preempt_exits == [EXIT_PREEMPTED]  # checkpointed exit 75
    assert rec_b.preemptions == 0 and rec_b.episodes == 1
    ev = read_fleet_events(fleet_dir)
    story = [(e["event"], e["job"]) for e in ev]
    assert story[:4] == [("fleet.schedule", "big-lowpri"),
                         ("fleet.preempt", "big-lowpri"),
                         ("fleet.schedule", "urgent"),
                         ("fleet.resume", "big-lowpri")]
    assert ev[0]["devices"] == 8 and ev[1]["victim_of"] == "urgent"
    assert ev[2]["devices"] == 4
    assert ev[3]["devices"] == 4  # elastic: fewer devices than episode 1
    # the lifecycle mirrors into telemetry through the registered names
    tel_events = open([os.path.join(fleet_dir, "telemetry", f)
                       for f in os.listdir(
                           os.path.join(fleet_dir, "telemetry"))
                       if f.startswith("events-rank")][0]).read()
    assert "fleet.preempt" in tel_events and "fleet.resume" in tel_events

    # -- B: bit-equal to an uncontended single-job run -----------------------
    assert _trace(trace_b) == [(0, 0), (0, 1), (1, 0), (1, 1)]
    ck_b_ref = str(tmp_path / "ck_bref")
    _bsp(4, ck_b_ref).wait()
    _assert_ckpt_equal(
        os.path.join(job_dir(fleet_dir, "urgent"), "ckpt",
                     "ckpt_e0001.npz"),
        os.path.join(ck_b_ref, "ckpt_e0001.npz"))

    # -- A: gap-free trace across the shrink + bit-equal to the replay -------
    ta = _trace(trace_a)
    k = _find_split(ta, n_train=64, gb_hi=32, gb_lo=16, n_epochs=3)
    assert 1 <= k < 6, f"preemption landed outside episode 1's work: {k}"
    # the single-job reference: the SAME training trajectory with no
    # fleet — stop (deterministically) after the k steps episode 1
    # completed, then resume resharded onto mesh4, exactly as the
    # preempted job did
    ck_a_ref = str(tmp_path / "ck_aref")
    ref8 = str(tmp_path / "trace_ref8")
    monkeypatch.setenv("THEANOMPI_DATA_TRACE", ref8)
    rule8 = _bsp(8, ck_a_ref, n_epochs=3, model_over={"n_train": 64},
                 exch_strategy="zero1", checkpoint_every_n_iters=1,
                 checkpoint_async=False, fault_plan=f"step:raise@{k}")
    with pytest.raises(FaultInjected):
        rule8.wait()
    ref4 = str(tmp_path / "trace_ref4")
    monkeypatch.setenv("THEANOMPI_DATA_TRACE", ref4)
    rule4 = _bsp(4, ck_a_ref, n_epochs=3, model_over={"n_train": 64},
                 exch_strategy="zero1", checkpoint_every_n_iters=1,
                 checkpoint_async=False, resume_reshard=True)
    rule4.wait()
    assert rule4.trainer.epoch == 3
    # the fleet trace IS the reference's two traces concatenated —
    # nothing replayed, nothing skipped, across the global-batch change
    assert ta == _trace(ref8) + _trace(ref4)
    _assert_ckpt_equal(
        os.path.join(job_dir(fleet_dir, "big-lowpri"), "ckpt",
                     "ckpt_e0002.npz"),
        os.path.join(ck_a_ref, "ckpt_e0002.npz"))
    # and the final lineage is stamped with the post-shrink topology
    man = json.load(open(os.path.join(
        job_dir(fleet_dir, "big-lowpri"), "ckpt",
        "ckpt_e0002.manifest.json")))
    assert man["fingerprint"]["mesh"]["data"] == 4
    assert man["data_state"]["completed"] is True


@pytest.mark.faultinject
def test_fleet_chaos_easgd_straggler_absorbed_under_preemption(
        tmp_path, monkeypatch, subproc_compile_cache):
    """THE ISSUE 20 chaos acceptance, end to end on the CPU mesh8 pool:

    an EASGD job (low priority, tau=1, cadence saves) owns all 8
    devices; a priority-10 BSP job preempts it (cooperative exit 75); it
    resumes **elastically** on the 4 devices left via the new stacked
    reshard plan while absorbing injected stragglers — the
    async_staleness detector must reach WARN (degraded, absorbed) and
    never CRITICAL.  Both jobs finish; the BSP job is bit-equal to an
    uncontended run; the EASGD job's data trace is gap-free across the
    shrink and its convergence clears a margin gate against an
    uncontended same-seed run, recorded as a ledger-classifiable
    CONVERGE.json."""
    monkeypatch.delenv("THEANOMPI_DATA_TRACE", raising=False)
    monkeypatch.delenv("THEANOMPI_FAULT_PLAN", raising=False)
    fleet_dir = str(tmp_path / "fleet")
    trace_a = str(tmp_path / "trace_a")
    trace_b = str(tmp_path / "trace_b")
    tel_a = str(tmp_path / "tel_a")
    rec_dir_a = str(tmp_path / "rec_a")
    easgd_model = {**TINY_CFG, "n_train": 64, "n_epochs": 5}
    # stragglers at exchange ordinals 8-12: late enough that the stretch
    # detector's rolling median is anchored by a majority of good rounds
    # (episode 2's FIRST interval is an eval-warmup outlier, and each
    # stall itself joins the window), consecutive enough to sustain the
    # bad-round streak past async_min_rounds — and the post-stall rounds
    # recover the verdict to ok before close.  The 0.05s health tick
    # cannot miss the multi-second warn window the 0.6s stalls hold open.
    spec_a = JobSpec(
        job_id="easgd-lowpri", priority=0, min_devices=4, rule="EASGD",
        model_config=easgd_model,
        rule_config={"tau": 1, "scale_lr": False,
                     "checkpoint_every_n_iters": 1,
                     "checkpoint_async": False,
                     "telemetry_health": {"tick_s": 0.05}},
        env={**_child_env(), "THEANOMPI_DATA_TRACE": trace_a,
             "THEANOMPI_EASGD_SLOW_S": "0.6",
             "THEANOMPI_FAULT_PLAN": ",".join(
                 f"easgd:worker_slow@{i}" for i in range(8, 13))},
        extra_args=["--telemetry-dir", tel_a, "--record-dir", rec_dir_a],
        max_restarts=3, backoff_base=0.1)
    spec_b = JobSpec(
        job_id="urgent", priority=10, min_devices=4, max_devices=4,
        model_config=dict(TINY_CFG),
        env={**_child_env(), "THEANOMPI_DATA_TRACE": trace_b},
        max_restarts=3, backoff_base=0.1)

    sched = FleetScheduler(fleet_dir, 8, poll_s=0.05)
    sched.submit(spec_a)
    box = {}
    t = threading.Thread(target=lambda: box.setdefault("rc", sched.run()))
    t.start()
    deadline = time.perf_counter() + 300
    while time.perf_counter() < deadline and not _trace(trace_a):
        time.sleep(0.02)
    assert _trace(trace_a), "EASGD job never completed a step"
    sched.submit(spec_b)
    t.join(600)
    assert not t.is_alive(), "fleet scheduler hung"
    assert box["rc"] == EXIT_CLEAN

    # -- lifecycle: preemption without a restart budget spent ---------------
    rec_a = read_record(fleet_dir, "easgd-lowpri")
    rec_b = read_record(fleet_dir, "urgent")
    assert rec_a.status == "done" and rec_b.status == "done"
    assert rec_a.preemptions == 1 and rec_a.episodes == 2
    assert rec_a.preempt_exits == [EXIT_PREEMPTED]
    story = [(e["event"], e["job"]) for e in read_fleet_events(fleet_dir)]
    assert story[:4] == [("fleet.schedule", "easgd-lowpri"),
                         ("fleet.preempt", "easgd-lowpri"),
                         ("fleet.schedule", "urgent"),
                         ("fleet.resume", "easgd-lowpri")]

    # -- the contender is untouched by the chaos ----------------------------
    ck_b_ref = str(tmp_path / "ck_bref")
    _bsp(4, ck_b_ref).wait()
    _assert_ckpt_equal(
        os.path.join(job_dir(fleet_dir, "urgent"), "ckpt",
                     "ckpt_e0001.npz"),
        os.path.join(ck_b_ref, "ckpt_e0001.npz"))

    # -- async health: stragglers WARN, never CRITICAL ----------------------
    # each relaunched attempt truncates events-rank0.jsonl, so the final
    # file is episode 2's — the elastic mesh4 resume that absorbed the
    # injected stalls
    events_path = [os.path.join(tel_a, f) for f in sorted(os.listdir(tel_a))
                   if f.startswith("events-rank")][0]
    events = [json.loads(line) for line in open(events_path)]
    exchanges = [e for e in events if e.get("name") == "easgd.exchange"]
    assert exchanges, "no exchange instants in episode 2"
    assert any(e.get("stretch", 0) >= 2.5 for e in exchanges), \
        "injected stalls never registered as interval stretch"
    async_verdicts = [e for e in events
                      if e.get("name") == "health.verdict"
                      and e.get("detector") == "async_staleness"]
    sevs = [v["severity"] for v in async_verdicts]
    assert "warn" in sevs, f"straggler absorption never warned: {sevs}"
    assert "critical" not in sevs, f"chaos escalated to critical: {sevs}"
    health = json.load(open(os.path.join(tel_a, "HEALTH.json")))
    by_det = {v["detector"]: v for v in health["verdicts"]}
    assert by_det["async_staleness"]["severity"] in ("ok", "warn")

    # -- gap-free trace across the shrink -----------------------------------
    ta = _trace(trace_a)
    k = _find_split(ta, n_train=64, gb_hi=32, gb_lo=16, n_epochs=5)
    # episode 2 must hold >= 14 exchange rounds so the ordinal-8..12
    # stalls all land there: 20 - 2k rounds remain after k mesh8 steps
    assert 1 <= k <= 3, f"preemption landed outside episode 1's work: {k}"

    # -- convergence gate vs an uncontended same-seed run -------------------
    from theanompi_tpu import EASGD

    ref = EASGD(config={"verbose": False, "scale_lr": False, "tau": 1})
    ref.init(devices=8, modelfile="theanompi_tpu.models.wide_resnet",
             modelclass="WideResNet", model_config=dict(easgd_model))
    ref.wait()
    ref_best = float(np.min(ref.trainer.recorder.val_history["cost"]))
    hist = np.load(os.path.join(rec_dir_a, "val_history.npy"),
                   allow_pickle=True).item()
    assert list(hist["epoch"]) == [0, 1, 2, 3, 4]  # continuous curve
    best = float(np.min(hist["cost"]))
    target = ref_best * 1.25  # generous: tiny-data noise, not a tuning gate
    to_target = next((int(e) for e, c in zip(hist["epoch"], hist["cost"])
                      if c <= target), None)
    row = {"model": "wrn_easgd_chaos", "rule": "EASGD",
           "target_error": target, "best_val_error": best,
           "passed": best <= target, "epochs_to_target": to_target}
    conv_path = os.path.join(str(tmp_path), "CONVERGE.json")
    with open(conv_path, "w") as f:
        json.dump({"run_id": "chaos-e2e", "results": [row]}, f)
    assert row["passed"], (
        f"contended EASGD lost convergence: best {best:.4f} vs "
        f"uncontended {ref_best:.4f} (target {target:.4f})")
    # the artifact is ledger-classifiable as a higher-is-better margin
    from theanompi_tpu.telemetry.ledger import classify_artifact

    (margin_rec,) = classify_artifact(conv_path, json.load(open(conv_path)))
    assert margin_rec["metric"] == "converge.wrn_easgd_chaos.margin"
    assert margin_rec["value"] == pytest.approx(target - best)
