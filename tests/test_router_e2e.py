"""ISSUE 19 end-to-end: the router + autoscaler driving real fleet jobs.

Three process-level scenarios on the mesh8 CPU pool:

- THE acceptance run: two serving replicas and one low-priority training
  job share the 8-device pool; a traffic spike trips the autoscaler,
  which leases chips by preempting the training job through the existing
  cooperative SIGTERM→75 path (cadence checkpoint saved); a third
  replica serves the spike; when pressure subsides the pool shrinks, the
  lease releases, and training resumes — finishing with final params
  **bit-equal** to an uncontended run of the same config, while every
  request gets exactly one terminal state.
- chaos: SIGKILL one of two replicas mid-traffic; survivors absorb the
  orphaned requests, the REQUESTS.jsonl dedup keeps terminal states
  exactly-once, and the autoscaler backfills the dead replica's lease.
- capacity: the same burst through one replica vs two — the 2-replica
  p99 router-visible TTFT must be strictly below the 1-replica baseline
  (the "why a router at all" witness).

Replicas here are process fakes speaking the full durable contract
(queue.jsonl tail, REQUESTS.jsonl restart dedup, atomic SERVE_SNAPSHOT,
SIGTERM drain-with-give-back) with zero XLA, so the serving side costs
milliseconds; the real-``tmserve``-replica path is exercised by the
runbook dry-run in test_runbook.py.  The training job is the real
launcher stack end to end.
"""

import json
import os
import signal
import sys
import threading
import time

import pytest

from theanompi_tpu.fleet import (
    FleetScheduler,
    JobSpec,
    job_dir,
    read_fleet_events,
    read_record,
)
from theanompi_tpu.resilience import EXIT_CLEAN, EXIT_PREEMPTED
from theanompi_tpu.router import (
    AutoscaleConfig,
    AutoscalePolicy,
    Balancer,
    ReplicaPool,
    Router,
)
from theanompi_tpu.router.cli import drive_traffic, synthetic_entries

from test_fleet import (
    TINY_CFG,
    _assert_ckpt_equal,
    _bsp,
    _child_env,
    _trace,
)

#: a serving replica as a process: tails its durable queue, "serves" by
#: sleeping FAKE_MS_PER_TOKEN per generated token, answers into
#: REQUESTS.jsonl (skipping rids a previous attempt already answered —
#: the restart dedup), publishes atomic load snapshots, and on SIGTERM
#: sheds still-queued work with reason "draining" (the give-back the
#: router redistributes) before exiting clean.  The full replica
#: lifecycle contract with zero XLA behind it.
FAKE_REPLICA = r'''
import json, os, signal, sys, time
jdir = os.environ["THEANOMPI_JOB_DIR"]
ms = float(os.environ.get("FAKE_MS_PER_TOKEN", "0"))
qpath = os.path.join(jdir, "queue.jsonl")
rpath = os.path.join(jdir, "REQUESTS.jsonl")
spath = os.path.join(jdir, "SERVE_SNAPSHOT.json")
open(os.path.join(jdir, "replica.pid"), "w").write(str(os.getpid()))
flag = [False]
signal.signal(signal.SIGTERM, lambda s, f: flag.__setitem__(0, True))
answered = set()
try:
    for line in open(rpath):
        try: answered.add(json.loads(line)["rid"])
        except ValueError: pass
except OSError: pass
log = open(rpath, "a")
def rec(d):
    log.write(json.dumps(d) + "\n"); log.flush()
def snap(backlog, done):
    with open(spath + ".tmp", "w") as f:
        json.dump({"updated": time.time(), "backlog_tokens": backlog,
                   "token_rate": (1000.0 / ms if ms > 0 else 4000.0),
                   "n_done": done, "queue_len": 0, "n_active": 0,
                   "draining": flag[0]}, f)
    os.replace(spath + ".tmp", spath)
offset = 0; drain_seen = False; pending = []; n_done = 0
while True:
    try:
        with open(qpath, "rb") as f:
            f.seek(offset); data = f.read()
    except OSError: data = b""
    nl = data.rfind(b"\n")
    if nl >= 0:
        for line in data[:nl].split(b"\n"):
            if not line.strip(): continue
            try: e = json.loads(line)
            except ValueError: continue
            if e.get("op") == "drain": drain_seen = True; continue
            if "rid" not in e or e["rid"] in answered: continue
            pending.append(e)
        offset += nl + 1
    if flag[0]:
        for e in pending:
            answered.add(e["rid"])
            rec({"rid": e["rid"], "state": "shed", "reason": "draining",
                 "n_generated": 0})
        snap(0, n_done); sys.exit(0)
    if pending:
        e = pending.pop(0); answered.add(e["rid"])
        n = int(e.get("max_new_tokens", 8))
        if ms > 0: time.sleep(ms * n / 1000.0)
        qw = max(time.time() - e.get("enq_wall", time.time()), 0.0) * 1e3
        n_done += 1
        rec({"rid": e["rid"], "state": "done", "reason": None,
             "n_generated": n, "ttft_ms": ms, "queue_wait_ms": round(qw, 3)})
        snap(sum(int(p.get("max_new_tokens", 8)) for p in pending), n_done)
        continue
    if drain_seen: snap(0, n_done); sys.exit(0)
    time.sleep(0.004)
'''


def _replica_spec(ms_per_token, devices=2, priority=10):
    return {"priority": priority, "min_devices": devices,
            "max_devices": devices, "max_restarts": 0,
            "backoff_base": 0.1,
            "argv": [sys.executable, "-c", FAKE_REPLICA],
            "env": {"FAKE_MS_PER_TOKEN": str(ms_per_token)}}


def _run_fleet(sched):
    box = {}
    t = threading.Thread(target=lambda: box.setdefault("rc", sched.run()))
    t.start()
    return t, box


def test_router_autoscale_preempts_training_and_resumes_bit_equal(
        tmp_path, monkeypatch, subproc_compile_cache):
    """THE ISSUE 19 acceptance scenario (docstring at module top)."""
    monkeypatch.delenv("THEANOMPI_DATA_TRACE", raising=False)
    monkeypatch.delenv("THEANOMPI_FAULT_PLAN", raising=False)
    fleet_dir = str(tmp_path / "fleet")
    trace = str(tmp_path / "trace_train")
    sched = FleetScheduler(fleet_dir, 8, poll_s=0.02)
    pool = ReplicaPool(sched, _replica_spec(ms_per_token=4))
    policy = AutoscalePolicy(AutoscaleConfig(
        min_replicas=2, max_replicas=3, up_pressure_s=0.4, up_after_s=0.15,
        down_pressure_s=0.05, down_after_s=0.4, cooldown_s=0.3))
    router = Router(pool, balancer=Balancer(), policy=policy,
                    default_rate=250.0)
    pool.spawn()
    pool.spawn()
    # the contending training job: low priority, exactly the remaining 4
    # devices, every-iter synchronous cadence saves so the cooperative
    # preemption point is an exact checkpoint (the PR 9/14 determinism
    # contract), warm session compile cache for velocity
    sched.submit(JobSpec(
        job_id="train-lowpri", priority=0, min_devices=4, max_devices=4,
        model_config={**TINY_CFG, "n_train": 64, "n_epochs": 3},
        rule_config={"checkpoint_every_n_iters": 1,
                     "checkpoint_async": False},
        env={**_child_env(), "THEANOMPI_DATA_TRACE": trace},
        max_restarts=3, backoff_base=0.1))
    t, box = _run_fleet(sched)
    try:
        # spike only once training has really consumed a step — the
        # preemption must interrupt work in flight
        deadline = time.perf_counter() + 300
        while time.perf_counter() < deadline and not _trace(trace):
            time.sleep(0.02)
        assert _trace(trace), "training never completed a step"
        # the spike: a 24-request burst of long generations, then a light
        # trickle that keeps the loop alive long enough for the
        # down-hysteresis window to elapse while the pool is near-idle
        entries = synthetic_entries(24, vocab=256, prompt_len=4,
                                    max_new_tokens=50, rate=0.0, seed=0)
        tail = synthetic_entries(6, 256, 4, 4, 0.0, 1)
        for i, e in enumerate(tail):
            e["rid"] = 100 + i
            e["arrival_s"] = 2.0 + 0.3 * i
        results, wall = drive_traffic(router, entries + tail,
                                      poll_s=0.01, timeout_s=120)
        router.drain_all()
    finally:
        t.join(300)
    assert not t.is_alive(), "fleet scheduler hung"
    assert box["rc"] == EXIT_CLEAN

    # -- serving: every request exactly one terminal state, spike absorbed
    rep = router.report(wall_s=wall)
    assert rep["exactly_once"] is True
    assert rep["requests"] == 30 and rep["answered"] == 30
    assert rep["terminal_states"] == {"done": 30}
    assert rep["replicas_peak"] == 3, rep["replica_trajectory"]
    # pressure subsided: the pool shrank back before the final drain
    assert rep["replica_trajectory"][-1][1] <= 2, rep["replica_trajectory"]
    assert rep["replicas_dead"] == 0 and rep["duplicates"] == 0

    # -- the fleet story: lease via preemption, drain, elastic resume
    rec = read_record(fleet_dir, "train-lowpri")
    assert rec.status == "done"
    assert rec.preemptions == 1 and rec.episodes == 2
    assert rec.preempt_exits == [EXIT_PREEMPTED]   # cooperative 75
    events = read_fleet_events(fleet_dir)
    names = [e["event"] for e in events]
    assert "fleet.preempt" in names    # autoscale leased by preempting
    assert "fleet.resume" in names     # training got its devices back
    preempt = [e for e in events if e["event"] == "fleet.preempt"][0]
    assert preempt["job"] == "train-lowpri"
    assert preempt["victim_of"].startswith("replica-")
    # scale-down is the graceful queue-sentinel drain, not a SIGTERM: the
    # drained replica finishes its queue and COMPLETES, releasing the
    # lease — and only then can training (min 4 devices) resume.  The
    # event order is the lease-release witness.
    first_replica_done = next(i for i, e in enumerate(events)
                              if e["event"] == "fleet.complete"
                              and e["job"].startswith("replica-"))
    train_resume = next(i for i, e in enumerate(events)
                        if e["event"] == "fleet.resume"
                        and e["job"] == "train-lowpri")
    assert first_replica_done < train_resume
    # all three replica jobs ended clean — drained, never preempted
    for jid in pool.replicas:
        r = read_record(fleet_dir, jid)
        assert r.status == "done" and r.preemptions == 0, jid

    # -- numerics: bit-equal to the uncontended run ---------------------------
    # same mesh4 before and after the preemption, every-iter cadence
    # saves: the resumed trajectory must be EXACTLY the uncontended one —
    # the trace gap-free and the final params bit-identical
    ck_ref = str(tmp_path / "ck_ref")
    ref_trace = str(tmp_path / "trace_ref")
    monkeypatch.setenv("THEANOMPI_DATA_TRACE", ref_trace)
    _bsp(4, ck_ref, n_epochs=3, model_over={"n_train": 64},
         checkpoint_every_n_iters=1, checkpoint_async=False).wait()
    assert _trace(trace) == _trace(ref_trace)
    _assert_ckpt_equal(
        os.path.join(job_dir(fleet_dir, "train-lowpri"), "ckpt",
                     "ckpt_e0002.npz"),
        os.path.join(ck_ref, "ckpt_e0002.npz"))


def test_router_chaos_sigkill_replica_absorbed_exactly_once(tmp_path):
    """SIGKILL one of two replicas mid-traffic (satellite 3): the router
    marks it dead, redistributes its orphaned rids to the survivor, the
    floor backfill re-leases its chips to a fresh replica, and the
    REQUESTS.jsonl dedup keeps every terminal state exactly-once even
    though some rids were queued on two replicas across the kill."""
    fleet_dir = str(tmp_path / "fleet")
    sched = FleetScheduler(fleet_dir, 8, poll_s=0.01, telemetry=False)
    pool = ReplicaPool(sched, _replica_spec(ms_per_token=6))
    # min == max == 2: the policy's only job here is the floor backfill
    policy = AutoscalePolicy(AutoscaleConfig(
        min_replicas=2, max_replicas=2, up_pressure_s=10.0, up_after_s=5.0,
        down_pressure_s=0.01, down_after_s=30.0, cooldown_s=0.1))
    router = Router(pool, balancer=Balancer(), policy=policy,
                    default_rate=150.0)
    pool.spawn()
    pool.spawn()
    t, box = _run_fleet(sched)
    killed = []

    def chaos(router, now):
        # one kill, after the pool has demonstrably served something
        if killed or not router.results:
            return
        victim = router.pool.replicas[0]
        pid_file = os.path.join(router.pool.jdir(victim), "replica.pid")
        if not os.path.exists(pid_file):
            return
        os.kill(int(open(pid_file).read()), signal.SIGKILL)
        killed.append(victim)

    try:
        entries = synthetic_entries(20, vocab=256, prompt_len=4,
                                    max_new_tokens=40, rate=0.0, seed=0)
        results, wall = drive_traffic(router, entries, poll_s=0.01,
                                      timeout_s=120, between_ticks=chaos)
        router.drain_all()
    finally:
        t.join(120)
    assert not t.is_alive(), "fleet scheduler hung"
    assert killed, "chaos hook never fired"
    rep = router.report(wall_s=wall)
    assert rep["exactly_once"] is True
    assert rep["duplicates"] == 0
    assert set(results) == set(range(20))
    assert rep["replicas_dead"] == 1
    assert rep["redistributed"] > 0          # orphans moved, not lost
    assert rep["replicas_spawned"] >= 3      # the backfill replica
    assert rep["max_attempts"] >= 2          # some rid needed a 2nd home
    # the dead replica's job failed (SIGKILL, max_restarts=0) but the
    # fleet as a whole still drained; its lease was re-leased
    assert read_record(fleet_dir, killed[0]).status == "failed"
    events = read_fleet_events(fleet_dir)
    scheduled = [e["job"] for e in events if e["event"] == "fleet.schedule"]
    assert len(scheduled) >= 3


def test_router_two_replicas_beat_one_on_p99_ttft(tmp_path):
    """The capacity witness: the identical burst trace through 1 replica
    vs 2 — with queue wait dominating, the 2-replica p99 router-visible
    TTFT (queue wait + replica TTFT) must be strictly below the
    1-replica baseline."""
    def run(n_replicas, sub):
        sched = FleetScheduler(str(tmp_path / sub), 8, poll_s=0.01,
                               telemetry=False)
        pool = ReplicaPool(sched, _replica_spec(ms_per_token=3))
        router = Router(pool, balancer=Balancer(), policy=None,
                        default_rate=300.0)
        for _ in range(n_replicas):
            pool.spawn()
        t, box = _run_fleet(sched)
        try:
            entries = synthetic_entries(16, vocab=256, prompt_len=4,
                                        max_new_tokens=30, rate=0.0,
                                        seed=0)
            results, wall = drive_traffic(router, entries, poll_s=0.01,
                                          timeout_s=120)
            router.drain_all()
        finally:
            t.join(120)
        assert not t.is_alive() and box["rc"] == EXIT_CLEAN
        rep = router.report(wall_s=wall)
        assert rep["exactly_once"] is True
        return rep

    rep1 = run(1, "one")
    rep2 = run(2, "two")
    # same total work, so per-request outcomes are comparable
    assert rep1["generated_tokens"] == rep2["generated_tokens"]
    p99_1 = rep1["ttft_ms"]["p99"]
    p99_2 = rep2["ttft_ms"]["p99"]
    assert p99_2 < p99_1, (p99_1, p99_2)
    # and not marginally: the burst is ~16 serial generations, so two
    # replicas should roughly halve the tail wait
    assert p99_2 < 0.8 * p99_1, (p99_1, p99_2)
