"""ISSUE 14 serving resilience tier: deadlines, shedding, drain, rollout.

The contract under test:

- **typed terminal states** — every request ends in exactly one of
  ``done|expired|shed|failed``; deadlines are enforced at admission AND
  between scheduler steps, and a preempted-requeued request past its
  deadline expires WITHOUT burning a recompute-prefill;
- **load shedding** — with ``shed=True`` a deadline-carrying request the
  backlog provably cannot meet at the recent token rate is refused at
  admission (never mid-flight), deadline-less requests never shed;
- **livelock guard** — a request whose prefix can never fit the KV pool
  fails typed instead of crashing the server or preempting forever;
- **graceful drain** — on the drain trigger the loop stops admitting,
  finishes or expires in-flight within the budget, and a supervised
  replica's drained exit classifies CLEAN (subprocess e2e);
- **verified live rollout** — a half-published or corrupt candidate is
  refused (never quarantined) while the old weights keep serving; a good
  candidate hot-swaps with zero dropped requests; a critical SLO verdict
  during probation rolls back to the previous weights.

Units run against a host-only fake engine (no XLA compile); the drain
e2e drives the real ``tmserve --supervise`` as a subprocess; the full
chaos drive (crash-restart + corrupt-then-good rollout + forced
rollback) is tier-2 (``-m slow``).
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

from theanompi_tpu.resilience.faults import FaultInjected, FaultPlan
from theanompi_tpu.serving import (
    Request,
    RequestLog,
    RolloutManager,
    Scheduler,
    TERMINAL_STATES,
    blocks_for,
    newest_manifest_epoch,
    run_open_loop,
    serve_report,
    terminal_rids,
)

from conftest import SERVING_TINY as TINY  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeEngine:
    """Host-only engine double: the scheduler's surface (pool geometry +
    prefill/decode) with no XLA behind it — lifecycle units stay
    compile-free.  Emits a fixed token so nothing ever hits EOS."""

    def __init__(self, max_batch=2, block_size=4, num_blocks=9,
                 max_context=64):
        self.max_batch = max_batch
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.max_context = max_context
        self.max_blocks_per_seq = blocks_for(max_context, block_size)
        self.n_prefills = 0
        self.n_decodes = 0
        self.quant_stats = None
        self.decode_impl = "fallback"

    @property
    def quantized(self):
        return False

    def prefill(self, row, tokens, temperature=0.0, rid=0, prefix_len=0):
        self.n_prefills += 1
        return 7, None

    def decode(self, tables, lengths, tokens, temps, rids):
        self.n_decodes += 1
        return np.full((self.max_batch,), 5, np.int32), None


def _req(rid, prompt_len=4, new=8, **kw):
    return Request(rid=rid, prompt=[1] * prompt_len, max_new_tokens=new,
                   **kw)


# -- deadlines ----------------------------------------------------------------

def test_deadline_expiry_queued_and_active():
    """Between-steps enforcement: an overrun ACTIVE request evicts and
    expires (its blocks free immediately), an overrun QUEUED one never
    prefills; both carry the typed state + reason."""
    sched = Scheduler(FakeEngine(max_batch=1, num_blocks=20))
    active = _req(0, new=50, total_deadline_ms=10_000.0)
    queued = _req(1, new=50, total_deadline_ms=10_000.0)
    assert sched.submit(active) and sched.submit(queued)
    out = sched.step()  # admits rid 0 (1 slot), rid 1 stays queued
    assert not out and sched.n_active == 1 and len(sched.queue) == 1
    free_before = sched.pool.free_blocks
    # blow both deadlines between steps
    active.t_submit -= 11.0
    queued.t_submit -= 11.0
    prefills = sched.engine.n_prefills
    out = sched.step()
    assert {r.rid for r in out} == {0, 1}
    assert all(r.state == "expired" and r.terminal for r in out)
    assert {r.reason for r in out} == {
        "total deadline exceeded (active)",
        "total deadline exceeded (queued)"}
    assert sched.engine.n_prefills == prefills, \
        "an expired queued request burned a prefill"
    assert sched.pool.free_blocks > free_before, \
        "the expired active request did not free its blocks"
    assert sched.n_expired == 2 and sched.idle


def test_ttft_deadline_only_applies_before_first_token():
    """A request past its TTFT deadline but already emitting tokens keeps
    going — TTFT is a first-token promise, not a lifetime."""
    sched = Scheduler(FakeEngine(max_batch=1, num_blocks=20))
    req = _req(0, new=6, ttft_deadline_ms=10_000.0)
    sched.submit(req)
    sched.step()  # prefill -> first token exists
    assert req.t_first_token is not None
    req.t_submit -= 60.0  # way past the TTFT deadline
    while not req.terminal:
        sched.step()
    assert req.state == "done" and len(req.generated) == 6


def test_preempted_requeued_past_deadline_expires_without_prefill():
    """ISSUE 14 satellite: preemption requeues to the queue FRONT, so the
    admission path must deadline-check BEFORE prefilling — the expired
    request costs nothing on its way out."""
    sched = Scheduler(FakeEngine(max_batch=2, num_blocks=30))
    victim = _req(0, new=20, total_deadline_ms=10_000.0)
    sched.submit(victim)
    sched.step()
    assert victim.state == "active"
    assert sched.preempt_all() == 1
    assert victim.state == "queued" and victim.n_preemptions == 1
    victim.t_submit -= 11.0  # the deadline passed while it waited
    prefills = sched.engine.n_prefills
    finished = []
    sched._admit(finished)  # the exact front-of-queue guard
    assert finished == [victim] and victim.state == "expired"
    assert "queued" in victim.reason
    assert sched.engine.n_prefills == prefills, \
        "a dead-on-arrival requeue burned a recompute-prefill"


# -- load shedding ------------------------------------------------------------

def test_load_shedding_refuses_hopeless_deadline_requests():
    sched = Scheduler(FakeEngine(max_batch=1, num_blocks=30), shed=True)
    # before any rate evidence exists, shedding never fires
    early = _req(0, new=8, total_deadline_ms=1.0)
    assert sched.submit(early) is True
    # measured rate: 4 steps, 1 token each, 0.3 s span -> ~13 tok/s
    sched._rate.extend([(0.0, 1), (0.1, 1), (0.2, 1), (0.3, 1)])
    assert 10 < sched.recent_token_rate() < 20
    # backlog of 8 owed tokens needs ~600ms at that rate: a 50ms-deadline
    # arrival is hopeless and sheds AT ADMISSION (never queued)
    doomed = _req(1, new=8, total_deadline_ms=50.0)
    assert sched.submit(doomed) is False
    assert doomed.state == "shed" and doomed.terminal
    assert "backlog" in doomed.reason
    assert sched.n_shed == 1 and len(sched.queue) == 1
    # deadline-less requests are NEVER shed, whatever the backlog
    free_rider = _req(2, new=8)
    assert sched.submit(free_rider) is True
    # a generous deadline clears the estimate and admits
    patient = _req(3, new=8, total_deadline_ms=60_000.0)
    assert sched.submit(patient) is True


# -- livelock guard -----------------------------------------------------------

def test_livelock_guard_fails_impossible_prefix_and_keeps_serving():
    """A preempted request whose prompt+generated prefix outgrew the whole
    pool can never re-admit: pre-ISSUE-14 this raised out of the serve
    loop (killing every other request); now it FAILS typed and the rest
    of the traffic completes."""
    eng = FakeEngine(max_batch=2, block_size=4, num_blocks=5, max_context=64)
    sched = Scheduler(eng)
    # passes submit() (4+8=12 tokens -> 3 blocks <= 4 usable), then the
    # prefix grows past the pool, as preemption + generation can make it
    doomed = _req(0, prompt_len=4, new=8)
    doomed.generated = [1] * 13  # prefix 17 tokens -> 5 blocks > 4 usable
    survivor = _req(1, prompt_len=4, new=4)
    results, _ = run_open_loop(sched, [doomed, survivor])
    assert results[0].state == "failed" and results[0].terminal
    assert "can never be admitted" in results[0].reason
    assert results[1].state == "done" and len(results[1].generated) == 4
    assert sched.n_failed == 1
    rep = serve_report(results, 1.0, sched)
    assert rep["terminal_states"]["failed"] == 1
    assert rep["terminal_states"]["done"] == 1


# -- graceful drain (in-process) ----------------------------------------------

def test_drain_sheds_queued_finishes_active_in_process():
    eng = FakeEngine(max_batch=2, num_blocks=40)
    sched = Scheduler(eng)
    reqs = [_req(i, new=12) for i in range(6)]
    drain = lambda: sched.n_steps >= 2  # noqa: E731 — trip mid-drive
    results, _ = run_open_loop(sched, reqs, drain=drain, drain_s=30.0)
    assert len(results) == 6, "a request was lost in the drain"
    states = {rid: r.state for rid, r in results.items()}
    assert set(states.values()) <= set(TERMINAL_STATES)
    done = [r for r in results.values() if r.state == "done"]
    shed = [r for r in results.values() if r.state == "shed"]
    assert len(done) == 2, "the in-flight pair should finish inside drain_s"
    assert all(len(r.generated) == 12 for r in done)
    assert len(shed) == 4 and all(r.reason == "draining" for r in shed)
    assert sched.draining
    assert serve_report(results, 1.0, sched)["drained"] is True
    # once draining, submit() sheds on arrival
    late = _req(9, new=4)
    assert sched.submit(late) is False and late.state == "shed"


def test_drain_deadline_force_expires_stragglers():
    eng = FakeEngine(max_batch=2, num_blocks=40)
    sched = Scheduler(eng)
    reqs = [_req(i, new=50) for i in range(2)]  # outlive a zero budget
    results, _ = run_open_loop(
        sched, reqs, drain=lambda: sched.n_steps >= 1, drain_s=0.0)
    assert len(results) == 2
    assert all(r.state == "expired" for r in results.values())
    assert all("drain deadline" in r.reason for r in results.values())


# -- chaos sites in the scheduler --------------------------------------------

def test_serve_raise_and_stall_faults(monkeypatch):
    plan = FaultPlan.parse("serve:raise@1")
    sched = Scheduler(FakeEngine(max_batch=1, num_blocks=20),
                      fault_plan=plan)
    sched.submit(_req(0, new=30))
    sched.step()  # decode step 0: below the ordinal
    with pytest.raises(FaultInjected, match="decode step 1"):
        sched.step()

    monkeypatch.setenv("THEANOMPI_SERVE_STALL_S", "0.15")
    sched2 = Scheduler(FakeEngine(max_batch=1, num_blocks=20),
                       fault_plan=FaultPlan.parse("serve:stall@0"))
    sched2.submit(_req(1, new=4))
    t0 = time.perf_counter()
    sched2.step()
    assert time.perf_counter() - t0 >= 0.15
    t0 = time.perf_counter()
    sched2.step()  # one-shot: fired specs never re-trigger
    assert time.perf_counter() - t0 < 0.1


# -- request log --------------------------------------------------------------

def test_request_log_roundtrip_tolerates_torn_tail(tmp_path):
    path = str(tmp_path / "REQUESTS.jsonl")
    assert terminal_rids(path) == set()  # no file yet: nothing answered
    log = RequestLog(path, attempt=1)
    done = _req(3, new=2)
    done.state, done.generated = "done", [5, 5]
    shed = _req(7, new=2)
    shed.state, shed.reason = "shed", "draining"
    log.record(done)
    log.record(shed)
    log.close()
    with open(path, "a") as f:
        f.write('{"rid": 9, "state": "do')  # the SIGKILL-torn tail
    assert terminal_rids(path) == {3, 7}
    recs = [json.loads(l) for l in open(path) if l.strip().endswith("}")]
    assert recs[0] == {"rid": 3, "state": "done", "reason": None,
                      "n_generated": 2, "attempt": 1}
    assert recs[1]["reason"] == "draining"


# -- rollout watcher ----------------------------------------------------------

def _publish(ckpt, model, params, epoch, shift=0.0):
    """One verified checkpoint publish, the training writer's way."""
    from theanompi_tpu.utils.checkpoint import Checkpointer, model_fingerprint

    writer = Checkpointer(ckpt, fingerprint={
        "mesh": {"data": 8}, "exchange": "psum", "n_subb": 1,
        **model_fingerprint(model)})
    trees = {"params": jax.tree.map(
        lambda a: np.asarray(a) + shift, params)}
    writer.save(epoch, 10 * (epoch + 1), trees).join()
    writer.mark_clean()
    return trees


class _SchedStub:
    """preempt_all() is the rollout barrier; count the calls."""

    def __init__(self):
        self.n_preempt_calls = 0

    def preempt_all(self):
        self.n_preempt_calls += 1
        return 2


def _manager(engine, ckpt, model, params, **kw):
    kw.setdefault("poll_s", 0.0)
    return RolloutManager(engine, ckpt, {"params": params}, model=model,
                          current_epoch=0, **kw)


def test_rollout_tolerates_half_published_then_adopts(
        dense_model, serving_engine_factory, tmp_path):
    """ISSUE 14 satellite: a manifest whose .npz is mid-replace (or still
    missing) is 'not yet published' — refused, NEVER quarantined, and the
    very same epoch adopts once its bytes verify."""
    model, params, _ = dense_model
    ckpt = str(tmp_path / "ckpt")
    _publish(ckpt, model, params, 0)
    # private engine: rollouts swap weights, the shared one is read-only
    engine = serving_engine_factory(shared=False)
    mgr = _manager(engine, ckpt, model, params)
    sched = _SchedStub()
    assert newest_manifest_epoch(ckpt) == 0
    assert mgr.poll(sched) is None  # nothing newer than what's serving

    # half-published epoch 1: manifest visible, npz bytes still the
    # writer's in-flight garbage (the torn-publish race at serving's edge)
    man = os.path.join(ckpt, "ckpt_e0001.manifest.json")
    npz = os.path.join(ckpt, "ckpt_e0001.npz")
    open(man, "w").write(open(
        os.path.join(ckpt, "ckpt_e0000.manifest.json")).read())
    open(npz, "wb").write(b"PK-but-not-really")
    assert mgr.poll(sched) == "refused"
    assert mgr.poll(sched) == "refused"  # re-polls, still patient
    assert mgr.n_refused == 1            # but one event per candidate
    assert mgr.current_epoch == 0 and sched.n_preempt_calls == 0
    # never quarantined, never deleted: the live writer still owns these
    assert os.path.exists(man) and os.path.exists(npz)
    assert not os.path.exists(os.path.join(ckpt, "corrupt"))

    # the writer finishes the publish -> the SAME epoch now adopts
    os.remove(man)
    os.remove(npz)
    p1 = _publish(ckpt, model, params, 1, shift=1.0)
    assert mgr.poll(sched) == "rollout"
    assert mgr.current_epoch == 1 and mgr.n_rollouts == 1
    assert sched.n_preempt_calls == 1, "adopt must preempt before swapping"
    np.testing.assert_array_equal(
        np.asarray(engine.params["head"]["w"]),
        np.asarray(p1["params"]["head"]["w"]))


def test_rollout_corrupt_fault_refused_old_weights_keep_serving(
        dense_model, serving_engine_factory, tmp_path):
    """serve:rollout_corrupt@0 bit-flips the FIRST candidate before
    verification: it must be refused with the old weights intact, and the
    next (ordinal 1) candidate adopts untouched."""
    model, params, _ = dense_model
    ckpt = str(tmp_path / "ckpt")
    _publish(ckpt, model, params, 0)
    engine = serving_engine_factory(shared=False)
    w0 = np.asarray(engine.params["head"]["w"]).copy()
    mgr = _manager(engine, ckpt, model, params,
                   fault_plan=FaultPlan.parse("serve:rollout_corrupt@0"))
    sched = _SchedStub()
    _publish(ckpt, model, params, 1, shift=1.0)
    assert mgr.poll(sched) == "refused"  # the fault ate candidate 0
    np.testing.assert_array_equal(
        np.asarray(engine.params["head"]["w"]), w0)
    assert os.path.exists(os.path.join(ckpt, "ckpt_e0001.npz"))
    assert not os.path.exists(os.path.join(ckpt, "corrupt"))
    p2 = _publish(ckpt, model, params, 2, shift=2.0)
    assert mgr.poll(sched) == "rollout"  # ordinal 1: no spec, clean adopt
    assert mgr.current_epoch == 2 and mgr.n_refused == 1
    np.testing.assert_array_equal(
        np.asarray(engine.params["head"]["w"]),
        np.asarray(p2["params"]["head"]["w"]))


def test_rollout_probation_rollback_and_commit(
        dense_model, serving_engine_factory, tmp_path):
    """A critical SLO verdict inside the probation window rolls back to
    the previous weights and blacklists the epoch; a quiet probation
    commits, after which verdicts no longer matter."""
    model, params, _ = dense_model
    ckpt = str(tmp_path / "ckpt")
    _publish(ckpt, model, params, 0)
    engine = serving_engine_factory(shared=False)
    w0 = np.asarray(engine.params["head"]["w"]).copy()
    t = [0.0]
    verdicts = []
    mgr = _manager(engine, ckpt, model, params, probation_s=100.0,
                   health_verdicts=lambda: verdicts, clock=lambda: t[0])
    sched = _SchedStub()

    _publish(ckpt, model, params, 1, shift=1.0)
    t[0] = 1.0
    assert mgr.poll(sched) == "rollout" and mgr.current_epoch == 1
    # a WARN verdict is not enough; an unrelated detector is not enough
    verdicts[:] = [{"detector": "slo", "severity": "warn"},
                   {"detector": "loss", "severity": "critical"}]
    t[0] = 2.0
    assert mgr.poll(sched) != "rollback"
    # critical SLO inside probation -> roll back, blacklist epoch 1
    verdicts[:] = [{"detector": "slo", "severity": "critical",
                    "reason": "ttft p99 blew the SLO"}]
    t[0] = 3.0
    assert mgr.poll(sched) == "rollback"
    assert mgr.current_epoch == 0 and mgr.n_rollbacks == 1
    assert sched.n_preempt_calls == 2  # once on adopt, once on rollback
    np.testing.assert_array_equal(
        np.asarray(engine.params["head"]["w"]), w0)
    t[0] = 4.0
    assert mgr.poll(sched) is None, "a rolled-back epoch was re-adopted"

    # a NEW epoch adopts, survives probation quietly, and commits
    verdicts[:] = []
    p2 = _publish(ckpt, model, params, 2, shift=2.0)
    t[0] = 5.0
    assert mgr.poll(sched) == "rollout" and mgr.current_epoch == 2
    t[0] = 200.0  # past the probation window
    assert mgr.poll(sched) is None
    verdicts[:] = [{"detector": "throughput", "severity": "critical"}]
    t[0] = 201.0
    assert mgr.poll(sched) != "rollback", "probation already committed"
    assert mgr.current_epoch == 2
    np.testing.assert_array_equal(
        np.asarray(engine.params["head"]["w"]),
        np.asarray(p2["params"]["head"]["w"]))


# -- graceful drain under load: the supervised subprocess e2e ----------------

TMSERVE_TINY = [
    "--modelclass", "TransformerLM",
    "--set", "dim=32", "--set", "heads=2", "--set", "n_layers=1",
    "--set", "seq_len=32", "--set", "vocab=61", "--set", "dropout=0.0",
    "--set", "precision=fp32", "--set", "n_train=64", "--set", "n_val=32",
    "--max-batch", "2", "--block-size", "4", "--prompt-len", "4",
]


def _child_env(cache, **extra):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
                "JAX_THREEFRY_PARTITIONABLE": "true",
                "JAX_COMPILATION_CACHE_DIR": cache,
                "PYTHONPATH": REPO})
    env.pop("THEANOMPI_FAULT_PLAN", None)
    env.update(extra)
    return env


def _wait_for(path, deadline_s, proc=None):
    deadline = time.perf_counter() + deadline_s
    while time.perf_counter() < deadline:
        if os.path.exists(path):
            return True
        if proc is not None and proc.poll() is not None:
            return False
        time.sleep(0.05)
    return False


@pytest.mark.faultinject
def test_graceful_drain_under_load_supervised_classifies_clean(
        tmp_path, subproc_compile_cache):
    """ISSUE 14 satellite e2e: tmserve --supervise with 8 burst requests
    in flight takes a SIGTERM, every request reaches a terminal state
    within --drain-s, none is lost, the replica exits 0 and the
    supervisor classifies the episode CLEAN (no restart burned)."""
    tel = str(tmp_path / "tel")
    # serve:stall@1 holds decode step 1 for 8s — a deterministic window
    # where all 8 requests are in flight (none can have finished: a
    # completion needs >= 15 decode steps), however fast the compile was.
    # stderr goes to a FILE: the wait below polls without reading, and a
    # replica that fills an unread pipe blocks before its serve loop
    err_path = tmp_path / "child.err"
    with open(err_path, "w") as err_f:
        child = subprocess.Popen(
            [sys.executable, "-m", "theanompi_tpu.serving", *TMSERVE_TINY,
             "--requests", "8", "--max-new-tokens", "16",
             "--drain-s", "30", "--telemetry-dir", tel, "--quiet",
             "--supervise", "--max-restarts", "2", "--backoff-base", "0.1"],
            env=_child_env(subproc_compile_cache,
                           THEANOMPI_FAULT_PLAN="serve:stall@1",
                           THEANOMPI_SERVE_STALL_S="8"),
            cwd=REPO, stdout=subprocess.DEVNULL, stderr=err_f, text=True)
        try:
            log = os.path.join(tel, "REQUESTS.jsonl")
            assert _wait_for(log, 240, child), (
                f"replica never reached the serve loop: "
                f"{err_path.read_text()[-2000:]}")
            time.sleep(1.0)  # into the loop (handler installed, stall armed)
            child.send_signal(signal.SIGTERM)  # supervisor forwards it
            child.wait(timeout=240)
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
    err = err_path.read_text()
    assert child.returncode == 0, f"drained exit was not clean:\n{err}"
    recs = [json.loads(l) for l in open(log) if l.strip()]
    assert sorted(r["rid"] for r in recs) == list(range(8)), \
        "a request was lost in the drain"
    assert {r["state"] for r in recs} <= set(TERMINAL_STATES)
    assert any(r["state"] == "shed" for r in recs), \
        "SIGTERM landed with nothing queued — the window logic broke"
    # the supervisor saw exit 0 after its SIGTERM forward: CLEAN, one
    # attempt, nothing restarted
    art = json.load(open(os.path.join(tel, "resilience.json")))
    assert [a["cause"] for a in art["attempts"]] == ["clean"]
    assert art["final_exit"] == 0


# -- the chaos acceptance drive (tier-2) --------------------------------------

@pytest.mark.slow
@pytest.mark.faultinject
def test_chaos_crash_restart_and_corrupt_then_good_rollout_with_rollback(
        tmp_path, subproc_compile_cache):
    """THE acceptance e2e: a 24-request supervised drive survives a
    serve:raise crash-restart AND a corrupt-then-good rollout published
    mid-drive — zero requests lost across attempts, the corrupt candidate
    refused with the old weights still serving, the good one swapped in
    (rollout event), and a forced SLO-critical probation auto-rolls back."""
    from theanompi_tpu.launcher import _parse_kv
    from theanompi_tpu.models.transformer_lm import TransformerLM

    tiny = _parse_kv([a for a in TMSERVE_TINY if "=" in a])
    model = TransformerLM(tiny)
    params, _ = model.init_params(jax.random.PRNGKey(0))
    ckpt = str(tmp_path / "ckpt")
    _publish(ckpt, model, params, 0)
    tel = str(tmp_path / "tel")
    out_json = str(tmp_path / "SERVE.json")

    child = subprocess.Popen(
        [sys.executable, "-m", "theanompi_tpu.serving", *TMSERVE_TINY,
         "--requests", "24", "--max-new-tokens", "16",
         "--arrival-rate", "2",  # ~12s of arrivals: a real mid-drive window
         "--checkpoint-dir", ckpt, "--rollout-watch",
         "--rollout-poll-s", "0.1", "--rollout-probation-s", "60",
         "--slo-ttft-ms", "0.001",  # every real TTFT is SLO-critical
         "--telemetry-dir", tel, "--out", out_json, "--quiet",
         "--supervise", "--max-restarts", "3", "--backoff-base", "0.1"],
        env=_child_env(subproc_compile_cache,
                       # crash attempt 1 at decode step 20 — past the first
                       # request's ~15 completion steps (so BOTH attempts
                       # have terminal records), attempt-gated so attempt 2
                       # rides through
                       THEANOMPI_FAULT_PLAN="serve:raise@20@1"),
        cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    try:
        log = os.path.join(tel, "REQUESTS.jsonl")
        assert _wait_for(log, 300, child), \
            f"replica never reached the serve loop: {child.communicate()}"
        # wait until attempt 2 is live (its records carry attempt: 2) —
        # the crash itself happened at decode step 6 of attempt 1
        deadline = time.perf_counter() + 300
        while time.perf_counter() < deadline:
            recs = [json.loads(l) for l in open(log)
                    if l.strip().endswith("}")]
            if any(r["attempt"] >= 2 for r in recs):
                break
            assert child.poll() is None, \
                f"supervisor died early: {child.communicate()}"
            time.sleep(0.1)
        else:
            pytest.fail("attempt 2 never produced a terminal request")
        # corrupt-then-good, published mid-drive by the training writer:
        # epoch 1's npz is garbage under a visible manifest (refused, old
        # weights keep serving), epoch 2 is the real thing (adopted)
        open(os.path.join(ckpt, "ckpt_e0001.manifest.json"), "w").write(
            open(os.path.join(ckpt, "ckpt_e0000.manifest.json")).read())
        open(os.path.join(ckpt, "ckpt_e0001.npz"), "wb").write(b"garbage")
        time.sleep(2.0)  # >= 20 watcher polls on the corrupt candidate
        _publish(ckpt, model, params, 2, shift=1.0)
        out, err = child.communicate(timeout=300)
    finally:
        if child.poll() is None:
            child.kill()
            child.communicate()
    assert child.returncode == 0, f"chaos drive did not end clean:\n{err}"

    # zero requests lost: every id reached exactly one terminal state
    recs = [json.loads(l) for l in open(log) if l.strip()]
    assert sorted(r["rid"] for r in recs) == list(range(24))
    assert {r["state"] for r in recs} <= set(TERMINAL_STATES)
    assert {r["attempt"] for r in recs} == {1, 2}, \
        "both attempts must have served requests"

    # supervisor audit: one crash (the injected raise), then clean
    art = json.load(open(os.path.join(tel, "resilience.json")))
    assert [a["cause"] for a in art["attempts"]] == ["crash", "clean"]

    # rollout audit: corrupt refused, good adopted, probation rolled back
    rep = json.load(open(out_json))
    assert rep["rollout"]["refused"] >= 1, "the corrupt candidate slipped by"
    assert rep["rollout"]["rollouts"] == 1
    assert rep["rollout"]["rollbacks"] == 1, \
        "the SLO-critical probation did not roll back"
    assert rep["rollout"]["serving_epoch"] == 0  # back on the old weights
    assert rep["attempt"] == 2
    # refused-never-quarantined, even from a subprocess
    assert os.path.exists(os.path.join(ckpt, "ckpt_e0001.npz"))
    assert not os.path.exists(os.path.join(ckpt, "corrupt"))
