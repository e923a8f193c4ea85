"""Resilience layer (ISSUE 4): fault plan, supervisor, watchdog, sentinel,
prefetch stall, launcher exit-code contract, dist-init retry.

Subprocess-based supervisor units use plain ``python -c`` children (no jax
import) so they run in milliseconds; the e2e supervised-training paths live
in ``tests/test_resilience_e2e.py``.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from theanompi_tpu.resilience import (
    EXIT_CONFIG,
    EXIT_CRASH,
    EXIT_HANG,
    EXIT_PREEMPTED,
    FaultPlan,
    FaultPlanError,
    NonFiniteLossError,
    Sentinel,
    Supervisor,
    Watchdog,
    classify_exit,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = ["--set", "depth=10", "--set", "widen=1", "--set", "batch_size=4",
        "--set", "image_size=8", "--set", "n_train=32", "--set", "n_val=16",
        "--set", "precision='fp32'"]


# -- faults.py ---------------------------------------------------------------

def test_fault_plan_grammar():
    p = FaultPlan.parse("step:kill@12@1; prefetch:stall@3, checkpoint:fail@0")
    assert [(s.site, s.action, s.index, s.attempt) for s in p.specs] == [
        ("step", "kill", 12, 1), ("prefetch", "stall", 3, None),
        ("checkpoint", "fail", 0, None)]


@pytest.mark.parametrize("bad", [
    "nosite:raise@1",        # unknown site
    "step:stall@1",          # action invalid for site
    "step:raise",            # missing index
    "step:raise@x",          # non-integer index
    "step:raise@1@2@3",      # too many @
    "",                      # empty
])
def test_fault_plan_rejects(bad):
    with pytest.raises(FaultPlanError):
        FaultPlan.parse(bad)


def test_fault_plan_one_shot_and_attempt_gate(monkeypatch):
    p = FaultPlan.parse("step:raise@5@2")
    monkeypatch.setenv("THEANOMPI_ATTEMPT", "1")
    assert p.fire("step", 5) is None          # wrong attempt
    monkeypatch.setenv("THEANOMPI_ATTEMPT", "2")
    assert p.fire("step", 4) is None          # wrong index
    assert p.fire("prefetch", 5) is None      # wrong site
    assert p.fire("step", 5) == "raise"
    assert p.fire("step", 5) is None          # one-shot: never twice


def test_fault_plan_env_fallback(monkeypatch):
    monkeypatch.delenv("THEANOMPI_FAULT_PLAN", raising=False)
    assert FaultPlan.from_spec(None) is None
    monkeypatch.setenv("THEANOMPI_FAULT_PLAN", "step:nan@3")
    plan = FaultPlan.from_spec(None)
    assert plan.fire("step", 3) == "nan"
    # explicit spec beats env
    assert FaultPlan.from_spec("step:kill@1").specs[0].action == "kill"


# -- exit classification -----------------------------------------------------

def test_classify_exit_table():
    import signal

    assert classify_exit(0) == "clean"
    assert classify_exit(EXIT_PREEMPTED) == "preemption"
    assert classify_exit(-signal.SIGTERM) == "preemption"
    assert classify_exit(EXIT_HANG) == "hang"
    assert classify_exit(EXIT_CONFIG) == "config"
    assert classify_exit(2) == "config"      # argparse usage error
    assert classify_exit(EXIT_CRASH) == "crash"
    assert classify_exit(-9) == "crash"      # SIGKILL
    assert classify_exit(1) == "crash"


# -- supervisor.py (python -c children: no jax, milliseconds) ---------------

def _script_child(tmp_path, body: str) -> list:
    """A child command running ``body`` with a state dir for cross-attempt
    counters (the supervisor restarts fresh processes)."""
    return [sys.executable, "-c", body.replace("STATE", repr(str(tmp_path)))]


def test_supervisor_restarts_crash_then_clean(tmp_path):
    body = """
import os, sys
marker = os.path.join(STATE, "crashed_once")
if not os.path.exists(marker):
    open(marker, "w").close()
    sys.exit(70)
sys.exit(0)
"""
    sleeps = []
    sup = Supervisor(_script_child(tmp_path, body), max_restarts=3,
                     backoff_base=0.01, jitter=0.0,
                     resilience_path=str(tmp_path / "resilience.json"),
                     sleep=sleeps.append)
    assert sup.run() == 0
    art = json.load(open(tmp_path / "resilience.json"))
    assert [a["cause"] for a in art["attempts"]] == ["crash", "clean"]
    assert art["restarts"] == 1 and art["final_exit"] == 0
    assert art["attempts"][0]["exit_code"] == 70
    assert art["attempts"][0]["time_lost_s"] >= 0
    assert len(sleeps) == 1  # one backoff before the restart


def test_supervisor_resume_args_added_from_second_attempt(tmp_path):
    body = """
import os, sys
marker = os.path.join(STATE, "n")
n = int(open(marker).read()) if os.path.exists(marker) else 0
open(marker, "w").write(str(n + 1))
if "--resume" in sys.argv:
    sys.exit(0 if n == 1 else 71)   # resume must arrive exactly at attempt 2
sys.exit(70 if n == 0 else 71)
"""
    sup = Supervisor(_script_child(tmp_path, body), max_restarts=2,
                     backoff_base=0.0, jitter=0.0,
                     resilience_path=str(tmp_path / "r.json"),
                     sleep=lambda s: None)
    assert sup.run() == 0


def test_supervisor_config_error_is_fatal(tmp_path):
    sup = Supervisor([sys.executable, "-c", f"import sys; sys.exit({EXIT_CONFIG})"],
                     max_restarts=5, backoff_base=0.0, jitter=0.0,
                     resilience_path=str(tmp_path / "r.json"),
                     sleep=lambda s: None)
    assert sup.run() == EXIT_CONFIG
    art = json.load(open(tmp_path / "r.json"))
    assert [a["cause"] for a in art["attempts"]] == ["config"]
    assert art["restarts"] == 0


def test_supervisor_budget_exhausted(tmp_path):
    sup = Supervisor([sys.executable, "-c", "import sys; sys.exit(70)"],
                     max_restarts=2, backoff_base=0.0, jitter=0.0,
                     resilience_path=str(tmp_path / "r.json"),
                     sleep=lambda s: None)
    assert sup.run() == 70
    art = json.load(open(tmp_path / "r.json"))
    assert len(art["attempts"]) == 3  # initial + 2 restarts
    assert art["restarts"] == 3


def test_supervisor_preemption_does_not_burn_budget(tmp_path):
    body = """
import os, sys
marker = os.path.join(STATE, "n")
n = int(open(marker).read()) if os.path.exists(marker) else 0
open(marker, "w").write(str(n + 1))
sys.exit(75 if n < 2 else 0)
"""
    sup = Supervisor(_script_child(tmp_path, body), max_restarts=0,
                     backoff_base=0.0, jitter=0.0,
                     resilience_path=str(tmp_path / "r.json"),
                     sleep=lambda s: None)
    assert sup.run() == 0  # two preemptions survived with a ZERO restart budget
    art = json.load(open(tmp_path / "r.json"))
    assert [a["cause"] for a in art["attempts"]] == [
        "preemption", "preemption", "clean"]
    assert art["restarts"] == 0 and art["preemptions"] == 2
    assert art["time_lost_s"] == 0  # preemptions are resumable, not lost


def test_supervisor_config_on_restart_is_retried(tmp_path):
    """A config exit on attempt 1 is fatal (wrong flags stay wrong); the
    SAME exit on a restart is suspect — attempt 1 got past init, so it is
    more likely environmental fallout of the previous death (a lazily
    released accelerator lock) and must burn budget, not end the run."""
    body = """
import os, sys
marker = os.path.join(STATE, "n")
n = int(open(marker).read()) if os.path.exists(marker) else 0
open(marker, "w").write(str(n + 1))
sys.exit([70, 78, 0][n])
"""
    sup = Supervisor(_script_child(tmp_path, body), max_restarts=3,
                     backoff_base=0.0, jitter=0.0,
                     resilience_path=str(tmp_path / "r.json"),
                     sleep=lambda s: None)
    assert sup.run() == 0
    art = json.load(open(tmp_path / "r.json"))
    assert [a["cause"] for a in art["attempts"]] == [
        "crash", "crash(config-on-restart)", "clean"]
    assert art["restarts"] == 2


def test_supervisor_clamps_sub_heartbeat_hang_timeout(tmp_path):
    sup = Supervisor(["true"], hang_timeout_s=0.5,
                     resilience_path=str(tmp_path / "r.json"))
    assert sup.hang_timeout_s == 3.0  # below the heartbeat write interval
    sup = Supervisor(["true"], hang_timeout_s=600,
                     resilience_path=str(tmp_path / "r.json"))
    assert sup.hang_timeout_s == 600


def test_supervisor_backoff_is_exponential_and_jittered(tmp_path):
    sleeps = []
    sup = Supervisor([sys.executable, "-c", "import sys; sys.exit(1)"],
                     max_restarts=3, backoff_base=1.0, backoff_cap=60.0,
                     jitter=0.5, seed=7,
                     resilience_path=str(tmp_path / "r.json"),
                     sleep=sleeps.append)
    assert sup.run() == 1
    assert len(sleeps) == 3
    for i, s in enumerate(sleeps):
        base = 2.0 ** i
        assert base <= s <= base * 1.5, (i, s)


def test_supervisor_forwards_sigterm_and_stops(tmp_path):
    """A preempted VM TERMs the supervisor too: it must forward the signal
    to the child, let it take its resumable exit, and NOT restart."""
    import signal as _signal
    import threading

    body = ("import signal, sys, time;"
            "signal.signal(signal.SIGTERM, lambda *a: sys.exit(75));"
            "time.sleep(60)")
    sup = Supervisor([sys.executable, "-c", body], max_restarts=3,
                     backoff_base=0.0, jitter=0.0, poll_s=0.05,
                     resilience_path=str(tmp_path / "r.json"),
                     sleep=lambda s: None)
    killer = threading.Timer(
        1.0, lambda: os.kill(os.getpid(), _signal.SIGTERM))
    killer.start()
    try:
        rc = sup.run()
    finally:
        killer.cancel()
    assert rc == EXIT_PREEMPTED
    art = json.load(open(tmp_path / "r.json"))
    assert [a["cause"] for a in art["attempts"]] == ["preemption"]
    assert art["restarts"] == 0  # terminated supervisor never restarts


def test_supervisor_terminated_during_backoff_does_not_respawn(tmp_path):
    """SIGTERM landing BETWEEN attempts (mid-backoff, no child running)
    must end supervision — never spawn a fresh child into a dying VM."""
    import signal as _signal

    def term_during_backoff(delay):
        os.kill(os.getpid(), _signal.SIGTERM)  # handler runs on return

    sup = Supervisor([sys.executable, "-c", "import sys; sys.exit(70)"],
                     max_restarts=3, backoff_base=0.01, jitter=0.0,
                     resilience_path=str(tmp_path / "r.json"),
                     sleep=term_during_backoff)
    assert sup.run() == EXIT_PREEMPTED
    art = json.load(open(tmp_path / "r.json"))
    assert len(art["attempts"]) == 1  # the crash; no post-TERM respawn


def test_dist_init_address_in_use_is_not_double_init(monkeypatch):
    """grpc's 'Address already in use' (stale coordinator port) contains
    'already' but is a REAL failure: it must retry and then raise, not be
    mistaken for harmless double-init."""
    import jax

    from theanompi_tpu import launcher

    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "10.0.0.1:8476")

    def port_taken():
        raise RuntimeError("UNKNOWN: Address already in use")

    monkeypatch.setattr(jax.distributed, "initialize", port_taken)
    with pytest.raises(launcher.DistributedInitError):
        launcher._maybe_init_distributed(retries=2, backoff_base=0.0,
                                         sleep=lambda s: None)


def test_supervisor_hang_backstop_kills_silent_child(tmp_path):
    hb = str(tmp_path / "heartbeat.json")
    sup = Supervisor([sys.executable, "-c", "import time; time.sleep(60)"],
                     max_restarts=0, backoff_base=0.0, jitter=0.0,
                     hang_timeout_s=0.8, poll_s=0.05, heartbeat_path=hb,
                     resilience_path=str(tmp_path / "r.json"),
                     sleep=lambda s: None)
    t0 = time.perf_counter()
    rc = sup.run()
    assert time.perf_counter() - t0 < 30  # killed, not waited out
    assert rc == EXIT_CRASH
    art = json.load(open(tmp_path / "r.json"))
    assert art["attempts"][0]["cause"] == "hang"


# -- watchdog.py -------------------------------------------------------------

def test_watchdog_median_adaptive_trigger(tmp_path):
    clock = [0.0]
    exits = []
    wd = Watchdog(multiple=4.0, min_timeout_s=1.0, escalate="exit",
                  exit_code=EXIT_HANG, _exit=exits.append,
                  _clock=lambda: clock[0])
    # calibration: no trigger before 3 step durations exist
    for step in range(4):
        wd.beat(step)
        clock[0] += 0.5
    assert wd.stall_threshold_s() == pytest.approx(2.0)  # max(4*0.5, 1.0)
    last_beat = clock[0] - 0.5  # the loop advanced the clock past the beat
    assert not wd.check(now=last_beat + 1.9)
    assert wd.check(now=last_beat + 2.1)
    assert exits == [EXIT_HANG]
    assert wd.check(now=last_beat + 10)  # latched, no double escalation
    assert exits == [EXIT_HANG]


def test_watchdog_warn_mode_does_not_exit(capsys):
    clock = [0.0]
    wd = Watchdog(multiple=2.0, min_timeout_s=0.1, escalate="warn",
                  _exit=lambda code: pytest.fail("escalated in warn mode"),
                  _clock=lambda: clock[0])
    for step in range(4):
        wd.beat(step)
        clock[0] += 0.1
    assert wd.check(now=clock[0] + 5.0)
    assert "watchdog: no train-step progress" in capsys.readouterr().err


def test_watchdog_pause_covers_beatfree_boundaries():
    """Epoch-boundary work (eval compile, val sweep, checkpoint joins)
    produces no beats; pause() must suspend detection and resume() must
    not count the paused stretch as no-progress time."""
    clock = [0.0]
    exits = []
    wd = Watchdog(multiple=2.0, min_timeout_s=0.1, escalate="exit",
                  _exit=exits.append, _clock=lambda: clock[0])
    for step in range(4):
        wd.beat(step)
        clock[0] += 0.1
    wd.pause()
    assert not wd.check(now=clock[0] + 100)  # paused: a long boundary is fine
    clock[0] += 100
    wd.resume()
    assert not wd.check(now=clock[0] + 0.05)  # boundary time not counted
    assert wd.check(now=clock[0] + 5)  # a real post-boundary stall still fires
    assert exits == [76]


def test_launcher_rejects_abbreviated_flags():
    """allow_abbrev must stay off: '--superv' forwarded to a child would
    make the child a supervisor too (recursive spawning)."""
    from theanompi_tpu import launcher

    with pytest.raises(SystemExit):
        launcher.build_parser().parse_args(["--superv"])


def test_supervise_refuses_recursion(monkeypatch, capsys):
    from theanompi_tpu import launcher

    monkeypatch.setenv("THEANOMPI_SUPERVISED", "1")
    rc = launcher.main(["--supervise", "--rule", "BSP", "--devices", "4"])
    assert rc == EXIT_CONFIG
    assert "recursive supervision" in capsys.readouterr().err


def test_watchdog_needs_calibration():
    wd = Watchdog(multiple=2.0, min_timeout_s=0.0)
    wd.beat(0)
    wd.beat(1)
    assert wd.stall_threshold_s() is None  # < 3 durations: still calibrating
    assert not wd.check(now=1e9)


@pytest.mark.faultinject
def test_heartbeat_written_even_with_watchdog_disabled(tmp_path, monkeypatch):
    """watchdog=False turns off the stall DETECTOR, not liveness: the
    supervisor's --hang-timeout backstop reads the heartbeat file and
    would kill a healthy-but-silent child."""
    hb = str(tmp_path / "hb.json")
    monkeypatch.setenv("THEANOMPI_HEARTBEAT", hb)
    from theanompi_tpu import BSP

    rule = BSP(config={"verbose": False, "watchdog": False})
    rule.init(devices=1, modelfile="theanompi_tpu.models.wide_resnet",
              modelclass="WideResNet",
              model_config={"depth": 10, "widen": 1, "batch_size": 4,
                            "image_size": 8, "n_train": 16, "n_val": 8,
                            "n_epochs": 1, "precision": "fp32"})
    rule.wait()
    assert json.load(open(hb))["step"] == rule.trainer.iteration


def test_heartbeat_file_roundtrip(tmp_path):
    from theanompi_tpu.resilience import Heartbeat, heartbeat_age_s

    path = str(tmp_path / "hb.json")
    assert heartbeat_age_s(path) is None
    hb = Heartbeat(path, min_interval_s=0.0)
    hb.beat(41)
    hb.beat(42)
    meta = json.load(open(path))
    assert meta["step"] == 42 and meta["pid"] == os.getpid()
    assert heartbeat_age_s(path) < 10


# -- sentinel.py (host side, no jax needed) ---------------------------------

def test_sentinel_rejects_unknown_policy():
    with pytest.raises(ValueError, match="policy"):
        Sentinel(policy="explode")


def test_sentinel_abort_on_nonfinite():
    s = Sentinel(policy="abort")
    s.watch(3, np.float32(1.5))
    s.check()  # finite: fine
    s.watch(4, np.float32("nan"))
    with pytest.raises(NonFiniteLossError) as ei:
        s.check()
    assert ei.value.step == 4


def test_sentinel_skip_budget():
    s = Sentinel(policy="skip_batch", max_skips=2)
    for step in (1, 2):
        s.watch(step, np.float32("nan"), skip_flag=np.float32(1.0))
        s.check()  # within budget
    assert s.skips == 2
    s.watch(3, np.float32("nan"), skip_flag=np.float32(1.0))
    with pytest.raises(NonFiniteLossError, match="budget"):
        s.check()


def test_sentinel_rollback_raises_control_flow():
    from theanompi_tpu.resilience import SentinelRollback

    s = Sentinel(policy="rollback")
    s.watch(7, np.float32("inf"))
    with pytest.raises(SentinelRollback):
        s.check()
    s.watch(8, np.float32("nan"))
    s.reset_pending()
    s.check()  # a rollback dropped the dead timeline's pending losses


# -- prefetch stall + fault sites -------------------------------------------

def test_prefetch_stall_timeout_raises():
    from theanompi_tpu.models.data.prefetch import (
        Prefetcher,
        PrefetchStallError,
    )

    def source():
        yield {"x": np.zeros(2)}
        time.sleep(10)  # a hung loader
        yield {"x": np.ones(2)}

    p = Prefetcher(source(), mesh=None, depth=1, stall_timeout=0.3)
    try:
        next(p)  # first batch flows
        t0 = time.perf_counter()
        with pytest.raises(PrefetchStallError, match="stalled"):
            next(p)
        assert time.perf_counter() - t0 < 5
    finally:
        p.close()


@pytest.mark.faultinject
def test_prefetch_fault_stall_site():
    from theanompi_tpu.models.data.prefetch import (
        Prefetcher,
        PrefetchStallError,
    )

    plan = FaultPlan.parse("prefetch:stall@1")
    p = Prefetcher(iter({"x": np.zeros(2)} for _ in range(10)), mesh=None,
                   depth=1, stall_timeout=0.3, fault_plan=plan)
    try:
        next(p)
        with pytest.raises(PrefetchStallError):
            next(p)
    finally:
        p.close()


@pytest.mark.faultinject
def test_prefetch_fault_raise_site():
    from theanompi_tpu.models.data.prefetch import Prefetcher
    from theanompi_tpu.resilience import FaultInjected

    plan = FaultPlan.parse("prefetch:raise@0")
    p = Prefetcher(iter({"x": np.zeros(2)} for _ in range(3)), mesh=None,
                   depth=1, fault_plan=plan)
    with pytest.raises(FaultInjected):
        next(p)


@pytest.mark.faultinject
def test_checkpoint_fail_fault_delivered_at_join(tmp_path):
    from theanompi_tpu.utils.checkpoint import Checkpointer

    plan = FaultPlan.parse("checkpoint:fail@1")
    ck = Checkpointer(str(tmp_path), async_save=True, fault_plan=plan)
    tree = {"a": np.arange(4, dtype=np.float32)}
    ck.save(0, 1, {"params": tree}).join()  # epoch 0 unaffected
    ck.save(1, 2, {"params": tree})
    with pytest.raises(OSError, match="injected checkpoint write"):
        ck.join_pending()
    # epoch 1 was never published; latest still points at epoch 0
    assert ck.latest_epoch() == 0


# -- launcher exit-code contract + dist-init retry --------------------------

def test_launcher_config_error_bad_kv(capsys):
    from theanompi_tpu import launcher

    rc = launcher.main(["--rule", "BSP", "--devices", "4", "--set", "novalue"])
    assert rc == EXIT_CONFIG
    err = capsys.readouterr().err
    assert "tmlauncher: error: config:" in err
    assert "Traceback" not in err  # one line, not a dump


def test_launcher_config_error_bad_modelfile(capsys):
    from theanompi_tpu import launcher

    rc = launcher.main(["--rule", "BSP", "--devices", "4",
                        "--modelfile", "theanompi_tpu.models.no_such_model"])
    assert rc == EXIT_CONFIG
    assert "tmlauncher: error: init:" in capsys.readouterr().err


@pytest.mark.faultinject
def test_launcher_crash_exit_code_with_injected_fault(tmp_path, capsys):
    """A training-phase exception -> one stderr line + EXIT_CRASH (the code
    the supervisor counts against the restart budget)."""
    from theanompi_tpu import launcher

    rc = launcher.main([
        "--rule", "BSP", "--devices", "4",
        "--modelfile", "theanompi_tpu.models.wide_resnet",
        "--modelclass", "WideResNet", *TINY, "--set", "n_epochs=1",
        "--rule-set", "fault_plan=step:raise@0", "--quiet",
    ])
    assert rc == EXIT_CRASH
    err = capsys.readouterr().err
    assert "tmlauncher: error: training: FaultInjected" in err
    assert "Traceback" not in err


def test_dist_init_retries_then_succeeds(monkeypatch):
    import jax

    from theanompi_tpu import launcher

    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "10.0.0.1:8476")
    calls, sleeps = [], []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("connection refused")

    monkeypatch.setattr(jax.distributed, "initialize", flaky)
    launcher._maybe_init_distributed(retries=4, backoff_base=1.0,
                                     sleep=sleeps.append)
    assert len(calls) == 3
    assert sleeps == [1.0, 2.0]  # exponential backoff between attempts


def test_dist_init_hard_error_on_pod(monkeypatch):
    import jax

    from theanompi_tpu import launcher

    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "host0,host1")

    def dead():
        raise RuntimeError("connection refused")

    monkeypatch.setattr(jax.distributed, "initialize", dead)
    with pytest.raises(launcher.DistributedInitError, match="3 attempts"):
        launcher._maybe_init_distributed(retries=3, backoff_base=0.0,
                                         sleep=lambda s: None)


def test_dist_init_already_initialized_short_circuits(monkeypatch, capsys):
    import jax

    from theanompi_tpu import launcher

    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "10.0.0.1:8476")

    def already():
        # jax's EXACT double-init wording (no "already" in it!)
        raise RuntimeError(
            "distributed.initialize should only be called once.")

    monkeypatch.setattr(jax.distributed, "initialize", already)
    launcher._maybe_init_distributed(retries=3, backoff_base=0.0,
                                     sleep=lambda s: pytest.fail("slept"))
    assert "skipped" in capsys.readouterr().err


def test_dist_init_half_initialized_retry_is_not_success(monkeypatch):
    """jax assigns its global client BEFORE connect(): after a failed
    attempt, the retry raises 'only be called once' about the carcass —
    that must surface as DistributedInitError, not a silent 'skipped'
    success (the single-host-downgrade this satellite eliminates)."""
    import jax

    from theanompi_tpu import launcher

    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "10.0.0.1:8476")
    calls = []

    def half_init():
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("deadline exceeded: failed to connect")
        raise RuntimeError("distributed.initialize should only be called "
                           "once.")

    monkeypatch.setattr(jax.distributed, "initialize", half_init)
    monkeypatch.setattr(jax.distributed, "shutdown",
                        lambda: None)  # nothing to tear down in the fake
    with pytest.raises(launcher.DistributedInitError,
                       match="failed to connect"):
        launcher._maybe_init_distributed(retries=3, backoff_base=0.0,
                                         sleep=lambda s: None)


def test_dist_init_shutdown_resets_between_retries(monkeypatch):
    """The retry calls jax.distributed.shutdown() so attempt 2 is a real
    fresh initialize (and succeeds when the coordinator recovers)."""
    import jax

    from theanompi_tpu import launcher

    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "10.0.0.1:8476")
    state = {"init": 0, "shutdown": 0}

    def flaky():
        state["init"] += 1
        if state["init"] == 1:
            raise RuntimeError("connection refused")

    monkeypatch.setattr(jax.distributed, "initialize", flaky)
    monkeypatch.setattr(
        jax.distributed, "shutdown",
        lambda: state.__setitem__("shutdown", state["shutdown"] + 1))
    launcher._maybe_init_distributed(retries=3, backoff_base=0.0,
                                     sleep=lambda s: None)
    assert state["init"] == 2 and state["shutdown"] >= 1


def test_supervisor_heartbeat_path_honors_rule_key(tmp_path):
    from theanompi_tpu import launcher

    base = str(tmp_path)
    args = launcher.build_parser().parse_args(
        ["--supervise", "--rule-set", "heartbeat_path=/tmp/custom_hb.json"])
    assert launcher._supervisor_heartbeat_path(args, base) == \
        "/tmp/custom_hb.json"
    args = launcher.build_parser().parse_args(["--supervise"])
    assert launcher._supervisor_heartbeat_path(args, base) == \
        os.path.join(base, "heartbeat.json")


def test_supervisor_abnormal_exit_does_not_orphan_child(tmp_path):
    """An exception escaping the supervisor loop (a ^C delivered as
    KeyboardInterrupt, a bug) must terminate the running child, not leave
    it training unsupervised."""
    pidfile = str(tmp_path / "pid")
    body = (f"import os, time; open({pidfile!r}, 'w').write(str(os.getpid()));"
            f" time.sleep(60)")
    sup = Supervisor([sys.executable, "-c", body], max_restarts=0,
                     resilience_path=str(tmp_path / "r.json"),
                     sleep=lambda s: None)

    def interrupt_wait(proc, started_s):
        while not os.path.exists(pidfile):
            time.sleep(0.02)
        raise KeyboardInterrupt

    sup._wait = interrupt_wait
    with pytest.raises(KeyboardInterrupt):
        sup.run()
    pid = int(open(pidfile).read())
    deadline = time.perf_counter() + 10
    while time.perf_counter() < deadline:
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            break  # child is gone — not orphaned
        time.sleep(0.05)
    else:
        os.kill(pid, 9)
        pytest.fail("child survived the supervisor's abnormal exit")


def test_dist_init_noop_off_pod(monkeypatch):
    import jax

    from theanompi_tpu import launcher

    for var in ("TPU_WORKER_HOSTNAMES", "JAX_COORDINATOR_ADDRESS"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setattr(jax.distributed, "initialize",
                        lambda: pytest.fail("initialized off-pod"))
    launcher._maybe_init_distributed()
