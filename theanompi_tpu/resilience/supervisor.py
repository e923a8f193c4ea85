"""Supervised training: auto-restart with backoff + resume (ISSUE 4).

The t5x/orbax-style auto-resume loop, built into the framework instead of
bolted onto each driver: the supervisor runs the training session (the
launcher's ``rule.init(...).wait()``) in a **child process**, classifies
how it died, and restarts it — auto-resuming from the latest checkpoint —
under a bounded exponential-backoff budget.  A child process, not a
thread or a try/except: SIGKILL, OOM, a wedged XLA runtime and a
preempting hypervisor all kill *processes*, and only a fresh process can
re-initialize a jax backend cleanly.  This parent never imports jax: a
chip belongs to one process at a time, and the device probe below is a
child that has exited — and so let go of the chip — before the next
attempt starts.

Exit-code contract (see the package ``__init__`` / README table)::

    0              clean        -> done
    75 / -SIGTERM  preemption   -> restart; does NOT count against budget
    76             hang         -> restart (counts)
    77             checkpoint   -> fatal (the recovery chain is exhausted)
    2 / 78         config       -> fatal, no restart (it won't fix itself)
    79             reshard      -> fatal (the transition is unplannable)
    anything else  crash        -> restart (counts)

Every attempt is recorded — cause, exit code, duration, time lost — to a
crash-safe ``resilience.json`` summary, and mirrored as JSONL events into
the telemetry directory (``supervisor.jsonl``; a separate file because
each child attempt truncates and rewrites the per-rank event sinks).

Hang detection is layered: the child's in-process :class:`~theanompi_tpu.
resilience.watchdog.Watchdog` (median-adaptive, exits ``EXIT_HANG``
itself) is primary; the supervisor's ``hang_timeout_s`` is the blunt
mtime-based backstop for a child too wedged to run even its watchdog
thread, enabled only when configured (``--hang-timeout``).
"""

from __future__ import annotations

import dataclasses
import json
import os
import random
import signal
import subprocess
import sys
import threading
import time

from theanompi_tpu.resilience.codes import (
    EXIT_CKPT,
    EXIT_CLEAN,
    EXIT_CONFIG,
    EXIT_CRASH,
    EXIT_HANG,
    EXIT_PREEMPTED,
    EXIT_RESHARD,
)
from theanompi_tpu.resilience.events import read_events
from theanompi_tpu.resilience.watchdog import heartbeat_age_s

#: restart-budget-exempt preemptions still need SOME bound, or a
#: preempt-loop (bad zone) supervises forever
MAX_PREEMPTIONS = 64


def classify_exit(returncode: int) -> str:
    """-> 'clean' | 'preemption' | 'hang' | 'config' | 'checkpoint' |
    'reshard' | 'crash'."""
    if returncode == EXIT_CLEAN:
        return "clean"
    # -SIGTERM: the preemptor's signal landed before (or instead of) the
    # child's cooperative handler — still a preemption, but the child had
    # no chance to checkpoint, so resume falls back to the last epoch
    if returncode in (EXIT_PREEMPTED, -signal.SIGTERM):
        return "preemption"
    if returncode == EXIT_HANG:
        return "hang"
    # ISSUE 5: the child's checkpoint recovery chain is exhausted — every
    # retained checkpoint failed verification and was quarantined.  A
    # restart would walk the same (now empty) chain: fatal, like config
    if returncode == EXIT_CKPT:
        return "checkpoint"
    # ISSUE 8: the elastic reshard was refused (tp/pp mesh, layout-family
    # change, bucket mismatch).  Replanning the same transition cannot
    # succeed: fatal, never a restart loop
    if returncode == EXIT_RESHARD:
        return "reshard"
    # 2 is argparse's usage-error exit
    if returncode in (EXIT_CONFIG, 2):
        return "config"
    return "crash"


def probe_device_count(env: dict | None = None, *,
                       timeout_s: float = 120.0, log=None) -> int | None:
    """The live accelerator inventory, or ``None`` when unknowable.

    Shared by the elastic :class:`Supervisor` (per-restart re-probe) and
    the fleet ledger (device-pool discovery): the
    ``THEANOMPI_ELASTIC_DEVICES`` env override first (operators who
    already know the slice size), else a fresh ``python -c "import jax;
    ..."`` subprocess — a SUBPROCESS because only an uninitialized
    backend sees the current inventory (and this stdlib-only module must
    not import jax).  A cpu-backend answer without an explicit
    ``JAX_PLATFORMS`` cpu pin is a FAILED probe, not a 1-chip topology.
    """
    def _log(msg: str) -> None:
        if log is not None:
            log(msg)

    def _valid(n: int, source: str) -> int | None:
        if n < 1:
            _log(f"ignoring nonsensical device count {n} from {source}")
            return None
        return n

    override = os.environ.get("THEANOMPI_ELASTIC_DEVICES")
    if override:
        try:
            return _valid(int(override), "THEANOMPI_ELASTIC_DEVICES")
        except ValueError:
            _log(f"ignoring non-integer "
                 f"THEANOMPI_ELASTIC_DEVICES={override!r}")
    env = dict(os.environ) if env is None else env
    try:
        out = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(len(jax.devices()), "
             "jax.default_backend())"],
            env=env, capture_output=True, text=True, timeout=timeout_s)
        if out.returncode != 0:
            _log(f"device probe exited {out.returncode}: "
                 f"{out.stderr.strip()[-200:]}")
            return None
        count_s, backend = out.stdout.strip().splitlines()[-1].split()
        if backend == "cpu" and "cpu" not in env.get(
                "JAX_PLATFORMS", "").lower():
            # jax silently falls back to the CPU backend when an
            # accelerator plugin fails to init: on a TPU VM that is a
            # FAILED probe ("1 cpu device"), not a 1-chip topology —
            # resharding onto it would keep "training" on host CPU
            _log(f"device probe fell back to the cpu backend "
                 f"({count_s} device(s)) but JAX_PLATFORMS does not pin "
                 f"cpu; treating as a failed probe")
            return None
        return _valid(int(count_s), "jax probe")
    except (OSError, subprocess.SubprocessError, ValueError,
            IndexError) as e:
        _log(f"device probe failed: {e}")
        return None


class Supervisor:
    """Run a child command under restart supervision.

    ``child_cmd`` is the full argv of one training attempt;
    ``resume_args`` (default ``("--resume",)``) are appended from the
    second attempt on, so restarts pick up the latest checkpoint while the
    first attempt honors exactly what the user asked for.

    ``elastic=True`` (ISSUE 8): before every RESTART the supervisor
    re-probes the live device count and rewrites the child's ``--devices``
    operand to what is actually there — "the pod comes back with fewer
    chips and keeps training".  Pair it with
    ``resume_args=("--resume", "--resume-reshard")`` (the launcher's
    ``--elastic`` flag does) so the child replans the checkpoint onto the
    probed topology instead of refusing the fingerprint mismatch.  The
    probe: ``device_probe()`` when injected (tests), else the
    ``THEANOMPI_ELASTIC_DEVICES`` env override (operators who already know
    the new slice size), else a fresh ``python -c "import jax; ..."``
    subprocess — a SUBPROCESS because only an uninitialized backend sees
    the current device inventory (and this stdlib-only module must not
    import jax).  Per-attempt device counts and reshard outcomes land in
    the ``resilience.json`` attempt records.
    """

    def __init__(self, child_cmd: list[str], *, max_restarts: int = 3,
                 backoff_base: float = 1.0, backoff_cap: float = 60.0,
                 jitter: float = 0.5, hang_timeout_s: float | None = None,
                 poll_s: float = 0.2, heartbeat_path: str | None = None,
                 resilience_path: str = "resilience.json",
                 telemetry_dir: str | None = None,
                 resume_args: tuple[str, ...] = ("--resume",),
                 env: dict | None = None, seed: int = 0,
                 sleep=None, elastic: bool = False, device_probe=None,
                 probe_timeout_s: float = 120.0):
        self.child_cmd = list(child_cmd)
        self.max_restarts = max_restarts
        self.backoff_base = backoff_base
        self.backoff_cap = backoff_cap
        self.jitter = jitter
        if hang_timeout_s is not None and hang_timeout_s < 3.0:
            # the child's heartbeat writer rate-limits to ~1/s: a timeout
            # at or below that kills every healthy child as "hung"
            self._log(f"hang_timeout_s={hang_timeout_s:g} is below the "
                      f"heartbeat write interval; clamping to 3.0s")
            hang_timeout_s = 3.0
        self.hang_timeout_s = hang_timeout_s
        self.poll_s = poll_s
        self.heartbeat_path = heartbeat_path
        self.resilience_path = resilience_path
        self.telemetry_dir = telemetry_dir
        self.resume_args = tuple(resume_args)
        self.env = dict(env or {})
        self.sleep = sleep
        self.elastic = elastic
        self.device_probe = device_probe
        self.probe_timeout_s = probe_timeout_s
        self._last_devices: int | None = None
        self._seen_reshard_applies = 0
        self._rng = random.Random(seed)  # jittered backoff, reproducible
        self.attempts: list[dict] = []
        self._proc: subprocess.Popen | None = None
        self._terminated = False
        # default backoff sleeper: an event wait, so a SIGTERM landing
        # DURING the backoff interrupts it instead of sleeping through the
        # preemption grace period (tests inject `sleep` to fake delays)
        self._term_event = threading.Event()

    # -- elastic topology probing (ISSUE 8) ----------------------------------
    def _valid_count(self, n: int, source: str) -> int | None:
        """A probed count must be a positive worker count — 0/negative is
        a failed probe (keep the previous topology), not a topology."""
        if n < 1:
            self._log(f"ignoring nonsensical device count {n} from "
                      f"{source}; keeping the previous topology")
            return None
        return n

    def _probe_devices(self, attempt: int) -> int | None:
        """The live device count, or None when unknowable (the attempt
        then runs with the previous topology unchanged)."""
        if self.device_probe is not None:
            try:
                return self._valid_count(int(self.device_probe()),
                                         "injected probe")
            # lint: swallow-ok — an injected probe may fail arbitrarily;
            # the failure is logged and the restart proceeds with the
            # previous topology instead of dying inside the supervisor
            except Exception as e:
                self._log(f"injected device probe failed: {e}")
                return None
        return probe_device_count(self._attempt_env(attempt),
                                  timeout_s=self.probe_timeout_s,
                                  log=self._log)

    @staticmethod
    def _with_devices(cmd: list[str], n: int) -> list[str]:
        """Rewrite the ``--devices`` operand (including ``--devices all``
        — "all" is exactly what changed) to the probed count.  A command
        without the flag is left alone: the child discovers all devices
        itself, which is already elastic."""
        out = list(cmd)
        for i, a in enumerate(out):
            if a == "--devices" and i + 1 < len(out):
                out[i + 1] = str(n)
                return out
            if a.startswith("--devices="):
                out[i] = f"--devices={n}"
                return out
        return out

    # -- one attempt ---------------------------------------------------------
    def _attempt_cmd(self, attempt: int) -> list[str]:
        cmd = list(self.child_cmd)
        if attempt > 1:
            cmd += [a for a in self.resume_args if a not in cmd]
            if self.elastic and self._last_devices is not None:
                cmd = self._with_devices(cmd, self._last_devices)
        return cmd

    def _attempt_env(self, attempt: int) -> dict:
        env = dict(os.environ)
        env.update(self.env)
        env["THEANOMPI_SUPERVISED"] = "1"
        env["THEANOMPI_ATTEMPT"] = str(attempt)
        if self.heartbeat_path:
            env["THEANOMPI_HEARTBEAT"] = self.heartbeat_path
        return env

    def _wait(self, proc: subprocess.Popen,
              started_s: float) -> tuple[int, bool]:
        """Poll the child; -> (returncode, killed_as_hung)."""
        # lint: wall-ok — compared against HEALTH.json file mtimes
        wall0 = time.time()
        next_health = 0.0
        while True:
            rc = proc.poll()
            if rc is not None:
                return rc, False
            # real sleep, NOT the injected self.sleep: that seam fakes the
            # restart BACKOFF in tests; a faked poll would busy-spin here
            if self.hang_timeout_s is not None and self.heartbeat_path:
                age = heartbeat_age_s(self.heartbeat_path)
                if age is None:
                    # no heartbeat yet: measure from attempt start (compile
                    # time counts — size the timeout accordingly)
                    age = time.perf_counter() - started_s
                if age > self.hang_timeout_s:
                    self._log(f"no heartbeat for {age:.0f}s "
                              f"(> {self.hang_timeout_s:.0f}s); killing "
                              f"hung child pid {proc.pid}")
                    proc.kill()
                    return proc.wait(), True
            # ISSUE 13: the child's own health monitor publishing a
            # critical hang verdict beats waiting out hang_timeout_s —
            # preempt-and-restart NOW instead of trusting the blunt
            # mtime backstop (checked ~1/s, not every poll tick)
            if self.telemetry_dir:
                nowp = time.perf_counter()
                if nowp >= next_health:
                    next_health = nowp + 1.0
                    v = self._health_hung(wall0)
                    if v is not None:
                        self._log(
                            f"health: critical hang verdict "
                            f"({v.get('reason', 'no reason')}); killing "
                            f"hung child pid {proc.pid}")
                        proc.kill()
                        return proc.wait(), True
            time.sleep(self.poll_s)

    def _health_hung(self, wall0: float) -> dict | None:
        """A FRESH critical hang verdict from the child's ``HEALTH.json``
        (ISSUE 13), or None.  Freshness is the file mtime vs this
        attempt's wall start — a previous attempt's dying verdict must
        never kill a healthy restart.  Plain ``json``: this stdlib-only
        module does not import the telemetry package."""
        path = os.path.join(self.telemetry_dir, "HEALTH.json")
        try:
            if os.stat(path).st_mtime <= wall0:
                return None
            with open(path) as f:
                health = json.load(f)
        except (OSError, ValueError):
            return None
        if not isinstance(health, dict):
            return None
        for v in health.get("verdicts", []):
            if (isinstance(v, dict) and v.get("detector") == "hang"
                    and v.get("severity") == "critical"):
                return v
        return None

    def _fresh_json(self, filename: str, wall0: float) -> dict | None:
        """Parse ``<telemetry_dir>/<filename>`` when its mtime postdates
        this attempt's wall start; None otherwise."""
        if not self.telemetry_dir:
            return None
        path = os.path.join(self.telemetry_dir, filename)
        try:
            if os.stat(path).st_mtime < wall0:
                return None
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            return None
        return data if isinstance(data, dict) else None

    def _backoff_s(self, restarts: int) -> float:
        base = min(self.backoff_cap,
                   self.backoff_base * (2 ** max(0, restarts - 1)))
        return base * (1.0 + self.jitter * self._rng.random())

    def _forward_term(self, signum, frame) -> None:
        """A preempted VM TERMs the supervisor too: hand the signal to the
        child (whose preemption handler checkpoints and exits 75) and end
        supervision after it — never restart into a dying machine."""
        self._terminated = True
        self._term_event.set()  # wake a supervisor mid-backoff
        p = self._proc
        if p is not None and p.poll() is None:
            try:
                p.terminate()
            except OSError:  # lint: swallow-ok — child already gone
                pass

    def terminate(self) -> None:
        """Thread-safe preemption entry point (the fleet scheduler's):
        act exactly as a delivered SIGTERM — forward it to the child
        (whose cooperative handler checkpoints and exits 75), interrupt
        any backoff wait, and end supervision after the child's
        shutdown, never restarting."""
        self._forward_term(signal.SIGTERM, None)

    # -- the loop ------------------------------------------------------------
    def run(self) -> int:
        prev_term = None
        if threading.current_thread() is threading.main_thread():
            prev_term = signal.signal(signal.SIGTERM, self._forward_term)
        try:
            return self._run()
        finally:
            if prev_term is not None:
                signal.signal(signal.SIGTERM, prev_term)
            # abnormal exit (KeyboardInterrupt in the poll loop, a bug)
            # must not orphan a still-running training child — on every
            # normal path _proc is already None here
            p, self._proc = self._proc, None
            if p is not None and p.poll() is None:
                self._log(f"terminating child pid {p.pid} on abnormal "
                          f"supervisor exit")
                p.terminate()
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()

    def _run(self) -> int:
        t_run0 = time.perf_counter()
        attempt, restarts, preemptions = 0, 0, 0
        final = EXIT_CRASH
        if self.elastic:
            # baseline against events a PREVIOUS supervised run left in
            # the (carried-forward) resilience.json — only applies newer
            # than this run's start may stamp an attempt as resharded
            self._seen_reshard_applies = self._count_reshard_applies()
        while True:
            if self._terminated:
                # SIGTERM landed between attempts (during backoff): never
                # spawn a fresh child into a dying machine
                self._log("terminated during backoff; not restarting")
                final = EXIT_PREEMPTED
                break
            attempt += 1
            if self.heartbeat_path:
                try:
                    os.remove(self.heartbeat_path)  # stale mtime = insta-kill
                except OSError:
                    pass  # lint: swallow-ok — heartbeat already absent
            if self.elastic and attempt > 1:
                # re-probe what is actually there before every restart —
                # the previous death may BE a topology change (preempted
                # chips); the child gets the probed count + the reshard
                # flag (resume_args) and replans the checkpoint onto it
                probed = self._probe_devices(attempt)
                if probed is not None:
                    if probed != self._last_devices:
                        self._log(f"elastic: probed {probed} device(s) "
                                  f"for attempt {attempt}")
                    self._last_devices = probed
            cmd = self._attempt_cmd(attempt)
            self._log(f"attempt {attempt}: {' '.join(cmd)}")
            t0 = time.perf_counter()
            # lint: wall-ok — gates blackbox/HEALTH harvesting by mtime
            wall_t0 = time.time()
            proc = subprocess.Popen(cmd, env=self._attempt_env(attempt))
            self._proc = proc
            rc, hung = self._wait(proc, t0)
            # cleared only on the NORMAL path: an exception out of _wait
            # leaves _proc set so run()'s finally can terminate the child
            self._proc = None
            dur = time.perf_counter() - t0
            cause = "hang" if hung else classify_exit(rc)
            rec = {"attempt": attempt, "cause": cause, "exit_code": rc,
                   "duration_s": round(dur, 3)}
            if self.elastic and self._last_devices is not None:
                rec["devices"] = self._last_devices
            outcome = self._reshard_outcome(rc)
            if outcome is not None:
                rec["reshard"] = outcome
            if cause not in ("clean", "preemption"):
                # progress since the last published checkpoint is gone; the
                # attempt's whole duration is the honest upper bound
                rec["time_lost_s"] = round(dur, 3)
            # ISSUE 13: harvest the attempt's flight-recorder dump and
            # final health verdicts into the attempt record (mtime-gated:
            # a stale file from an earlier attempt is not THIS death).
            # The blackbox summary drops the event ring — resilience.json
            # is the index; the full ring stays in blackbox.json
            bb = self._fresh_json("blackbox.json", wall_t0)
            if bb is not None:
                rec["blackbox"] = {
                    k: bb[k] for k in ("reason", "error", "wall_time",
                                       "pid", "rank", "n_events",
                                       "fingerprint") if k in bb}
            hv = self._fresh_json("HEALTH.json", wall_t0)
            if hv is not None:
                bad = [v for v in hv.get("verdicts", [])
                       if isinstance(v, dict) and v.get("severity") != "ok"]
                if bad:
                    rec["health"] = bad
            self.attempts.append(rec)
            self._emit({"name": "supervisor.attempt", **rec})
            if cause == "clean":
                final = EXIT_CLEAN
                break
            if self._terminated:
                self._log("terminated; ending supervision after the "
                          "child's shutdown (no restart)")
                final = rc if rc > 0 else EXIT_PREEMPTED
                break
            if cause == "checkpoint":
                # no verifiable checkpoint left (the child already walked
                # the whole recovery chain and quarantined every rung):
                # restarting replays the same exhausted walk
                self._log(f"attempt {attempt} exhausted the checkpoint "
                          f"recovery chain (exit {rc}); not restarting — "
                          f"inspect <checkpoint-dir>/corrupt/ and "
                          f"resilience.json")
                final = rc
                break
            if cause == "reshard":
                # ISSUE 8: the transition is unplannable (tp/pp mesh,
                # layout-family change, bucket mismatch) — replanning the
                # same pair cannot succeed, so a restart is a fatal loop
                self._log(f"attempt {attempt} could not reshard the "
                          f"checkpoint onto the live topology (exit {rc}); "
                          f"not restarting — dry-run `python -m "
                          f"theanompi_tpu.utils.checkpoint --reshard-plan "
                          f"<checkpoint-dir> --to-devices N` to see why")
                final = rc
                break
            if cause == "config":
                if attempt == 1:
                    self._log(f"attempt 1 exited with a config error "
                              f"(exit {rc}); not restarting")
                    final = rc
                    break
                # a config classification appearing only on a RESTART is
                # suspect: attempt 1 got past init, so this is more likely
                # environmental fallout of the previous death (e.g. an
                # accelerator lock released lazily after a SIGKILL,
                # shrinking the visible device count) — burn budget and
                # retry rather than give up with restarts remaining
                self._log(f"attempt {attempt} exited with a config error "
                          f"(exit {rc}) AFTER a working first attempt; "
                          f"treating as a restartable crash")
                cause = "crash"
                self.attempts[-1]["cause"] = "crash(config-on-restart)"
            if cause == "preemption":
                preemptions += 1
                if preemptions > MAX_PREEMPTIONS:
                    self._log(f"{preemptions} preemptions; giving up")
                    final = rc if rc > 0 else EXIT_PREEMPTED
                    break
            else:
                restarts += 1
                if restarts > self.max_restarts:
                    self._log(f"restart budget exhausted "
                              f"({restarts - 1}/{self.max_restarts}); "
                              f"giving up after {cause} (exit {rc})")
                    final = rc if rc > 0 else EXIT_CRASH
                    break
            delay = self._backoff_s(max(1, restarts))
            budget = ("free" if cause == "preemption"
                      else f"{restarts}/{self.max_restarts}")
            self._log(f"attempt {attempt} ended: {cause} (exit {rc}); "
                      f"restart {budget} with resume in {delay:.1f}s")
            self._write_summary(final=None, t_run0=t_run0,
                                restarts=restarts, preemptions=preemptions)
            if self.sleep is not None:
                self.sleep(delay)
            else:
                self._term_event.wait(delay)  # interruptible by SIGTERM
        self._write_summary(final=final, t_run0=t_run0,
                            restarts=restarts, preemptions=preemptions)
        self._emit({"name": "supervisor.done", "final_exit": final,
                    "restarts": restarts, "preemptions": preemptions})
        return final

    def _count_reshard_applies(self) -> int:
        return sum(1 for e in read_events(self.resilience_path)
                   if e.get("name") == "reshard.apply")

    def _reshard_outcome(self, rc: int) -> str | None:
        """'applied' when the attempt recorded a fresh ``reshard.apply``
        event in resilience.json (the child's checkpointer writes them),
        'failed' when it died with the reshard exit code, None otherwise."""
        if not self.elastic:
            return None
        applies = self._count_reshard_applies()
        if applies > self._seen_reshard_applies:
            self._seen_reshard_applies = applies
            return "applied"
        if rc == EXIT_RESHARD:
            return "failed"
        return None

    # -- reporting -----------------------------------------------------------
    def summary(self, final, t_run0, restarts, preemptions) -> dict:
        return {
            "attempts": self.attempts,
            "restarts": restarts,
            "preemptions": preemptions,
            "time_lost_s": round(sum(a.get("time_lost_s", 0.0)
                                     for a in self.attempts), 3),
            "total_s": round(time.perf_counter() - t_run0, 3),
            "final_exit": final,  # None while still running
        }

    def _write_summary(self, **kw) -> None:
        """Crash-safe rewrite after every attempt, not just at the end —
        a supervisor killed mid-run still leaves the attempt record.
        ``events`` recorded into the same file by the child's checkpoint
        recovery chain (ISSUE 5: ``ckpt.fallback``/``ckpt.quarantine``)
        are carried forward, never clobbered by the rewrite."""
        path = self.resilience_path
        data = self.summary(**kw)
        events = read_events(path)
        if events:
            data["events"] = events
        try:
            os.makedirs(os.path.dirname(os.path.abspath(path)),
                        exist_ok=True)
            with open(path + ".tmp", "w") as f:
                json.dump(data, f, indent=1)
            os.replace(path + ".tmp", path)
        except OSError as e:
            self._log(f"could not write {path}: {e}")

    def _emit(self, event: dict) -> None:
        """Mirror supervisor events into the telemetry dir as JSONL.

        A dedicated ``supervisor.jsonl`` (append mode), NOT an
        ``events-rank*`` sink: each child attempt truncates those, and the
        aggregation pass must not mistake the supervisor for a rank."""
        if not self.telemetry_dir:
            return
        try:
            os.makedirs(self.telemetry_dir, exist_ok=True)
            line = json.dumps({"ts": time.time(),  # lint: wall-ok — log
                               "kind": "instant", **event})  # stamp
            # lint: atomic-publish-ok — JSONL audit stream; read_events
            # skips a torn final line, and losing the tail on crash is
            # exactly the crash being recorded
            with open(os.path.join(self.telemetry_dir,
                                   "supervisor.jsonl"), "a") as f:
                f.write(line + "\n")
        except OSError as e:
            self._log(f"could not write supervisor telemetry: {e}")

    @staticmethod
    def _log(msg: str) -> None:
        print(f"supervisor: {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class JobResult:
    """What one supervised job episode came to (``run_job``'s return)."""

    exit_code: int       #: the final exit code of the whole episode
    cause: str           #: the LAST attempt's classification
    attempts: list       #: per-attempt records (resilience.json shape)
    preempted: bool      #: episode ended by preemption (resumable later)

    @property
    def clean(self) -> bool:
        return self.exit_code == EXIT_CLEAN


def run_job(child_cmd: list[str], *, on_supervisor=None,
            **supervisor_kwargs) -> JobResult:
    """One supervised job episode: the per-attempt run/classify/backoff
    core behind both ``tmlauncher --supervise`` and the fleet scheduler.

    Builds a :class:`Supervisor` over ``child_cmd`` (all keyword options
    pass straight through) and runs it to a final exit code.
    ``on_supervisor``, when given, receives the Supervisor before the
    first attempt — the fleet scheduler registers the handle there so a
    priority preemption can :meth:`Supervisor.terminate` the episode
    from another thread.  ``run()`` installs its SIGTERM forwarder only
    on the main thread, so calling this from worker threads is safe.
    """
    sup = Supervisor(child_cmd, **supervisor_kwargs)
    if on_supervisor is not None:
        on_supervisor(sup)
    rc = sup.run()
    cause = (sup.attempts[-1]["cause"] if sup.attempts
             else classify_exit(rc))
    return JobResult(
        exit_code=rc, cause=cause, attempts=list(sup.attempts),
        preempted=sup._terminated or classify_exit(rc) == "preemption")
