"""tmlauncher: the CLI session launcher.

Reference (unverified — SURVEY.md §1/§3.1): ``tmlauncher``/``launch_session.py``
composed an ``mpirun`` command line placing one worker process per requested
``cudaN`` device (plus the EASGD server rank) and joined it.

TPU-native re-expression: there is no process tree to compose — the "cluster"
is the device mesh.  The launcher parses the same launch intent
(rule, device count, modelfile/modelclass, config) and drives
``Rule.init(...).wait()`` in-process.  On a multi-host pod, run this same
command on every host under the JAX multi-controller runtime
(``jax.distributed.initialize`` is called automatically when the standard TPU
pod environment variables are present); each host sees the global mesh.

Examples::

    tmlauncher --rule BSP --devices 8 \
        --modelfile theanompi_tpu.models.resnet50 --modelclass ResNet50 \
        --set batch_size=64 --set n_epochs=90 \
        --rule-set exch_strategy=psum_bf16 --record-dir ./record

    tmlauncher --rule EASGD --devices all --rule-set tau=8 \
        --checkpoint-dir ./ckpt --resume
"""

from __future__ import annotations

import argparse
import ast
import json
import os
import sys
import time


class DistributedInitError(RuntimeError):
    """The pod's multi-controller runtime could not be joined (after
    retries) — a hard error, because training single-host while the other
    hosts wait at a collective would hang the whole slice."""


def _parse_kv(pairs: list[str]) -> dict:
    """k=v pairs with Python-literal values (`lr=0.1`, `lrn=False`,
    `stage_blocks=(3,4,6,3)`); bare strings stay strings."""
    out = {}
    for pair in pairs:
        if "=" not in pair:
            raise SystemExit(f"expected key=value, got {pair!r}")
        k, v = pair.split("=", 1)
        try:
            out[k] = ast.literal_eval(v)
        except (ValueError, SyntaxError):
            out[k] = v
    return out


def _maybe_init_distributed(retries: int | None = None,
                            backoff_base: float | None = None,
                            sleep=time.sleep) -> None:
    """Join the JAX multi-controller runtime on a pod (no-op on one host).

    ISSUE 4 satellite: a flaky coordinator used to be swallowed here,
    silently downgrading a pod launch to single-host training.  Now init
    is retried with bounded exponential backoff
    (``THEANOMPI_DIST_INIT_RETRIES`` / ``THEANOMPI_DIST_INIT_BACKOFF``,
    defaults 3 / 1s), and exhausting the retries while the pod env vars
    are present raises :class:`DistributedInitError` — the supervisor
    classifies that as a restartable crash, never a quiet downgrade.
    An "already initialized" runtime (harness-managed) still short-circuits.
    """
    # one host has no runtime to join: a single TPU VM still exports
    # TPU_WORKER_HOSTNAMES (naming only itself), and initialize() there
    # goes looking for the cloud metadata server — on a sealed machine
    # that is a ConnectionError before the first step (found on the v5e)
    hosts = [h for h in os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",")
             if h.strip()]
    if len(hosts) <= 1 and not os.environ.get("JAX_COORDINATOR_ADDRESS"):
        return
    import jax

    if retries is None:
        retries = int(os.environ.get("THEANOMPI_DIST_INIT_RETRIES", "3"))
    if backoff_base is None:
        backoff_base = float(os.environ.get("THEANOMPI_DIST_INIT_BACKOFF",
                                            "1.0"))
    retries = max(1, retries)
    last: Exception | None = None
    for attempt in range(1, retries + 1):
        try:
            jax.distributed.initialize()
            return
        except (RuntimeError, ValueError) as e:
            msg = str(e).lower()
            # double-init is fine (the harness beat us to it); jax says
            # "distributed.initialize should only be called once.".  Match
            # that SPECIFIC phrasing — a bare "already" would also swallow
            # grpc's "Address already in use" (a stale coordinator port),
            # which is a real failure that must retry/raise.
            # And only on the FIRST attempt: jax assigns its global client
            # BEFORE connect(), so after a failed attempt the retry raises
            # this same message about the half-initialized carcass —
            # honoring it then would silently report success on a runtime
            # that never connected
            if "only be called once" in msg:
                if attempt == 1:
                    print(f"tmlauncher: distributed init skipped: {e}",
                          file=sys.stderr)
                    return
            else:
                last = e
                print(f"tmlauncher: distributed init attempt "
                      f"{attempt}/{retries} failed: {e}", file=sys.stderr)
            try:
                # clear the half-initialized global state so the retry is
                # a real fresh initialize, not a double-init error
                jax.distributed.shutdown()
            except Exception:  # lint: swallow-ok — nothing to shut down
                pass
            if attempt < retries:
                sleep(backoff_base * (2 ** (attempt - 1)))
    raise DistributedInitError(
        f"could not join the multi-controller runtime after {retries} "
        f"attempts (pod env vars present, so a single-host fallback would "
        f"desynchronize the slice): {last}")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tmlauncher",
        description="Launch a theanompi_tpu training session on the local "
        "mesh (run on every host of a pod for multi-host).",
        # no prefix abbreviation: the supervisor strips its own flags from
        # the child argv by exact spelling — an abbreviated '--superv'
        # sneaking through would make the child a supervisor too
        # (recursive spawning)
        allow_abbrev=False,
    )
    p.add_argument("--rule", default="BSP",
                   choices=["BSP", "EASGD", "GOSGD", "LocalSGD"])
    p.add_argument("--devices", default="all",
                   help="worker count or 'all' (default)")
    p.add_argument("--modelfile", default="theanompi_tpu.models.wide_resnet")
    p.add_argument("--modelclass", default="WideResNet")
    p.add_argument("--set", dest="model_set", action="append", default=[],
                   metavar="K=V", help="model config entry (repeatable)")
    p.add_argument("--rule-set", dest="rule_set", action="append", default=[],
                   metavar="K=V", help="rule config entry (repeatable)")
    p.add_argument("--config-json", default=None,
                   help="path to a JSON file with {'model': {...}, 'rule': {...}}")
    p.add_argument("--record-dir", default=None)
    p.add_argument("--telemetry-dir", default=None,
                   help="enable structured telemetry: per-rank JSONL event "
                   "sinks under this dir; rank 0 writes trace.json "
                   "(Perfetto-loadable) + summary.json (cross-rank skew) "
                   "at the end of the run.  Also enables live health "
                   "(HEALTH.json verdicts — watch with tmhealth) and the "
                   "crash flight recorder (blackbox.json); tune/disable "
                   "via --rule-set telemetry_health=... / "
                   "telemetry_blackbox=N (ISSUE 13).  Step-time "
                   "attribution (attr.* gauges + ATTRIB.json — inspect "
                   "with tmprof) rides the same opt-in; disable via "
                   "--rule-set telemetry_profile=False, and open a "
                   "bounded jax.profiler device-trace window with "
                   "--rule-set profile_dir=DIR profile_window=START:STOP "
                   "(ISSUE 16).  Under --supervise "
                   "a critical hang verdict kills and restarts the child "
                   "without waiting out --hang-timeout")
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--resume-force", action="store_true",
                   help="override the checkpoint run-fingerprint check: "
                   "resume even though the mesh / exchange strategy / "
                   "model config differ from the checkpoint's (ISSUE 5; "
                   "normally a hard refusal)")
    p.add_argument("--resume-reshard", action="store_true",
                   help="elastic resume (ISSUE 8; implies --resume): a "
                   "checkpoint written under a different data-parallel "
                   "topology is re-laid-out onto the live mesh — params "
                   "re-replicated, zero1 optimizer shards re-padded and "
                   "re-scattered, LR rescaled by the linear-scaling rule "
                   "(stderr-warned).  Model-identity mismatches still "
                   "refuse; unplannable transitions (tp/pp meshes) exit 79")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--quiet", action="store_true")
    sup = p.add_argument_group(
        "supervision (ISSUE 4: auto-restart + resume)")
    sup.add_argument("--supervise", action="store_true",
                     help="run the session in a supervised child process: "
                     "classify exits (crash/preemption/hang/config), "
                     "restart with bounded exponential backoff and "
                     "--resume, and write a resilience.json audit trail")
    sup.add_argument("--max-restarts", type=int, default=3,
                     help="crash/hang restart budget (preemption exits are "
                     "budget-free); default 3")
    sup.add_argument("--backoff-base", type=float, default=1.0,
                     help="first restart delay in seconds, doubling per "
                     "restart (jittered, capped); default 1.0")
    sup.add_argument("--hang-timeout", type=float, default=None,
                     help="supervisor-side heartbeat-staleness kill switch "
                     "in seconds (backstop for a child too wedged to run "
                     "its own watchdog; off by default)")
    sup.add_argument("--elastic", action="store_true",
                     help="elastic supervision (ISSUE 8; implies "
                     "--supervise): re-probe the available device count "
                     "before every restart, rewrite the child's --devices "
                     "to it, and resume with --resume-reshard — the pod "
                     "comes back with fewer chips and keeps training "
                     "(THEANOMPI_ELASTIC_DEVICES overrides the probe)")
    p.add_argument("--sentinel", default=None,
                   choices=["abort", "skip_batch", "rollback"],
                   help="non-finite loss/grad guard policy (shorthand for "
                   "--rule-set sentinel_policy=...); off when absent")
    return p


#: supervision-layer flags stripped from the child's command line
#: (value = how many operands follow the flag)
_SUPERVISOR_FLAGS = {"--supervise": 0, "--max-restarts": 1,
                     "--backoff-base": 1, "--hang-timeout": 1,
                     "--elastic": 0}


def _strip_supervision_args(argv: list[str]) -> list[str]:
    out, i = [], 0
    while i < len(argv):
        key = argv[i].split("=", 1)[0]
        if key in _SUPERVISOR_FLAGS:
            i += 1
            if "=" not in argv[i - 1]:
                i += _SUPERVISOR_FLAGS[key]
            continue
        out.append(argv[i])
        i += 1
    return out


def _supervisor_heartbeat_path(args, base: str) -> str:
    """The supervisor must watch the SAME file the child writes: a
    ``heartbeat_path`` rule key overrides the ``THEANOMPI_HEARTBEAT`` env
    in the child, so honor it here too — a mismatch would make
    ``--hang-timeout`` kill every healthy child as silent."""
    try:
        _, rule_config = _build_configs(args)
    except Exception:  # lint: swallow-ok — the child will report it
        rule_config = {}
    return (rule_config.get("heartbeat_path")
            or os.path.join(base, "heartbeat.json"))


def _supervise(argv: list[str], args) -> int:
    """The --supervise path: this process becomes the Supervisor; the
    actual session runs in child launcher processes (a fresh process is
    the only thing a SIGKILL/OOM/wedged-runtime can't take down with it,
    and the only way to re-init a jax backend cleanly)."""
    from theanompi_tpu.resilience import EXIT_CONFIG, run_job, supervised

    if supervised():
        # belt-and-braces recursion guard: a supervised child must never
        # itself supervise (argv stripping should prevent this; if it ever
        # leaks through, fail loudly instead of forking forever)
        print("tmlauncher: error: config: --supervise inside a supervised "
              "child (recursive supervision)", file=sys.stderr, flush=True)
        return EXIT_CONFIG

    base = args.checkpoint_dir or "."
    os.makedirs(base, exist_ok=True)
    if not args.checkpoint_dir:
        print("tmlauncher: warning: --supervise without --checkpoint-dir — "
              "restarts will redo all work (nothing to resume from)",
              file=sys.stderr)
    heartbeat = _supervisor_heartbeat_path(args, base)
    child = ([sys.executable, "-m", "theanompi_tpu.launcher"]
             + _strip_supervision_args(argv))
    # the per-attempt run/classify/backoff core is the shared run_job
    # seam — the fleet scheduler drives the same loop for its children
    return run_job(
        child,
        max_restarts=args.max_restarts,
        backoff_base=args.backoff_base,
        hang_timeout_s=args.hang_timeout,
        heartbeat_path=heartbeat,
        resilience_path=os.path.join(base, "resilience.json"),
        telemetry_dir=args.telemetry_dir,
        seed=args.seed,
        # ISSUE 8: elastic restarts re-probe the device inventory and
        # resume with the reshard gate open
        elastic=args.elastic,
        resume_args=(("--resume", "--resume-reshard") if args.elastic
                     else ("--resume",)),
    ).exit_code


def _error_line(phase: str, e: BaseException) -> None:
    """The one-line exit-code-contract error report (ISSUE 4 satellite):
    no raw traceback unless THEANOMPI_DEBUG asks for one."""
    print(f"tmlauncher: error: {phase}: {type(e).__name__}: {e}",
          file=sys.stderr, flush=True)
    if os.environ.get("THEANOMPI_DEBUG"):
        import traceback

        traceback.print_exc()


#: setup-phase exception types that will not fix themselves on restart
_CONFIG_ERRORS = (ImportError, AttributeError, TypeError, ValueError,
                  KeyError, IndexError, FileNotFoundError,
                  IsADirectoryError, NotADirectoryError,
                  json.JSONDecodeError)


def _build_configs(args) -> tuple[dict, dict]:
    model_config: dict = {}
    rule_config: dict = {}
    if args.config_json:
        with open(args.config_json) as f:
            blob = json.load(f)
        model_config.update(blob.get("model", {}))
        rule_config.update(blob.get("rule", {}))
    model_config.update(_parse_kv(args.model_set))
    rule_config.update(_parse_kv(args.rule_set))
    rule_config.setdefault("seed", args.seed)
    if args.record_dir:
        rule_config["record_dir"] = args.record_dir
    if args.telemetry_dir:
        rule_config["telemetry_dir"] = args.telemetry_dir
    if args.checkpoint_dir:
        rule_config["checkpoint_dir"] = args.checkpoint_dir
    if args.sentinel:
        rule_config.setdefault("sentinel_policy", args.sentinel)
    if args.resume:
        rule_config["resume"] = True
    if args.resume_reshard:
        # ISSUE 8: the elastic flag IS a resume (nothing to reshard onto
        # a fresh run), with the fingerprint gate opened for replanning
        rule_config["resume"] = True
        rule_config["resume_reshard"] = True
    if args.resume_force:
        rule_config["resume_force"] = True
    if args.quiet:
        rule_config["verbose"] = False
    return model_config, rule_config


def main(argv: list[str] | None = None) -> int:
    """Exit-code contract (ISSUE 4/5/8; see the README table): 0 clean,
    70 training crash, 75 resumable preemption exit, 76 watchdog hang,
    77 checkpoint recovery chain exhausted, 78 config error, 79 elastic
    reshard refused (unplannable topology transition) — each reported as
    ONE ``tmlauncher: ...`` stderr line
    (set THEANOMPI_DEBUG=1 for the full traceback), so the supervisor —
    and any outer scheduler — can classify without parsing tracebacks."""
    argv = list(sys.argv[1:]) if argv is None else list(argv)
    args = build_parser().parse_args(argv)
    if args.elastic:
        args.supervise = True  # elastic IS supervision with re-probing
    if args.supervise:
        return _supervise(argv, args)

    from theanompi_tpu.resilience import EXIT_CONFIG, EXIT_CRASH

    # -- config phase: wrong flags/files will not fix themselves ------------
    try:
        model_config, rule_config = _build_configs(args)
        import theanompi_tpu

        rule_cls = getattr(theanompi_tpu, args.rule)
        devices = None if args.devices == "all" else int(args.devices)
    except SystemExit as e:  # _parse_kv-style one-line config rejections
        print(f"tmlauncher: error: config: {e}", file=sys.stderr, flush=True)
        return EXIT_CONFIG
    except Exception as e:
        _error_line("config", e)
        return EXIT_CONFIG

    # -- environment phase: transient by nature, restartable ----------------
    try:
        _maybe_init_distributed()
        from theanompi_tpu.parallel.mesh import (
            CompileStats,
            device_summary,
            setup_compile_cache,
        )

        # before the first compile (rule.init compiles lazily)
        cache_dir = setup_compile_cache()
        compiles = CompileStats()
        if not args.quiet:
            dev = device_summary()
            print(f"tmlauncher: device platform={dev['platform']} "
                  f"kind={dev['kind']!r} count={dev['count']} "
                  f"compile_cache={cache_dir}", flush=True)
    except Exception as e:
        _error_line("environment", e)
        return EXIT_CRASH

    try:
        return _run_session(args, rule_cls, devices, model_config,
                            rule_config, compiles)
    finally:
        compiles.close()


def _run_session(args, rule_cls, devices, model_config, rule_config,
                 compiles) -> int:
    """The init and training phases of :func:`main` (same exit-code
    contract), under one :class:`CompileStats` the caller closes."""
    from theanompi_tpu.parallel.mesh import shard_report
    from theanompi_tpu.resilience import (
        EXIT_CKPT,
        EXIT_CONFIG,
        EXIT_CRASH,
        EXIT_PREEMPTED,
        EXIT_RESHARD,
        PreemptionExit,
    )
    from theanompi_tpu.utils.checkpoint import (
        CheckpointCorruptError,
        CheckpointFingerprintError,
        CheckpointReshardError,
    )

    # -- init phase: model import / mesh build / compile / resume ----------
    try:
        rule = rule_cls(config=rule_config)
        rule.init(
            devices=devices,
            modelfile=args.modelfile,
            modelclass=args.modelclass,
            model_config=model_config,
        )
    except CheckpointReshardError as e:
        # ISSUE 8: --resume-reshard was set but the transition cannot be
        # planned (tp/pp mesh, layout-family change, bucket mismatch) —
        # a DISTINCT code: the elastic supervisor must stop, not loop
        _error_line("reshard", e)
        return EXIT_RESHARD
    except CheckpointFingerprintError as e:
        # a topology change, not corruption: restarting won't fix it, and
        # the user holds the override (--resume-force, or --resume-reshard
        # when the mismatch is reshardable) — config class
        _error_line("resume", e)
        return EXIT_CONFIG
    except CheckpointCorruptError as e:
        # ISSUE 5: the recovery chain is exhausted — every retained
        # checkpoint failed verification (the bad files are under
        # <checkpoint-dir>/corrupt/).  Distinct code: the supervisor must
        # NOT restart into the same empty chain
        _error_line("checkpoint", e)
        return EXIT_CKPT
    except _CONFIG_ERRORS as e:
        _error_line("init", e)
        return EXIT_CONFIG
    except Exception as e:
        _error_line("init", e)
        return EXIT_CRASH
    if not args.quiet:
        trainer = rule.trainer
        paths = " ".join(f"{k}={v}" for k, v in
                         trainer.model.resolved_paths().items())
        print(f"tmlauncher: mesh {dict(trainer.mesh.shape)} "
              f"global_batch={trainer.global_batch} paths: {paths or '-'}",
              flush=True)

    # -- training phase -----------------------------------------------------
    try:
        recorder = rule.wait()
    except PreemptionExit as e:
        print(f"tmlauncher: preempted: {e} (exit {EXIT_PREEMPTED}; rerun "
              f"with --resume or under --supervise)", file=sys.stderr,
              flush=True)
        return EXIT_PREEMPTED
    except KeyboardInterrupt:
        raise  # a human's ^C is not a crash to classify
    except CheckpointReshardError as e:
        _error_line("reshard", e)
        return EXIT_RESHARD
    except CheckpointCorruptError as e:
        # a sentinel rollback can exhaust the chain mid-training too
        _error_line("checkpoint", e)
        return EXIT_CKPT
    except Exception as e:
        _error_line("training", e)
        return EXIT_CRASH
    if not args.quiet:
        last = {k: v[-1] for k, v in recorder.val_history.items() if v}
        shards = shard_report(rule.trainer.params)
        print(f"tmlauncher: shards devices={shards['devices']} "
              f"bytes_in_use={shards['bytes_in_use']}", flush=True)
        print(f"tmlauncher: compiles {compiles.line()}", flush=True)
        print(f"tmlauncher: done. final val: {last}", flush=True)
        if args.telemetry_dir:
            print(f"tmlauncher: telemetry in {args.telemetry_dir} "
                  f"(trace.json for Perfetto, summary.json for skew)",
                  flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
