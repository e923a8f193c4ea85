"""``tmfleet`` — submit jobs to a fleet dir and run the scheduler.

Shares ``tmlauncher``'s operational contract: the same ``--set key=value``
literal grammar (``ast.literal_eval`` with bare-string fallback) and the
same typed exit codes — config errors (bad spec, bad fault plan, torn
ledger with no recoverable generation) exit
:data:`~theanompi_tpu.resilience.codes.EXIT_CONFIG`, anything unexpected
exits :data:`~theanompi_tpu.resilience.codes.EXIT_CRASH`, and ``run``
returns the scheduler's own verdict (clean only when every job
completed).  The grammar is restated locally rather than imported: the
fleet layer supervises the launcher as a *subprocess* and must never
import it (the ``tmlint`` import-DAG wall enforces this).

::

    tmfleet submit --fleet-dir /pool --job-id a --priority 0 \\
        --set depth=16 --set n_epochs=2
    tmfleet submit --fleet-dir /pool --job-id b --priority 5 \\
        --min-devices 4 --max-devices 4
    tmfleet run --fleet-dir /pool --pool-size 8
    tmfleet status --fleet-dir /pool
"""

from __future__ import annotations

import argparse
import ast
import json
import os
import sys

from theanompi_tpu.resilience import EXIT_CLEAN, EXIT_CONFIG, EXIT_CRASH
from theanompi_tpu.resilience.faults import FaultPlanError
from theanompi_tpu.fleet.jobs import (
    JobRecord,
    JobSpec,
    JobSpecError,
    list_records,
    write_record,
)
from theanompi_tpu.fleet.ledger import LedgerError


def _parse_kv(pairs: list[str] | None) -> dict:
    """``key=value`` pairs with Python-literal values, bare strings kept
    as strings — the same grammar as ``tmlauncher --set`` (restated here;
    the layering wall forbids importing the launcher)."""
    out = {}
    for pair in pairs or []:
        if "=" not in pair:
            raise SystemExit(f"expected key=value, got {pair!r}")
        k, v = pair.split("=", 1)
        try:
            out[k] = ast.literal_eval(v)
        except (ValueError, SyntaxError):
            out[k] = v
    return out


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tmfleet", allow_abbrev=False,
        description="multi-job fleet orchestration on the elastic "
                    "supervisor")
    sub = p.add_subparsers(dest="cmd", required=True)

    ps = sub.add_parser("submit", allow_abbrev=False,
                        help="queue one job spec into the fleet dir")
    ps.add_argument("--fleet-dir", required=True)
    ps.add_argument("--job-id", required=True)
    ps.add_argument("--priority", type=int, default=0)
    ps.add_argument("--min-devices", type=int, default=1)
    ps.add_argument("--max-devices", type=int, default=None)
    ps.add_argument("--rule", default="BSP")
    ps.add_argument("--modelfile",
                    default="theanompi_tpu.models.wide_resnet")
    ps.add_argument("--modelclass", default="WideResNet")
    ps.add_argument("--set", action="append", dest="overrides",
                    metavar="KEY=VALUE",
                    help="model config override (Python literal values)")
    ps.add_argument("--rule-set", action="append", dest="rule_overrides",
                    metavar="KEY=VALUE")
    ps.add_argument("--extra-arg", action="append", dest="extra_args",
                    metavar="ARG",
                    help="verbatim extra launcher argv for the child "
                         "(repeatable; e.g. --extra-arg "
                         "--record-dir=/records)")
    ps.add_argument("--max-restarts", type=int, default=3)
    ps.add_argument("--backoff-base", type=float, default=0.1)

    pr = sub.add_parser("run", allow_abbrev=False,
                        help="run the scheduler until every job is done")
    pr.add_argument("--fleet-dir", required=True)
    pr.add_argument("--pool-size", type=int, default=None,
                    help="device inventory (default: probe, or the "
                         "persisted ledger's)")
    pr.add_argument("--poll-s", type=float, default=0.05)
    pr.add_argument("--fault-plan", default=None,
                    help="fleet-site fault plan (NOT read from the env; "
                         "children are always scrubbed)")
    pr.add_argument("--quiet", action="store_true",
                    help="suppress the final status JSON on stdout")

    pt = sub.add_parser("status", allow_abbrev=False,
                        help="print the fleet's job + pool state as JSON")
    pt.add_argument("--fleet-dir", required=True)
    return p


def _status_dict(fleet_dir: str) -> dict:
    jobs = [r.to_dict() for r in list_records(fleet_dir)]
    pool = None
    path = os.path.join(fleet_dir, "ledger.json")
    for p in (path, path + ".prev"):
        try:
            with open(p) as f:
                pool = json.load(f)
            break
        except (FileNotFoundError, ValueError):
            continue
    return {"jobs": jobs, "pool": pool}


def _cmd_submit(args) -> int:
    spec = JobSpec(
        job_id=args.job_id, priority=args.priority,
        min_devices=args.min_devices, max_devices=args.max_devices,
        rule=args.rule, modelfile=args.modelfile,
        modelclass=args.modelclass,
        model_config=_parse_kv(args.overrides),
        rule_config=_parse_kv(args.rule_overrides),
        extra_args=list(args.extra_args or []),
        max_restarts=args.max_restarts, backoff_base=args.backoff_base)
    spec.validate()
    jpath = os.path.join(args.fleet_dir, "jobs", spec.job_id, "job.json")
    if os.path.exists(jpath):
        raise JobSpecError(f"job {spec.job_id!r} already exists "
                           f"in {args.fleet_dir}")
    write_record(args.fleet_dir, JobRecord(spec=spec))
    print(f"tmfleet: queued {spec.job_id!r} (priority {spec.priority}, "
          f"devices {spec.min_devices}..{spec.max_devices or 'free'})")
    return EXIT_CLEAN


def _cmd_run(args) -> int:
    from theanompi_tpu.fleet.scheduler import FleetScheduler

    sched = FleetScheduler(args.fleet_dir, args.pool_size,
                           fault_plan=args.fault_plan, poll_s=args.poll_s)
    for rec in list_records(args.fleet_dir):
        if rec.status not in ("done", "failed"):
            sched.adopt(rec)
    rc = sched.run()
    if not args.quiet:
        print(json.dumps(_status_dict(args.fleet_dir), indent=1))
    return rc


def _cmd_status(args) -> int:
    print(json.dumps(_status_dict(args.fleet_dir), indent=1))
    return EXIT_CLEAN


def _error_line(phase: str, e: BaseException) -> None:
    print(f"tmfleet: error: {phase}: {type(e).__name__}: {e}",
          file=sys.stderr)
    if os.environ.get("THEANOMPI_DEBUG"):
        import traceback

        traceback.print_exc()


def main(argv: list[str] | None = None) -> int:
    try:
        args = build_parser().parse_args(argv)
    except SystemExit as e:
        return int(e.code or 0)
    try:
        if args.cmd == "submit":
            return _cmd_submit(args)
        if args.cmd == "run":
            return _cmd_run(args)
        return _cmd_status(args)
    except (JobSpecError, LedgerError, FaultPlanError) as e:
        _error_line("config", e)
        return EXIT_CONFIG
    except Exception as e:
        _error_line("fleet", e)
        return EXIT_CRASH


if __name__ == "__main__":
    raise SystemExit(main())
