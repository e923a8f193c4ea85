"""``tmlint`` — the static-analysis console script.

Shares the repo's one-line-error exit contract (tmlauncher/tmserve):

- ``0`` — clean: no unsuppressed findings;
- ``1`` — findings (each printed ``path:line:col: severity [rule] msg``);
- ``2`` — usage error (unknown rule, bad path), one ``tmlint: error:``
  stderr line (argparse's own exit 2 for bad flags is kept).

``--report FILE`` writes the JSON artifact (schema locked by test);
``--hlo-audit`` additionally runs the compiled-artifact auditor, which
needs jax and a few seconds of XLA compile — the plain AST run stays
dependency-light and fast for pre-commit use.  ``--race-audit`` runs
the interleaving harness's negative proof (pure Python, no jax): the
seeded lost-update race must be detected and its lock-guarded twin
must stay clean, or the run exits 1.
"""

from __future__ import annotations

import argparse
import json
import sys

from theanompi_tpu.analysis import core


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tmlint",
        description="JAX-aware static analysis for theanompi_tpu "
                    "(rule registry + compiled-artifact auditor)",
        allow_abbrev=False)
    p.add_argument("paths", nargs="*",
                   help="files to lint (default: the package)")
    p.add_argument("--rules", default=None,
                   help="comma-separated rule subset (default: all)")
    p.add_argument("--list-rules", action="store_true",
                   help="print the registered rules and exit")
    p.add_argument("--report", default=None, metavar="FILE",
                   help="write the JSON report artifact to FILE")
    p.add_argument("--hlo-audit", action="store_true",
                   help="also audit compiled train/serve steps (donation, "
                        "collective counts, host callbacks; needs jax)")
    p.add_argument("--race-audit", action="store_true",
                   help="run the interleaving harness self-check: the "
                        "seeded synthetic race must be detected, the "
                        "guarded twin must stay clean (pure Python)")
    p.add_argument("--show-suppressed", action="store_true",
                   help="print suppressed findings too (always in --report)")
    p.add_argument("--quiet", action="store_true",
                   help="summary line only, no per-finding output")
    return p


def _error_line(what: str, err: BaseException | str) -> None:
    print(f"tmlint: error: {what}: {err}", file=sys.stderr, flush=True)


def main(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:]) if argv is None else list(argv)
    try:
        args = build_parser().parse_args(argv)
    except SystemExit as e:
        # argparse exits 2 on bad flags — keep its contract
        return int(e.code or 0)

    if args.list_rules:
        for name, cls in sorted(core.all_rules().items()):
            print(f"{name:16s} {cls.severity:8s} {cls.description}")
        return 0

    rule_names = (None if args.rules is None
                  else [r.strip() for r in args.rules.split(",") if r.strip()])
    paths = args.paths or None
    try:
        findings, n_files = core.lint_paths(paths, rule_names)
    except KeyError as e:
        _error_line("rules", e.args[0])
        return 2
    except (OSError, SyntaxError) as e:
        _error_line("paths", e)
        return 2
    except Exception as e:
        _error_line("internal", e)
        return 2

    audit_reports = None
    audit_failure = None
    if args.hlo_audit:
        from theanompi_tpu.analysis import hlo_audit

        try:
            audit_reports = hlo_audit.run_default_audits()
        except hlo_audit.HLOAuditError as e:
            # a locked-invariant violation is a FINDING, not a usage
            # error: keep going so the AST findings still print and the
            # report artifact (which shows what failed) still publishes
            audit_failure = str(e)
            audit_reports = getattr(e, "reports", None)
        except Exception as e:
            _error_line("hlo-audit", e)
            return 2

    race_report = None
    race_failure = None
    if args.race_audit:
        from theanompi_tpu.analysis import interleave

        try:
            race_report = interleave.race_audit()
        except interleave.RaceAuditError as e:
            # same contract as --hlo-audit: a failed negative proof is a
            # FINDING (the harness lost its teeth), not a usage error
            race_failure = str(e)
            race_report = getattr(e, "report", None)
        except Exception as e:
            _error_line("race-audit", e)
            return 2

    active = [f for f in findings if not f.suppressed]
    if not args.quiet:
        for f in findings:
            if f.suppressed and not args.show_suppressed:
                continue
            print(f.format())
    n_sup = sum(f.suppressed for f in findings)
    print(f"tmlint: {len(active)} finding(s), {n_sup} suppressed, "
          f"{n_files} file(s) scanned"
          + (f", {len(audit_reports)} compiled artifact(s) audited"
             if audit_reports is not None else ""))
    if audit_failure is not None:
        _error_line("hlo-audit", audit_failure)
    if race_report is not None and race_failure is None:
        print(f"tmlint: race-audit: seeded race detected in "
              f"{race_report['racy_lost_updates']}/"
              f"{race_report['orderings']} orderings; guarded twin clean")
    if race_failure is not None:
        _error_line("race-audit", race_failure)

    if args.report:
        report = core.build_report(
            findings, n_files,
            sorted(core.all_rules()) if rule_names is None else rule_names)
        if audit_reports is not None:
            report["hlo_audit"] = audit_reports
        if audit_failure is not None:
            report["hlo_audit_error"] = audit_failure
        if race_report is not None:
            report["race_audit"] = race_report
        if race_failure is not None:
            report["race_audit_error"] = race_failure
        try:
            core.write_report(report, args.report)
        except OSError as e:
            _error_line("report", e)
            return 2
        if not args.quiet:
            print(f"tmlint: report written to {args.report}")

    return 1 if active or audit_failure or race_failure else 0


if __name__ == "__main__":
    raise SystemExit(main())
