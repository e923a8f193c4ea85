"""``tmprof`` — step-time attribution tables + the perf ledger (ISSUE 16).

Attribution mode (the default) re-derives the segment decomposition from
a telemetry directory's event files — the same numbers the in-process
:class:`~theanompi_tpu.telemetry.profile.StepAttributor` publishes to
``ATTRIB.json``, recomputed offline so the tool works on any recorded
run::

    tmprof ./telemetry                  # attribution table per rank
    tmprof ./telemetry --json           # machine-readable
    tmprof ./telemetry --write          # also (re)publish ATTRIB.json

Ledger mode drives ``TMPROF_LEDGER.jsonl`` (``telemetry/ledger.py``)::

    tmprof --ledger update SERVE.json ./telemetry/ATTRIB.json
    tmprof --ledger check               # exit 1 on any regression
    tmprof --ledger backfill .          # one-shot ingest of repo artifacts
    tmprof --ledger show                # per-metric trajectories

Exit contract (shared with ``tmhealth``/``tmlint`` — a read-mostly
reporting tool, not a party to the supervisor's 70/75–79 codes): ``0``
clean, ``1`` at least one problem (a regression verdict in ``--ledger
check``; an attribution whose unattributed host share exceeds half the
window — the stream is missing its spans), ``2`` usage error.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from theanompi_tpu.telemetry.ledger import (
    DEFAULT_TOLERANCE,
    DEFAULT_WINDOW,
    LEDGER_FILENAME,
    PerfLedger,
    read_ledger,
    regressions,
    trajectories,
)
from theanompi_tpu.telemetry.profile import (
    ATTRIB_FILENAME,
    attribute_events,
    format_attribution,
)

#: attribution-mode problem threshold: a majority-unattributed window
#: means the run's spans never made it into the stream
HOST_SHARE_LIMIT = 0.5


def _attribution(args) -> int:
    from theanompi_tpu.telemetry.aggregate import load_all_events

    if not os.path.isdir(args.directory):
        print(f"tmprof: error: no such directory: {args.directory}",
              file=sys.stderr)
        return 2
    events = load_all_events(args.directory)
    per_rank = attribute_events(events) if events else {}
    if not per_rank:
        # a finished run may have rotated its events away; the published
        # summary is then the only witness
        from theanompi_tpu.telemetry.profile import read_attrib

        attrib = read_attrib(args.directory)
        if attrib:
            per_rank = attrib.get("per_rank", {})
    if not per_rank:
        print(f"tmprof: error: no attributable events or "
              f"{ATTRIB_FILENAME} in {args.directory}", file=sys.stderr)
        return 2
    if args.write:
        payload = {"per_rank": per_rank}
        path = os.path.join(args.directory, ATTRIB_FILENAME)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1)
        os.replace(tmp, path)
    if args.as_json:
        print(json.dumps({"per_rank": per_rank}, indent=1))
    else:
        print(format_attribution(per_rank))
    worst_host = max((res["segments"].get("host", {}).get("share", 0.0)
                      for res in per_rank.values()), default=0.0)
    return 1 if worst_host > HOST_SHARE_LIMIT else 0


def _ledger(args) -> int:
    ledger = PerfLedger(args.ledger_path)
    if args.ledger == "update":
        paths = args.paths or ([args.directory] if args.directory else [])
        if not paths:
            print("tmprof: error: --ledger update needs artifact paths",
                  file=sys.stderr)
            return 2
        written = []
        for p in paths:
            if not os.path.exists(p):
                print(f"tmprof: error: no such artifact: {p}",
                      file=sys.stderr)
                return 2
            written.extend(ledger.ingest_artifact(p))
        ledger.snapshot(tolerance=args.tolerance)
        print(f"ingested {len(written)} new record(s) into "
              f"{args.ledger_path}")
        return 0
    if args.ledger == "backfill":
        root = args.directory or "."
        if not os.path.isdir(root):
            print(f"tmprof: error: no such directory: {root}",
                  file=sys.stderr)
            return 2
        written = ledger.backfill(root)
        ledger.snapshot(tolerance=args.tolerance)
        print(f"backfilled {len(written)} record(s) from {root} into "
              f"{args.ledger_path}")
        return 0
    records = read_ledger(args.ledger_path)
    if not records:
        print(f"tmprof: error: no ledger at {args.ledger_path}",
              file=sys.stderr)
        return 2
    if args.ledger == "show":
        if args.as_json:
            print(json.dumps(trajectories(records), indent=1))
        else:
            for metric, pts in sorted(trajectories(records).items()):
                vals = " -> ".join(f"{p['value']:g}" for p in pts[-6:])
                print(f"{metric:<48} [{len(pts)}] {vals}")
        return 0
    # check
    verdicts = ledger.check(tolerance=args.tolerance, window=args.window)
    bad = regressions(verdicts)
    if args.as_json:
        print(json.dumps({"verdicts": verdicts}, indent=1))
    else:
        for v in verdicts:
            if v["verdict"] == "insufficient_history" and not args.verbose:
                continue
            mark = {"ok": " ", "improvement": "+",
                    "regression": "X"}.get(v["verdict"], "?")
            delta = ("" if v["delta_pct"] is None
                     else f"  {v['delta_pct']:+.1f}% vs median "
                          f"{v['baseline']:g} (tol "
                          f"{v['tolerance_pct']:g}%)")
            print(f"[{mark}] {v['verdict']:<12} {v['metric']:<48} "
                  f"latest {v['latest']:g}{delta}")
        n_skip = sum(1 for v in verdicts
                     if v["verdict"] == "insufficient_history")
        if n_skip and not args.verbose:
            print(f"({n_skip} single-point metric(s) without history "
                  f"omitted; --verbose shows them)")
    return 1 if bad else 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="tmprof",
        description="Step-time attribution tables from a telemetry dir, "
                    "and the TMPROF_LEDGER.jsonl regression trajectory")
    p.add_argument("directory", nargs="?",
                   help="telemetry dir (attribution mode) or repo dir "
                        "(--ledger backfill)")
    p.add_argument("--ledger", choices=("update", "check", "backfill",
                                        "show"),
                   help="drive the perf ledger instead of attributing")
    p.add_argument("paths", nargs="*",
                   help="artifact JSONs for --ledger update")
    p.add_argument("--ledger-path", default=LEDGER_FILENAME,
                   help=f"ledger file (default ./{LEDGER_FILENAME})")
    p.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE,
                   help="relative regression tolerance (default 0.10)")
    p.add_argument("--window", type=int, default=DEFAULT_WINDOW,
                   help="trailing-median window (default 5)")
    p.add_argument("--write", action="store_true",
                   help="attribution mode: also publish ATTRIB.json")
    p.add_argument("--verbose", action="store_true",
                   help="--ledger check: include single-point metrics")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="machine-readable output")
    args = p.parse_args(argv)

    if args.ledger:
        return _ledger(args)
    if not args.directory:
        p.print_usage(sys.stderr)
        print("tmprof: error: a telemetry directory is required "
              "(or --ledger MODE)", file=sys.stderr)
        return 2
    return _attribution(args)


if __name__ == "__main__":
    sys.exit(main())
