"""Timing-discipline lint (ISSUE 1 satellite), now a tmlint shim (ISSUE 7).

The ad-hoc regex walker that lived here moved into the rule registry as
``theanompi_tpu/analysis/rules.py::WallClockRule`` — this file keeps the
original test name green (bisectability) and proves the ported rule
still catches the negative case it was born from.  Coverage is the rule
engine's default path set: the whole package (serving/ and resilience/
included).
"""

from theanompi_tpu.analysis import core


def test_no_wall_clock_in_timed_paths():
    """No unsuppressed ``time.time()`` anywhere tmlint scans — durations
    use ``time.perf_counter()``; genuine wall-clock stamps carry a
    justified ``lint: wall-ok`` marker."""
    findings, n_files = core.lint_paths(rule_names=["wall"])
    offenders = [f.format() for f in findings
                 if f.rule == "wall" and not f.suppressed]
    assert n_files > 70, f"suspiciously small scan: {n_files} files"
    assert not offenders, (
        "time.time() in timed paths — use time.perf_counter() for "
        "durations (or mark the line 'lint: wall-ok — <why>'):\n"
        + "\n".join(offenders))


def test_wall_rule_still_catches_the_original_negative_case(tmp_path):
    """The ported rule fires on a bare time.time() and honours a
    justified marker — the legacy lint's exact semantics."""
    bad = tmp_path / "bad.py"
    bad.write_text("import time\nt0 = time.time()\n")
    findings, _ = core.lint_paths([str(bad)], ["wall"], root=str(tmp_path))
    assert any(f.rule == "wall" and not f.suppressed for f in findings)

    ok = tmp_path / "ok.py"
    ok.write_text("import time\n"
                  "t0 = time.perf_counter()\n"
                  "stamp = time.time()  # lint: wall-ok — run-id stamp\n")
    findings, _ = core.lint_paths([str(ok)], ["wall"], root=str(tmp_path))
    assert not [f for f in findings if not f.suppressed]
    assert [f for f in findings if f.suppressed]  # visible, not silent
