"""Error-path / import-wall / np.load lints (ISSUEs 4-6), now tmlint
shims (ISSUE 7).

The three AST walkers that lived here moved into the rule registry
(``swallow``, ``np-load``, and the serving wall generalized into the
``import-dag`` layer declaration in ``theanompi_tpu/analysis/layers.py``).
Each original test name stays green and re-proves its negative case
against the ported rule, so a bisect across the migration still lands on
the real culprit.
"""

from theanompi_tpu.analysis import core
from theanompi_tpu.analysis.layers import SERVING_FORBIDDEN_IMPORTS
from theanompi_tpu.analysis.rules import (
    NP_LOAD_ALLOWED_PREFIXES,
    SWALLOW_ALLOWLIST,
)

REPO = core.REPO_ROOT


def _unsuppressed(findings, rule):
    return [f.format() for f in findings
            if f.rule == rule and not f.suppressed]


def test_no_exception_swallowing_in_package_error_paths():
    findings, _ = core.lint_paths(rule_names=["swallow"])
    offenders = _unsuppressed(findings, "swallow")
    assert not offenders, (
        "exception swallowing in package error paths — the resilience "
        "layer needs failures to propagate (re-raise, stash for deferred "
        "delivery, narrow the type, or mark the line 'lint: swallow-ok — "
        "<why>'):\n" + "\n".join(offenders))


def test_swallow_rule_still_catches_the_original_negative_cases(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "def f():\n"
        "    try:\n"
        "        work()\n"
        "    except:\n"
        "        pass\n"
        "def g():\n"
        "    try:\n"
        "        work()\n"
        "    except Exception:\n"
        "        log('oops')\n"
        "def h():\n"
        "    try:\n"
        "        work()\n"
        "    except Exception as e:\n"
        "        self._err = e\n")
    findings, _ = core.lint_paths([str(bad)], ["swallow"],
                                  root=str(tmp_path))
    lines = sorted(f.line for f in findings if not f.suppressed)
    assert lines == [4, 9], findings  # bare+pass at 4, broad swallow at 9
    # h()'s deferred-stash pattern stays allowed


def test_swallow_allowlist_still_names_the_documented_sites():
    """The exempt (file, function) pairs moved into the rule; the two
    teardown sites and the CLI mains must stay exactly the documented
    set — growth here needs review, not drift."""
    assert ("theanompi_tpu/parallel/trainer.py", "run") in SWALLOW_ALLOWLIST
    assert ("theanompi_tpu/parallel/trainer.py", "wait") in SWALLOW_ALLOWLIST
    assert ("theanompi_tpu/launcher.py", "main") in SWALLOW_ALLOWLIST
    # main's init/training half, split out in ISSUE 21 (same handlers)
    assert ("theanompi_tpu/launcher.py", "_run_session") in SWALLOW_ALLOWLIST
    assert ("theanompi_tpu/serving/cli.py", "main") in SWALLOW_ALLOWLIST
    assert ("theanompi_tpu/analysis/cli.py", "main") in SWALLOW_ALLOWLIST
    assert ("theanompi_tpu/fleet/cli.py", "main") in SWALLOW_ALLOWLIST
    assert ("theanompi_tpu/router/cli.py", "main") in SWALLOW_ALLOWLIST
    assert len(SWALLOW_ALLOWLIST) == 8


def test_faultinject_marker_registered():
    """The marker the fault-plan tests carry must stay registered, or a
    future `--strict-markers` run (and `-m faultinject` selection) breaks."""
    import pathlib

    pyproject = (pathlib.Path(REPO) / "pyproject.toml").read_text()
    assert "faultinject:" in pyproject


def test_serving_never_imports_training_paths():
    """The serving package is a consumer: no trainer, exchanger, optimizer,
    or supervisor imports anywhere under ``theanompi_tpu/serving/`` — now
    the any-depth wall of the ``import-dag`` rule (the wall list itself is
    asserted so a layers.py edit can't silently drop an entry)."""
    for mod in ("theanompi_tpu.parallel.trainer",
                "theanompi_tpu.parallel.exchanger",
                "theanompi_tpu.ops.opt",
                "theanompi_tpu.resilience.supervisor"):
        assert mod in SERVING_FORBIDDEN_IMPORTS
    findings, _ = core.lint_paths(rule_names=["import-dag"])
    offenders = _unsuppressed(findings, "import-dag")
    assert not offenders, (
        "package layering violated (serving wall / declared DAG):\n"
        + "\n".join(offenders))


def test_fleet_wall_names_the_supervised_machinery():
    """The mirror half of the serving ⊥ fleet wall (ISSUE 11): the fleet
    supervises the launcher/trainer as SUBPROCESSES and must never import
    them (even lazily) — the wall list itself is asserted so a layers.py
    edit can't silently drop an entry.  The clean run rides the
    import-dag check above."""
    from theanompi_tpu.analysis.layers import FLEET_FORBIDDEN_IMPORTS

    for mod in ("theanompi_tpu.serving", "theanompi_tpu.parallel",
                "theanompi_tpu.models", "theanompi_tpu.ops",
                "theanompi_tpu.launcher"):
        assert mod in FLEET_FORBIDDEN_IMPORTS
    assert "theanompi_tpu.fleet" in SERVING_FORBIDDEN_IMPORTS


def test_serving_wall_still_catches_the_original_negative_case(tmp_path):
    """A lazy (function-local) trainer import inside serving/ must fire:
    the wall holds at ANY depth, unlike the module-level-only layering."""
    pkg = tmp_path / "theanompi_tpu" / "serving"
    pkg.mkdir(parents=True)
    bad = pkg / "bad.py"
    bad.write_text(
        "def sneak():\n"
        "    from theanompi_tpu.parallel.trainer import BaseTrainer\n"
        "    return BaseTrainer\n")
    findings, _ = core.lint_paths([str(bad)], ["import-dag"],
                                  root=str(tmp_path))
    assert any("training machinery" in f.message for f in findings
               if not f.suppressed), findings


def test_serving_has_no_np_load_allowance():
    """Serving reads checkpoint bytes ONLY through the verified loader:
    no ``serving/`` prefix may appear in the np.load allowlist."""
    assert not any(p.startswith("theanompi_tpu/serving")
                   for p in NP_LOAD_ALLOWED_PREFIXES)


def test_checkpoint_npz_loads_confined_to_verified_loader():
    """No `np.load` outside the allowlist: new checkpoint-reading code is
    forced through `Checkpointer.load` / `load_latest_verified` /
    `verify_file`, where integrity verification lives."""
    findings, _ = core.lint_paths(rule_names=["np-load"])
    offenders = _unsuppressed(findings, "np-load")
    assert not offenders, (
        "np.load outside the verified checkpoint loader / dataset "
        "allowlist:\n" + "\n".join(offenders))


def test_np_load_rule_still_catches_the_original_negative_case(tmp_path):
    pkg = tmp_path / "theanompi_tpu" / "serving"
    pkg.mkdir(parents=True)
    bad = pkg / "bad.py"
    bad.write_text("import numpy as np\nd = np.load('ckpt.npz')\n")
    findings, _ = core.lint_paths([str(bad)], ["np-load"],
                                  root=str(tmp_path))
    assert _unsuppressed(findings, "np-load"), findings
