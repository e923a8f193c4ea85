"""tmlint subsystem tests (ISSUE 7): per-rule fixtures, suppression
grammar, the declared layer DAG, CLI exit contract, JSON report schema,
and THE tier-1 acceptance: the full rule set runs clean over the package.

Fixture style: each rule gets synthetic sources asserting both the
firing and the non-firing case — the rule must catch its bug class AND
must not cry wolf on the idioms the repo actually uses (the conditional
``a = a.copy()`` ownership check, consumed-by-call asarray, early-return
guards above a rebinding, lazy cycle-breaking imports).
"""

import json
import os

import pytest

from theanompi_tpu.analysis import cli, core
from theanompi_tpu.analysis import layers as L

# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------


def run_src(tmp_path, source, rules=None, rel="fx.py"):
    """Lint one synthetic source; -> (unsuppressed, suppressed) lists."""
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(source)
    findings, _ = core.lint_paths([str(path)], rules, root=str(tmp_path))
    return ([f for f in findings if not f.suppressed],
            [f for f in findings if f.suppressed])


# ---------------------------------------------------------------------------
# the tier-1 acceptance: the whole package is clean
# ---------------------------------------------------------------------------


def test_package_runs_clean_under_the_full_rule_set():
    """Zero unsuppressed findings over theanompi_tpu/ with
    every registered rule on — the ISSUE 7 acceptance criterion.  Every
    suppression in the tree must carry its justification (the meta rule
    fires otherwise and shows up right here)."""
    findings, n_files = core.lint_paths()
    offenders = [f.format() for f in findings if not f.suppressed]
    assert n_files > 70, f"suspiciously small scan: {n_files}"
    assert not offenders, "tmlint findings in the tree:\n" + \
        "\n".join(offenders)


def test_default_path_set_is_the_package(tmp_path):
    """With no path arguments tmlint scans the package and nothing beside
    it: a root script is linted only when named."""
    pkg = tmp_path / "theanompi_tpu" / "sub"
    pkg.mkdir(parents=True)
    (pkg / "m.py").write_text("x = 1\n")
    (tmp_path / "theanompi_tpu" / "__init__.py").write_text("")
    (tmp_path / "root_script.py").write_text("x = 1\n")
    rels = [os.path.relpath(p, str(tmp_path))
            for p in core.default_paths(str(tmp_path))]
    assert rels == ["theanompi_tpu/__init__.py", "theanompi_tpu/sub/m.py"]
    assert all(os.path.relpath(p, core.REPO_ROOT).startswith("theanompi_tpu/")
               for p in core.default_paths())


def test_registry_has_the_advertised_rules():
    names = set(core.all_rules())
    assert {"wall", "swallow", "np-load", "donated-escape", "host-sync",
            "jit-nondet", "exit-code", "import-dag",
            "data-determinism", "atomic-publish", "guarded-state",
            "thread-lifecycle", "lock-order"} <= names


# ---------------------------------------------------------------------------
# suppression grammar
# ---------------------------------------------------------------------------


def test_suppression_requires_justification(tmp_path):
    active, sup = run_src(
        tmp_path, "import time\nt = time.time()  # lint: wall-ok\n")
    assert any(f.rule == "suppression" for f in active)
    assert any(f.rule == "wall" for f in active)  # bare marker = no effect
    assert not sup


def test_suppression_with_justification_is_recorded_not_silent(tmp_path):
    active, sup = run_src(
        tmp_path,
        "import time\nt = time.time()  # lint: wall-ok — epoch stamp\n")
    assert not active
    assert len(sup) == 1 and sup[0].justification == "epoch stamp"


def test_suppression_unknown_rule_is_a_finding(tmp_path):
    active, _ = run_src(
        tmp_path, "x = 1  # lint: no-such-rule-ok — because\n")
    assert any(f.rule == "suppression" and "unknown rule" in f.message
               for f in active)


def test_suppression_on_comment_block_above_counts(tmp_path):
    active, sup = run_src(
        tmp_path,
        "import time\n"
        "# lint: wall-ok — the long call below needs a stamp\n"
        "t = time.time()\n")
    assert not active and len(sup) == 1


def test_prose_mention_of_the_grammar_is_not_a_marker(tmp_path):
    """'use lint: wall-ok' mid-comment (or in a docstring) must neither
    suppress nor trip the meta rule — only a marker STARTING its comment
    counts (review fix)."""
    active, sup = run_src(
        tmp_path,
        '"""Docs may say lint: wall-ok freely."""\n'
        "import time\n"
        "t = time.perf_counter()  # to opt out, use lint: wall-ok\n"
        "w = time.time()  # silenceable via lint: wall-ok — but not here\n")
    assert not sup, sup  # the prose on line 4 does NOT suppress the wall hit
    rules_hit = {f.rule for f in active}
    assert rules_hit == {"wall"}, active  # and no `suppression` meta noise


def test_deselected_rules_still_get_marker_grammar_checks(tmp_path):
    """`--rules wall` must not hide a broken swallow-ok marker."""
    path = tmp_path / "fx.py"
    path.write_text("try:\n    x = 1\nexcept Exception:  "
                    "# lint: swallow-ok\n    pass\n")
    findings, _ = core.lint_paths([str(path)], ["wall"],
                                  root=str(tmp_path))
    assert any(f.rule == "suppression" for f in findings)


# ---------------------------------------------------------------------------
# donated-escape
# ---------------------------------------------------------------------------


def test_donated_escape_fires_on_returned_view(tmp_path):
    active, _ = run_src(
        tmp_path,
        "import numpy as np\ndef f(x):\n    return np.asarray(x)\n",
        ["donated-escape"])
    assert len(active) == 1 and active[0].rule == "donated-escape"


def test_donated_escape_fires_on_queue_and_thread_handoff(tmp_path):
    active, _ = run_src(
        tmp_path,
        "import numpy as np\n"
        "def f(q, x, y):\n"
        "    q.put((1, np.asarray(x)))\n"
        "    a = np.asarray(y)\n"
        "    q.put(a)\n",
        ["donated-escape"])
    assert len(active) == 2, active


def test_donated_escape_respects_copy_and_the_ownership_idiom(tmp_path):
    active, _ = run_src(
        tmp_path,
        "import numpy as np\n"
        "def direct(x):\n"
        "    return np.asarray(x).copy()\n"
        "def wrapped(x):\n"
        "    return g(np.broadcast_to(np.asarray(x), (2, 3)).copy())\n"
        "def conditional(v):\n"
        "    a = np.asarray(v)\n"
        "    if a.base is not None or not a.flags.owndata:\n"
        "        a = a.copy()\n"
        "    return a\n",
        ["donated-escape"])
    assert not active, active


def test_donated_escape_ignores_consumed_views_and_early_returns(tmp_path):
    """np.percentile(arr) returns derived data; `return x` ABOVE the
    rebinding returns the original object (the put_global regression)."""
    active, _ = run_src(
        tmp_path,
        "import numpy as np\n"
        "def pct(xs):\n"
        "    arr = np.asarray(xs)\n"
        "    return float(np.percentile(arr, 50))\n"
        "def put(x, sharding):\n"
        "    if ready(x):\n"
        "        return x\n"
        "    x = np.asarray(x)\n"
        "    return device_put(x, sharding)\n",
        ["donated-escape"])
    assert not active, active


def test_donated_escape_fires_on_attribute_store(tmp_path):
    active, _ = run_src(
        tmp_path,
        "import numpy as np\n"
        "def f(self, x):\n"
        "    a = np.asarray(x)\n"
        "    self.snapshot = a\n",
        ["donated-escape"])
    assert len(active) == 1, active


# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------

SPAN_SRC = """\
import numpy as np
def f(tel, x):
    with tel.span("train.step"):
        v = float(x)
    return v
def g(tel, x):
    with tel.span("validate"):
        acc = []
        acc.append(x)
    return float(np.asarray(x).mean())
def h(tel, x):
    s = tel.span("decode")
    with s:
        return x.item()
def cond(tel, x, nullcontext):
    with (tel.span("snap") if tel else nullcontext()):
        return np.asarray(x)
"""


def test_host_sync_fires_only_inside_spans(tmp_path):
    active, _ = run_src(tmp_path, SPAN_SRC, ["host-sync"])
    lines = sorted(f.line for f in active)
    # f: float inside span (4); g: pulls AFTER the span are clean;
    # h: .item() under a span-bound name (14); cond: asarray under the
    # conditional-span idiom (17)
    assert lines == [4, 14, 17], active


def test_host_sync_is_a_warning_and_suppressible(tmp_path):
    active, sup = run_src(
        tmp_path,
        "def f(tel, x):\n"
        "    with tel.span('serve.prefill'):\n"
        "        # lint: host-sync-ok — span measures execution by design\n"
        "        return float(x)\n",
        ["host-sync"])
    assert not active and len(sup) == 1
    assert sup[0].severity == "warning"


# ---------------------------------------------------------------------------
# jit-nondet
# ---------------------------------------------------------------------------

JIT_SRC = """\
import time
import numpy as np
import jax

@jax.jit
def decorated(x):
    return x * time.time()

def _impl(x):
    return x + np.random.randn()

step = jax.jit(_impl)

def host_side():
    return time.time()  # wall rule's business, not jit-nondet's

@jax.jit
def seeded_ok(x):
    rng = np.random.RandomState(0)
    return x
"""


def test_jit_nondet_fires_in_jitted_functions_only(tmp_path):
    active, _ = run_src(tmp_path, JIT_SRC, ["jit-nondet"])
    lines = sorted(f.line for f in active)
    assert lines == [7, 10], active  # decorated + jax.jit(_impl) form


def test_jit_nondet_guards_the_fault_plan_module(tmp_path):
    active, _ = run_src(
        tmp_path,
        "import numpy as np\n"
        "def plan():\n"
        "    return np.random.rand()\n",
        ["jit-nondet"],
        rel="theanompi_tpu/resilience/faults.py")
    assert len(active) == 1 and "fault plan" in active[0].message


def test_jit_nondet_flags_unseeded_constructors(tmp_path):
    active, _ = run_src(
        tmp_path,
        "import numpy as np\n"
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    a = np.random.default_rng()\n"
        "    b = np.random.default_rng(42)\n"
        "    return x\n",
        ["jit-nondet"])
    assert len(active) == 1 and "no seed" in active[0].message


# ---------------------------------------------------------------------------
# exit-code
# ---------------------------------------------------------------------------


def test_exit_code_fires_in_exit_contexts_only(tmp_path):
    active, _ = run_src(
        tmp_path,
        "import sys\n"
        "def f(rc):\n"
        "    if rc == 77:\n"
        "        sys.exit(75)\n"
        "    raise SystemExit(78)\n"
        "def not_an_exit_code():\n"
        "    width = 77\n"
        "    return width + 75\n",
        ["exit-code"])
    lines = sorted(f.line for f in active)
    assert lines == [3, 4, 5], active


def test_exit_code_source_module_is_exempt(tmp_path):
    active, _ = run_src(
        tmp_path,
        "EXIT_PREEMPTED = 75\nassert EXIT_PREEMPTED == 75\n",
        ["exit-code"],
        rel="theanompi_tpu/resilience/codes.py")
    assert not active, active


# ---------------------------------------------------------------------------
# data-determinism (ISSUE 10)
# ---------------------------------------------------------------------------

_DATA_REL = "theanompi_tpu/models/data/fx.py"


def test_data_determinism_fires_only_under_models_data(tmp_path):
    src = ("import numpy as np\n"
           "def order():\n"
           "    return np.random.permutation(8)\n")
    active, _ = run_src(tmp_path, src, ["data-determinism"], rel=_DATA_REL)
    assert len(active) == 1, active
    assert "np.random.permutation()" in active[0].message
    assert active[0].severity == "error"
    # the same draw OUTSIDE the data plane is this rule's non-concern
    # (jit-nondet still owns jitted scopes there)
    elsewhere, _ = run_src(tmp_path, src, ["data-determinism"],
                           rel="theanompi_tpu/parallel/fx.py")
    assert not elsewhere, elsewhere


def test_data_determinism_allows_derive_seed_keyed_randomstate(tmp_path):
    """The repo's sanctioned idiom — a RandomState keyed on
    derive_seed(..., epoch, position) — must pass untouched."""
    active, _ = run_src(
        tmp_path,
        "import numpy as np\n"
        "from theanompi_tpu.models.data.base import derive_seed\n"
        "def order(seed, epoch):\n"
        "    rng = np.random.RandomState("
        "derive_seed('shuffle', seed, epoch))\n"
        "    return rng.permutation(8)\n",
        ["data-determinism"], rel=_DATA_REL)
    assert not active, active


def test_data_determinism_flags_unseeded_ctor_and_bare_random(tmp_path):
    """An unseeded RandomState(), global random.seed() and a bare
    random.random() draw are all order-dependent state a checkpoint
    cannot capture — each is its own finding."""
    active, _ = run_src(
        tmp_path,
        "import numpy as np\n"
        "import random\n"
        "def f():\n"
        "    rng = np.random.RandomState()\n"
        "    random.seed(0)\n"
        "    return rng, random.random()\n",
        ["data-determinism"], rel=_DATA_REL)
    lines = sorted(f.line for f in active)
    assert lines == [4, 5, 6], active
    assert any("no seed" in f.message for f in active)


# ---------------------------------------------------------------------------
# import-dag
# ---------------------------------------------------------------------------


def test_layer_dag_declaration_is_acyclic_by_construction():
    L.validate_dag()  # raises on forward refs / duplicates
    # spot-check the load-bearing assignments
    assert L.module_layer("theanompi_tpu.resilience.codes") == "codes"
    assert L.module_layer("theanompi_tpu.resilience.faults") == "resilience"
    assert L.module_layer("theanompi_tpu.telemetry.core") == "telemetry"
    assert L.module_layer("theanompi_tpu.parallel.mesh") == "mesh"
    assert L.module_layer("theanompi_tpu.parallel.trainer") == "training"
    assert L.module_layer("theanompi_tpu.serving.engine") == "serving"
    assert L.module_layer("theanompi_tpu.launcher") == "tooling"
    assert L.module_layer("theanompi_tpu.analysis.cli") == "analysis"


def test_layer_dag_rejects_forward_references(monkeypatch):
    bad = (("a", ("theanompi_tpu.a",), ("b",)),
           ("b", ("theanompi_tpu.b",), ()))
    monkeypatch.setattr(L, "LAYER_DAG", bad)
    with pytest.raises(ValueError, match="acyclic"):
        L.validate_dag()


def test_import_dag_flags_module_level_layer_violation(tmp_path):
    """telemetry is the bottom layer: a module-level mesh import fires."""
    active, _ = run_src(
        tmp_path,
        "from theanompi_tpu.parallel.mesh import DATA_AXIS\n",
        ["import-dag"],
        rel="theanompi_tpu/telemetry/bad.py")
    assert any("leaf subpackage" in f.message or "allowed set" in f.message
               for f in active), active


def test_import_dag_checks_class_body_imports(tmp_path):
    """A class-body import executes at module import time — it must obey
    the layering like any top-level import (review fix)."""
    active, _ = run_src(
        tmp_path,
        "class Sneaky:\n"
        "    from theanompi_tpu.parallel.mesh import DATA_AXIS\n",
        ["import-dag"],
        rel="theanompi_tpu/telemetry/bad.py")
    assert active, "class-body import-time dependency not checked"


def test_import_dag_allows_lazy_cycle_breaking_imports(tmp_path):
    """A function-local upward import is a deliberate lazy edge (the
    ops/opt.py idiom) — layering ignores it; only walls check deep."""
    active, _ = run_src(
        tmp_path,
        "def late():\n"
        "    from theanompi_tpu.parallel.trainer import BaseTrainer\n"
        "    return BaseTrainer\n",
        ["import-dag"],
        rel="theanompi_tpu/models/helper.py")
    assert not active, active


def test_import_dag_wall_catches_lazy_serving_import(tmp_path):
    active, _ = run_src(
        tmp_path,
        "def late():\n"
        "    from theanompi_tpu.parallel import exchanger\n"
        "    return exchanger\n",
        ["import-dag"],
        rel="theanompi_tpu/serving/bad.py")
    assert any("training machinery" in f.message for f in active), active


# ---------------------------------------------------------------------------
# CLI exit contract + JSON report schema
# ---------------------------------------------------------------------------

import os

VIOLATION_FIXTURE = os.path.join(core.REPO_ROOT, "tests", "fixtures",
                                 "tmlint_violation.py")


def test_cli_exits_nonzero_on_the_seeded_violation_file(capsys):
    rc = cli.main([VIOLATION_FIXTURE])
    out = capsys.readouterr().out
    assert rc == 1
    for rule in ("wall", "swallow", "np-load", "donated-escape",
                 "exit-code", "suppression", "atomic-publish",
                 "thread-lifecycle"):
        assert f"[{rule}]" in out, f"seeded {rule} violation not caught"


def test_cli_exit_contract(tmp_path, capsys):
    assert cli.main(["--rules", "bogus"]) == 2
    err = capsys.readouterr().err
    assert err.startswith("tmlint: error:") and err.count("\n") == 1

    assert cli.main([str(tmp_path / "missing.py")]) == 2

    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    assert cli.main([str(clean)]) == 0

    assert cli.main(["--no-such-flag"]) == 2  # argparse's own contract


def test_cli_report_schema(tmp_path, capsys):
    """The JSON artifact schema the runbook step publishes (LINT.json):
    version/tool/summary + per-finding keys, suppressed entries carrying
    their justification."""
    report_path = tmp_path / "LINT.json"
    rc = cli.main([VIOLATION_FIXTURE, "--report", str(report_path),
                   "--quiet"])
    assert rc == 1
    rep = json.loads(report_path.read_text())
    assert rep["version"] == 1 and rep["tool"] == "tmlint"
    assert rep["files_scanned"] == 1
    assert {r["name"] for r in rep["rules"]} == set(core.all_rules())
    for r in rep["rules"]:
        assert set(r) == {"name", "severity", "description"}
    assert rep["findings"], "seeded violations missing from the report"
    for f in rep["findings"]:
        assert set(f) == {"rule", "severity", "path", "line", "col",
                          "message", "suppressed"}
        assert f["suppressed"] is False
        assert isinstance(f["line"], int) and f["line"] > 0
    for f in rep["suppressed"]:
        assert f["suppressed"] is True and f["justification"]
    s = rep["summary"]
    assert s["errors"] == sum(f["severity"] == "error"
                              for f in rep["findings"])
    assert s["suppressed"] == len(rep["suppressed"])


def test_cli_clean_package_report(tmp_path):
    """tmlint over the package writes a findings-free report and exits 0
    — the exact runbook invocation (BASELINE.md)."""
    report_path = tmp_path / "LINT.json"
    rc = cli.main(["--report", str(report_path), "--quiet"])
    assert rc == 0
    rep = json.loads(report_path.read_text())
    assert rep["findings"] == []
    assert rep["summary"]["errors"] == 0
    assert rep["summary"]["suppressed"] > 0  # justified markers, visible


# ---------------------------------------------------------------------------
# the concurrency tier (ISSUE 15): atomic-publish / guarded-state /
# thread-lifecycle / lock-order
# ---------------------------------------------------------------------------

from theanompi_tpu.analysis import rules as R


def test_atomic_publish_flags_direct_write(tmp_path):
    active, _ = run_src(tmp_path, (
        "import json\n"
        "def publish(path, obj):\n"
        "    with open(path, 'w') as f:\n"
        "        json.dump(obj, f)\n"), rules=["atomic-publish"])
    assert [f.rule for f in active] == ["atomic-publish"]
    assert "os.replace" in active[0].message


def test_atomic_publish_flags_append_mode(tmp_path):
    active, _ = run_src(tmp_path, (
        "def log(path, line):\n"
        "    with open(path, 'a') as f:\n"
        "        f.write(line)\n"), rules=["atomic-publish"])
    assert [f.rule for f in active] == ["atomic-publish"]
    assert "torn tail" in active[0].message


def test_atomic_publish_append_suppressible_with_justification(tmp_path):
    active, sup = run_src(tmp_path, (
        "def log(path, line):\n"
        "    # lint: atomic-publish-ok — JSONL, readers skip torn tails\n"
        "    with open(path, 'a') as f:\n"
        "        f.write(line)\n"), rules=["atomic-publish"])
    assert not active
    assert [f.rule for f in sup] == ["atomic-publish"]


def test_atomic_publish_flags_unpublished_tmp(tmp_path):
    active, _ = run_src(tmp_path, (
        "def publish(path, data):\n"
        "    with open(path + '.tmp', 'w') as f:\n"
        "        f.write(data)\n"), rules=["atomic-publish"])
    assert [f.rule for f in active] == ["atomic-publish"]
    assert "never published" in active[0].message


def test_atomic_publish_accepts_the_idiom(tmp_path):
    # direct-constant tmp suffix, name bound to a tmp expr, and f-string
    # tmp — the three spellings the package actually uses
    active, _ = run_src(tmp_path, (
        "import json, os\n"
        "def publish(path, obj):\n"
        "    with open(path + '.tmp', 'w') as f:\n"
        "        json.dump(obj, f)\n"
        "    os.replace(path + '.tmp', path)\n"
        "def publish2(path, obj):\n"
        "    tmp = path + '.tmp'\n"
        "    with open(tmp, 'w') as f:\n"
        "        json.dump(obj, f)\n"
        "    os.replace(tmp, path)\n"
        "def publish3(path, obj):\n"
        "    tmp = f'{path}.tmp.{os.getpid()}'\n"
        "    with open(tmp, 'w') as f:\n"
        "        json.dump(obj, f)\n"
        "    os.replace(tmp, path)\n"), rules=["atomic-publish"])
    assert not active


def test_atomic_publish_ignores_reads_and_dynamic_modes(tmp_path):
    active, _ = run_src(tmp_path, (
        "def load(path, mode):\n"
        "    with open(path) as f:\n"
        "        a = f.read()\n"
        "    with open(path, 'r+b') as f:\n"
        "        b = f.read()\n"
        "    with open(path, mode) as f:\n"  # statically unknown: skip
        "        c = f.read()\n"
        "    return a, b, c\n"), rules=["atomic-publish"])
    assert not active


GUARDED_MIXED = """
import threading

class Registry:
    def __init__(self):
        self._lock = threading.Lock()
        self.entries = {}

    def register(self, e):
        with self._lock:
            self.entries = e

    def reset(self):
        self.entries = None
"""


def test_guarded_state_flags_mixed_assignment(tmp_path):
    active, _ = run_src(tmp_path, GUARDED_MIXED, rules=["guarded-state"])
    assert [f.rule for f in active] == ["guarded-state"]
    assert "entries" in active[0].message
    # the flagged site is the UNGUARDED one (reset), not register
    assert active[0].line == GUARDED_MIXED.splitlines().index(
        "        self.entries = None") + 1


def test_guarded_state_init_is_exempt(tmp_path):
    active, _ = run_src(tmp_path, (
        "import threading\n"
        "class Box:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.v = 0\n"
        "    def set(self, v):\n"
        "        with self._lock:\n"
        "            self.v = v\n"), rules=["guarded-state"])
    assert not active


def test_guarded_state_ignores_lockless_classes(tmp_path):
    active, _ = run_src(tmp_path, (
        "class Plain:\n"
        "    def a(self):\n"
        "        self.v = 1\n"
        "    def b(self):\n"
        "        self.v = 2\n"), rules=["guarded-state"])
    assert not active


def test_guarded_state_helper_called_under_lock_counts_guarded(tmp_path):
    # the EventSink._rotate idiom: a helper whose every call site holds
    # the lock assigns state without a lexical with — not a finding
    active, _ = run_src(tmp_path, (
        "import threading\n"
        "class Sink:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.size = 0\n"
        "    def emit(self, n):\n"
        "        with self._lock:\n"
        "            self.size += n\n"
        "            if self.size > 10:\n"
        "                self._rotate()\n"
        "    def _rotate(self):\n"
        "        self.size = 0\n"), rules=["guarded-state"])
    assert not active


def test_guarded_state_helper_with_unlocked_call_site_fires(tmp_path):
    # one call site outside the lock disqualifies the helper — ambiguity
    # is the bug
    active, _ = run_src(tmp_path, (
        "import threading\n"
        "class Sink:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.size = 0\n"
        "    def emit(self, n):\n"
        "        with self._lock:\n"
        "            self.size += n\n"
        "            self._rotate()\n"
        "    def close(self):\n"
        "        self._rotate()\n"
        "    def _rotate(self):\n"
        "        self.size = 0\n"), rules=["guarded-state"])
    assert [f.rule for f in active] == ["guarded-state"]


def test_thread_lifecycle_flags_unnamed_thread(tmp_path):
    active, _ = run_src(tmp_path, (
        "import threading\n"
        "def go(fn):\n"
        "    t = threading.Thread(target=fn, daemon=True)\n"
        "    t.start()\n"
        "    return t\n"), rules=["thread-lifecycle"])
    assert [f.rule for f in active] == ["thread-lifecycle"]
    assert "unnamed" in active[0].message


def test_thread_lifecycle_accepts_named_daemon(tmp_path):
    active, _ = run_src(tmp_path, (
        "import threading\n"
        "def go(fn):\n"
        "    t = threading.Thread(target=fn, name='seam', daemon=True)\n"
        "    t.start()\n"
        "    return t\n"), rules=["thread-lifecycle"])
    assert not active


def test_thread_lifecycle_nondaemon_needs_a_join(tmp_path):
    src = (
        "import threading\n"
        "import os\n"
        "def go(fn, d):\n"
        "    p = os.path.join(d, 'x')\n"  # not a thread join
        "    t = threading.Thread(target=fn, name='seam')\n"
        "    t.start()\n"
        "    return t, p\n")
    active, _ = run_src(tmp_path, src, rules=["thread-lifecycle"])
    assert [f.rule for f in active] == ["thread-lifecycle"]
    assert "non-daemon" in active[0].message
    active, _ = run_src(tmp_path, src + (
        "def wait(t):\n"
        "    t.join()\n"), rules=["thread-lifecycle"])
    assert not active


def test_lock_order_dag_declaration_is_valid():
    R.validate_lock_order()  # the shipped declaration must parse


def test_lock_order_rejects_forward_references():
    with pytest.raises(ValueError):
        R.validate_lock_order((
            ("outer", ("pkg/a.py", "_lock"), ("inner",), False),
            ("inner", ("pkg/b.py", "_lock"), (), False),
        ))


_TEST_LOCK_DAG = (
    # prefix matches run_src's synthetic fixture path
    ("inner", ("fx.py", "_inner"), (), False),
    ("outer", ("fx.py", "_outer"), ("inner",), True),
)


def test_lock_order_flags_undeclared_nesting(tmp_path, monkeypatch):
    monkeypatch.setattr(R, "LOCK_ORDER_DAG", _TEST_LOCK_DAG)
    active, _ = run_src(tmp_path, (
        "class C:\n"
        "    def bad(self):\n"
        "        with self._inner:\n"
        "            with self._outer:\n"  # inner->outer: not declared
        "                pass\n"), rules=["lock-order"])
    assert [f.rule for f in active] == ["lock-order"]
    assert "LOCK_ORDER_DAG" in active[0].message


def test_lock_order_accepts_declared_nesting(tmp_path, monkeypatch):
    monkeypatch.setattr(R, "LOCK_ORDER_DAG", _TEST_LOCK_DAG)
    active, _ = run_src(tmp_path, (
        "class C:\n"
        "    def ok(self):\n"
        "        with self._outer:\n"
        "            with self._inner:\n"
        "                pass\n"
        "    def multi(self):\n"
        "        with self._outer, self._inner:\n"  # left-to-right
        "            pass\n"), rules=["lock-order"])
    assert not active


def test_lock_order_multi_item_with_is_ordered(tmp_path, monkeypatch):
    monkeypatch.setattr(R, "LOCK_ORDER_DAG", _TEST_LOCK_DAG)
    active, _ = run_src(tmp_path, (
        "class C:\n"
        "    def bad(self):\n"
        "        with self._inner, self._outer:\n"
        "            pass\n"), rules=["lock-order"])
    assert [f.rule for f in active] == ["lock-order"]


def test_lock_order_self_deadlock_vs_reentrant(tmp_path, monkeypatch):
    monkeypatch.setattr(R, "LOCK_ORDER_DAG", _TEST_LOCK_DAG)
    active, _ = run_src(tmp_path, (
        "class C:\n"
        "    def bad(self):\n"
        "        with self._inner:\n"
        "            with self._inner:\n"  # non-reentrant: deadlock
        "                pass\n"
        "    def ok(self):\n"
        "        with self._outer:\n"
        "            with self._outer:\n"  # declared reentrant (RLock)
        "                pass\n"), rules=["lock-order"])
    assert len(active) == 1
    assert "self-deadlock" in active[0].message


def test_lock_order_nested_def_resets_lexical_scope(tmp_path, monkeypatch):
    # a closure defined inside a with-block runs on its caller's
    # schedule, not under the enclosing lock — no finding
    monkeypatch.setattr(R, "LOCK_ORDER_DAG", _TEST_LOCK_DAG)
    active, _ = run_src(tmp_path, (
        "class C:\n"
        "    def ok(self):\n"
        "        with self._inner:\n"
        "            def cb():\n"
        "                with self._outer:\n"
        "                    pass\n"
        "            return cb\n"), rules=["lock-order"])
    assert not active
