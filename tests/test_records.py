"""The repository's records and the documents that point at them (ISSUE 29).

``benchmarks/`` is the one measurement stack and ``PERF_LEDGER.jsonl`` /
``PERF.md`` the one speed record.  These tests keep a second one from
growing back: a stray JSON record at the root, a console script whose
target is gone, a document naming a module that no longer exists, or the
program's peak table drifting from the benchmark's.
"""

import importlib.util
import json
import os
import re
import subprocess
import sys
import tomllib

import pytest

from benchmarks.peaks import DEVICE_PEAKS as BENCH_PEAKS
from theanompi_tpu.telemetry.metrics import DEVICE_PEAKS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(rel: str) -> str:
    with open(os.path.join(REPO, rel)) as f:
        return f.read()


#: the ``*.json`` at the root: the benchmark's declaration, the
#: reference's targets, and the convergence evidence (not speed records)
ROOT_RECORDS = {"BENCHMARK.json", "BASELINE.json", "CONVERGE.json",
                "RULECOMP.json", "RULECOMP_r04.json", "RULECOMP_r05.json"}

SCRIPTS = tomllib.loads(_read("pyproject.toml"))["project"]["scripts"]


def _documented_modules() -> list[str]:
    """Every ``python -m theanompi_tpu.<module>`` and
    ``theanompi_tpu/<path>.py`` that README.md or BASELINE.md names."""
    found = set()
    for doc in ("README.md", "BASELINE.md"):
        text = _read(doc)
        found.update(re.findall(r"python3? -m (theanompi_tpu[.\w]*\w)", text))
        found.update(re.findall(r"\btheanompi_tpu/[\w/]+\.py\b", text))
    return sorted(found)


def test_root_json_files_are_the_named_records():
    ignored = set(_read(".gitignore").split())
    at_root = {n for n in os.listdir(REPO)
               if n.endswith(".json") and n not in ignored}
    assert at_root == ROOT_RECORDS, (
        "a JSON record at the repository root that is not one of the named "
        "ones: speed records live in PERF_LEDGER.jsonl / PERF.md, run "
        f"outputs in chiprun_out/ — {sorted(at_root ^ ROOT_RECORDS)}")


@pytest.mark.parametrize("name", sorted(SCRIPTS))
def test_console_script_resolves(name):
    """The entry point imports, is callable, and ``--help`` exits 0
    without opening a JAX backend (a platform no backend answers to makes
    any backend initialisation raise)."""
    module, func = SCRIPTS[name].split(":")
    assert callable(getattr(importlib.import_module(module), func))
    code = (f"import sys; from {module} import {func}; "
            f"sys.argv = [{name!r}, '--help']; {func}()")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=120,
        env={**os.environ, "PYTHONPATH": REPO,
             "JAX_PLATFORMS": "no_such_platform"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert "usage:" in out.stdout


@pytest.mark.parametrize("module", _documented_modules())
def test_documented_module_exists(module):
    if module.endswith(".py"):
        assert os.path.isfile(os.path.join(REPO, module)), module
    else:
        assert importlib.util.find_spec(module) is not None, module


def test_documented_tests_exist():
    """A document that cites ``tests/<file>.py[::test]`` as the lock of a
    claim cites a file, and a test in it, that exist."""
    missing = []
    for doc in ("README.md", "BASELINE.md"):
        refs = set(re.findall(r"\b(tests/[\w/]+\.py)(?:::(\w+))?", _read(doc)))
        for path, name in sorted(refs):
            if not os.path.isfile(os.path.join(REPO, path)):
                missing.append(f"{doc}: {path}")
            elif name and f"def {name}(" not in _read(path):
                missing.append(f"{doc}: {path}::{name}")
    assert not missing, missing


def test_readme_lists_every_cell():
    """README's "Chip numbers" table has one row per cell of
    ``BENCHMARK.json`` and no other: it is the one place outside PERF.md
    that quotes the ledger, so it follows the benchmark's cells."""
    cells = {w["name"] for w in json.loads(_read("BENCHMARK.json"))["workloads"]}
    section = _read("README.md").split("## Chip numbers")[1].split("\n## ")[0]
    rows = set(re.findall(r"^\| `([\w.]+)` \|", section, flags=re.M))
    assert rows == cells


#: names of the measurement stack deleted in PR 29; they may appear only
#: in the files that record history
STALE = re.compile(
    r"bench\.py|benchlib|utils[./]roofline|utils[./]scaling|loaderbench|"
    r"BENCH_[A-Z]|ROOFLINE|SCALING\.json|LOADER\.json|MULTICHIP_r0")
HISTORY = {"CHANGES.md", "ROADMAP.md", "BASELINE.md", "ADVICE.md",
           "ISSUE.md", "PERF_LEDGER.jsonl", "tests/test_records.py"}


def test_no_sentence_cites_the_deleted_stack():
    """Code, comments and the documents a newcomer reads first (README,
    PERF.md, the package) answer "how fast is it, and how do I find out"
    once: no name of the deleted stack outside the history files."""
    hits = []
    for dirpath, dirnames, filenames in os.walk(REPO):
        dirnames[:] = [d for d in dirnames
                       if not d.startswith((".", "_")) and d != "chiprun_out"]
        for fn in filenames:
            rel = os.path.relpath(os.path.join(dirpath, fn), REPO)
            if rel in HISTORY or not fn.endswith((".py", ".md", ".toml",
                                                  ".json")):
                continue
            for i, line in enumerate(_read(rel).splitlines(), 1):
                if STALE.search(line):
                    hits.append(f"{rel}:{i}: {line.strip()[:80]}")
    assert not hits, hits


def test_peak_tables_agree():
    """Two peak tables remain (ROADMAP D13): the program's, behind the
    ``train.mfu`` gauge, and the benchmark's own.  Until one imports the
    other they state the same chip."""
    assert set(DEVICE_PEAKS) == set(BENCH_PEAKS)
    for kind, row in DEVICE_PEAKS.items():
        bench = BENCH_PEAKS[kind]
        assert row["bf16_tflops"] * 1e12 == bench["bf16_flops"]
        assert row["int8_tops"] * 1e12 == bench["int8_ops"]
        assert row["hbm_gbps"] * 1e9 == bench["hbm_bytes_per_s"]
        assert row["hbm_gb"] * 1e9 == bench["hbm_bytes"]
