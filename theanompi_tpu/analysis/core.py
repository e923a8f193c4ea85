"""tmlint core: rule registry, one-parse-per-file engine, suppressions.

The three ad-hoc AST walkers that grew inside ``tests/test_lint_*.py``
(PR 1's wall-clock lint, PR 4's exception-swallowing lint, PR 5's np.load
confinement) each re-implemented the same loop: glob the package, read,
parse, walk, collect offender strings.  This module is that loop, once:

- a file is read and ``ast.parse``'d exactly ONCE per run (``SourceFile``),
  shared by every rule — adding a rule costs a visitor, not a parse;
- rules are small classes registered by name (:func:`register`), each
  yielding :class:`Finding`\\ s with a severity and a one-line message;
- suppression is inline and self-documenting: ``# lint: <rule>-ok — why``
  on the flagged line.  The justification text is REQUIRED — a bare
  marker is itself a finding (rule ``suppression``), as is a marker
  naming a rule that does not exist.  Nothing is suppressed invisibly:
  suppressed findings ride the JSON report under ``"suppressed"``.

The engine is import-light (stdlib only) so ``tmlint`` runs in any
environment the repo's tests run in; the compiled-artifact auditor
(:mod:`theanompi_tpu.analysis.hlo_audit`), which needs jax, stays a
separate module.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
import re
from typing import Callable, Iterable, Iterator

#: repository root (the directory holding ``theanompi_tpu/``)
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SEV_ERROR = "error"
SEV_WARNING = "warning"
_SEVERITIES = (SEV_ERROR, SEV_WARNING)

#: suppression marker grammar: ``# lint: <rule>-ok <justification>``.
#: The justification may be set off with ``—``, ``--`` or ``:`` and must be
#: non-empty; ``tmlint`` verifies both the rule name and the justification.
#: The marker must START its comment (``# lint: ...``) — a prose mention
#: of the grammar mid-sentence neither suppresses nor trips the meta rule.
_MARKER_RE = re.compile(r"#\s*lint:\s*([A-Za-z0-9_-]+)-ok\b(.*)")
_SEP_RE = re.compile(r"^[\s—:,-]+")

#: marker rule id for suppression-grammar violations (bare marker, unknown
#: rule name) — not a registered Rule: it cannot itself be suppressed.
META_RULE = "suppression"


@dataclasses.dataclass
class Finding:
    """One lint offence, pointing at a source line."""

    rule: str
    severity: str
    path: str        # repo-relative, '/'-separated
    line: int
    col: int
    message: str
    suppressed: bool = False
    justification: str | None = None

    def format(self) -> str:
        tail = (f"  [suppressed: {self.justification}]"
                if self.suppressed else "")
        return (f"{self.path}:{self.line}:{self.col}: "
                f"{self.severity} [{self.rule}] {self.message}{tail}")

    def as_json(self) -> dict:
        d = dataclasses.asdict(self)
        if not self.suppressed:
            d.pop("justification")
        return d


@dataclasses.dataclass
class Suppression:
    """A parsed ``# lint: <rule>-ok`` marker on one line."""

    rule: str
    line: int
    justification: str


class SourceFile:
    """One parsed python file: text, lines, AST and suppression markers —
    computed once, shared by every rule in the run."""

    def __init__(self, path: str, root: str = REPO_ROOT):
        self.path = os.path.abspath(path)
        self.rel = os.path.relpath(self.path, root).replace(os.sep, "/")
        self.text = open(self.path, encoding="utf-8").read()
        self.lines = self.text.splitlines()
        self.tree = ast.parse(self.text, filename=self.rel)
        self._parents: dict[ast.AST, ast.AST] | None = None
        #: line -> list of markers on that line
        self.markers: dict[int, list[Suppression]] = {}
        for lineno, line in enumerate(self.lines, start=1):
            for m in _MARKER_RE.finditer(line):
                just = _SEP_RE.sub("", m.group(2)).strip()
                self.markers.setdefault(lineno, []).append(
                    Suppression(m.group(1), lineno, just))

    def parent_map(self) -> dict[ast.AST, ast.AST]:
        """child -> parent for the whole tree (built lazily, once)."""
        if self._parents is None:
            self._parents = {}
            for node in ast.walk(self.tree):
                for child in ast.iter_child_nodes(node):
                    self._parents[child] = node
        return self._parents

    def ancestors(self, node: ast.AST) -> Iterator[ast.AST]:
        parents = self.parent_map()
        while node in parents:
            node = parents[node]
            yield node

    def marker_for(self, rule: str, lines: Iterable[int]) -> Suppression | None:
        """The first ``<rule>-ok`` marker on any of ``lines`` (a rule may
        accept the marker on more than one line, e.g. the ``except`` line
        or its first body line)."""
        for lineno in lines:
            for sup in self.markers.get(lineno, ()):
                if sup.rule == rule:
                    return sup
        return None


class Rule:
    """A registered lint rule: a named check over one :class:`SourceFile`.

    Subclasses set ``name``/``severity``/``description`` and implement
    :meth:`check`, yielding findings via :meth:`finding`.  ``marker_lines``
    lets a rule accept its suppression marker on lines other than the
    flagged one (the swallow rule honours the first handler-body line,
    matching the PR 4 marker placement).
    """

    name: str = ""
    severity: str = SEV_ERROR
    description: str = ""

    def check(self, src: SourceFile) -> Iterator[Finding]:
        raise NotImplementedError

    def finding(self, src: SourceFile, line: int, col: int, message: str,
                marker_lines: Iterable[int] = ()) -> Finding:
        f = Finding(self.name, self.severity, src.rel, line, col, message)
        # the marker counts on the flagged line, on rule-specific extra
        # lines, or on a contiguous pure-comment block immediately above
        # (where justifications go when the flagged line has no room)
        cand = [line, *marker_lines]
        prev = line - 1
        while 0 < prev <= len(src.lines) \
                and src.lines[prev - 1].lstrip().startswith("#"):
            cand.append(prev)
            prev -= 1
        sup = src.marker_for(self.name, cand)
        if sup is not None and sup.justification:
            f.suppressed = True
            f.justification = sup.justification
        return f


_REGISTRY: dict[str, type[Rule]] = {}


def register(cls: type[Rule]) -> type[Rule]:
    """Class decorator: add a rule to the global registry (name-keyed)."""
    if not cls.name:
        raise ValueError(f"rule {cls.__name__} has no name")
    if cls.severity not in _SEVERITIES:
        raise ValueError(f"rule {cls.name}: bad severity {cls.severity!r}")
    if cls.name in _REGISTRY:
        raise ValueError(f"duplicate rule name {cls.name!r}")
    _REGISTRY[cls.name] = cls
    return cls


def all_rules() -> dict[str, type[Rule]]:
    """name -> rule class, importing the built-in rule modules on first
    use (registration happens at import time)."""
    from theanompi_tpu.analysis import layers, rules  # noqa: F401

    return dict(_REGISTRY)


def default_paths(root: str = REPO_ROOT) -> list[str]:
    """What ``tmlint`` scans with no path arguments: the package."""
    paths = []
    pkg = os.path.join(root, "theanompi_tpu")
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames.sort()
        for f in sorted(filenames):
            if f.endswith(".py"):
                paths.append(os.path.join(dirpath, f))
    return paths


def _meta_findings(src: SourceFile, known: set[str]) -> Iterator[Finding]:
    """Suppression-grammar violations: unknown rule name, or a marker with
    no justification.  These are never themselves suppressible."""
    for lineno, sups in sorted(src.markers.items()):
        for sup in sups:
            if sup.rule not in known:
                yield Finding(
                    META_RULE, SEV_ERROR, src.rel, lineno, 0,
                    f"suppression names unknown rule {sup.rule!r} "
                    f"(known: {', '.join(sorted(known))})")
            elif not sup.justification:
                yield Finding(
                    META_RULE, SEV_ERROR, src.rel, lineno, 0,
                    f"suppression 'lint: {sup.rule}-ok' carries no "
                    f"justification — say WHY the exception is safe")


def lint_paths(paths: Iterable[str] | None = None,
               rule_names: Iterable[str] | None = None,
               root: str = REPO_ROOT,
               on_file: Callable[[str], None] | None = None,
               ) -> tuple[list[Finding], int]:
    """Run rules over files; -> (all findings incl. suppressed, n_files).

    ``rule_names=None`` runs every registered rule.  Suppression-grammar
    checks always run: a stale or bare marker is a finding even when the
    rule it names was deselected (otherwise ``--rules wall`` would hide a
    broken ``swallow-ok`` marker from CI).
    """
    registry = all_rules()
    if rule_names is None:
        selected = sorted(registry)
    else:
        selected = list(rule_names)
        unknown = [r for r in selected if r not in registry]
        if unknown:
            raise KeyError(
                f"unknown rule(s) {', '.join(unknown)} "
                f"(known: {', '.join(sorted(registry))})")
    rules_ = [registry[n]() for n in selected]
    known = set(registry)
    findings: list[Finding] = []
    n_files = 0
    for path in (default_paths(root) if paths is None else paths):
        if on_file is not None:
            on_file(path)
        src = SourceFile(path, root=root)
        n_files += 1
        for rule in rules_:
            findings.extend(rule.check(src))
        findings.extend(_meta_findings(src, known))
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings, n_files


def build_report(findings: list[Finding], n_files: int,
                 rule_names: Iterable[str] | None = None) -> dict:
    """The ``--report`` JSON artifact (schema locked by test)."""
    registry = all_rules()
    names = sorted(registry) if rule_names is None else list(rule_names)
    active = [f for f in findings if not f.suppressed]
    return {
        "version": 1,
        "tool": "tmlint",
        "files_scanned": n_files,
        "rules": [
            {"name": n, "severity": registry[n].severity,
             "description": registry[n].description}
            for n in names
        ],
        "findings": [f.as_json() for f in active],
        "suppressed": [f.as_json() for f in findings if f.suppressed],
        "summary": {
            "errors": sum(f.severity == SEV_ERROR for f in active),
            "warnings": sum(f.severity == SEV_WARNING for f in active),
            "suppressed": sum(f.suppressed for f in findings),
        },
    }


def write_report(report: dict, path: str) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
