"""tmlint rules: the bug classes this repo has actually hit.

Three rules are straight ports of the PR 1/4/5 test lints (``wall``,
``swallow``, ``np-load``); four are new, distilled from the repo's own
incident history:

- ``donated-escape`` — PR 5's latent async-writer race: ``np.asarray`` on
  a jax array is ZERO-COPY on the CPU backend, so a view that crosses a
  return/thread/queue boundary aliases a buffer the next donated step
  will rewrite underneath the reader (torn .npz, flaky CRC).
- ``host-sync`` — PR 2's hoisting lesson: ``float()``/``bool()``/
  ``np.asarray``/``.item()`` on device values inside a telemetry span
  forces a device sync inside the timed region, so the span measures the
  sync it caused.
- ``jit-nondet`` — wall clocks and global RNG inside a jitted function
  burn a trace-time constant into the executable (different on every
  recompile, invisible at runtime); in the fault plan they break the
  PR 4 determinism contract outright.
- ``exit-code`` — PR 4's exit-code drift: bare 70/75/76/77/78/79 literals
  outside ``resilience/codes.py`` re-create the duplicated contract that
  module exists to kill.
- ``data-determinism`` — ISSUE 10's resume contract: one unseeded
  ``np.random.*`` draw in ``models/data/`` makes batch content depend on
  call order, which a mid-epoch cursor fast-forward cannot reproduce.
- ``telemetry-registered-names`` — ISSUE 13's health detectors and the
  fleet aggregator key on event names; a string-literal name at an
  emission site in ``serving/``/``resilience/`` is a typo'd or drifted
  name the registry in ``telemetry/metrics.py`` cannot catch.

The concurrency tier (ISSUE 15) — every threading bug shipped so far
(PR 5's torn async snapshot, PR 10's ``on_supervisor`` registration
race) was found by accident; these make thread discipline a checked
invariant:

- ``atomic-publish`` — durable artifacts (JSON reports, manifests,
  health files) must publish via tmp→``os.replace``; a direct or
  append-mode write is a torn read waiting for a crash, unless the
  format provably tolerates torn tails (JSONL sinks — suppress with
  that justification).
- ``guarded-state`` — in a class that owns a ``Lock``/``RLock``, an
  attribute assigned both under ``with self._lock:`` and outside it is
  the PR 10 registration-race shape: half the writers think the lock
  protects it.
- ``thread-lifecycle`` — every ``threading.Thread`` carries a ``name``
  (tmhealth/blackbox dumps and py-spy output must identify the seam);
  non-daemon threads need a reachable ``join`` or they outlive the run.
- ``lock-order`` — nested ``with``-acquisitions are checked against the
  declared :data:`LOCK_ORDER_DAG` (``layers.LAYER_DAG`` style); an
  undeclared nesting is a deadlock candidate.

Every rule is heuristic where it must be (static analysis cannot prove a
buffer is donated); the escape hatch is the suppression grammar in
:mod:`theanompi_tpu.analysis.core` — inline, justified, reported.
"""

from __future__ import annotations

import ast
from typing import Iterator

from theanompi_tpu.analysis.core import (
    SEV_ERROR,
    SEV_WARNING,
    Finding,
    Rule,
    SourceFile,
    register,
)

# ---------------------------------------------------------------------------
# ports of the legacy test lints
# ---------------------------------------------------------------------------


@register
class WallClockRule(Rule):
    """``time.time()`` in package code (PR 1's timing lint).

    Durations must come from ``time.perf_counter()`` — ``time.time()`` is
    NTP-steppable and low-resolution.  Wall-clock *stamps* (run ids,
    heartbeat payloads, audit records) mark the line ``lint: wall-ok``
    with the reason wall time is genuinely required.
    """

    name = "wall"
    severity = SEV_ERROR
    description = ("time.time() in timed paths — use time.perf_counter() "
                   "for durations")

    def check(self, src: SourceFile) -> Iterator[Finding]:
        for node in ast.walk(src.tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "time"
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "time"):
                yield self.finding(
                    src, node.lineno, node.col_offset,
                    "time.time() — durations use time.perf_counter(); a "
                    "genuine wall-clock stamp marks the line 'lint: "
                    "wall-ok — <why>'")


#: (repo-relative path, enclosing function) pairs exempt from the broad-
#: handler check — the documented correlated-failure teardown sites plus
#: the CLI mains whose whole job is the exit-code contract
SWALLOW_ALLOWLIST = {
    ("theanompi_tpu/parallel/trainer.py", "run"),    # teardown join
    ("theanompi_tpu/parallel/trainer.py", "wait"),   # telemetry finalize
    ("theanompi_tpu/launcher.py", "main"),           # exit-code contract
    ("theanompi_tpu/launcher.py", "_run_session"),   # ... its session half
    ("theanompi_tpu/serving/cli.py", "main"),        # tmserve contract
    ("theanompi_tpu/analysis/cli.py", "main"),       # tmlint contract
    ("theanompi_tpu/fleet/cli.py", "main"),          # tmfleet contract
    ("theanompi_tpu/router/cli.py", "main"),         # tmrouter contract
}

_BROAD = {"Exception", "BaseException"}


def _is_broad(type_node) -> bool:
    if type_node is None:
        return True
    nodes = type_node.elts if isinstance(type_node, ast.Tuple) else [type_node]
    return any(isinstance(n, ast.Name) and n.id in _BROAD for n in nodes)


def _stashes_error(handler: ast.ExceptHandler) -> bool:
    """Deferred-delivery pattern: the caught error is assigned somewhere
    (``self._err = e``) for a later re-raise at the consuming site."""
    if not handler.name:
        return False
    for node in ast.walk(handler):
        if isinstance(node, ast.Assign):
            for sub in ast.walk(node.value):
                if isinstance(sub, ast.Name) and sub.id == handler.name:
                    return True
    return False


@register
class SwallowRule(Rule):
    """Exception swallowing in package error paths (PR 4's lint).

    The resilience layer only works if failures PROPAGATE: flags bare
    ``except:``, pass-only handler bodies, and broad handlers
    (``Exception``/``BaseException``) that neither re-raise nor stash the
    error for deferred delivery.  The marker counts on the ``except``
    line or the first body line (the PR 4 placement).
    """

    name = "swallow"
    severity = SEV_ERROR
    description = ("bare/pass-only/broad exception handlers swallow "
                   "failures the resilience layer needs")

    def _enclosing_function(self, src: SourceFile,
                            handler: ast.ExceptHandler) -> str:
        for anc in src.ancestors(handler):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return anc.name
        return "<module>"

    def check(self, src: SourceFile) -> Iterator[Finding]:
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            marker_lines = (node.body[0].lineno,) if node.body else ()
            if node.type is None:
                yield self.finding(
                    src, node.lineno, node.col_offset,
                    "bare `except:` catches everything, SystemExit "
                    "included", marker_lines)
                continue
            body_is_pass = (len(node.body) == 1
                            and isinstance(node.body[0], ast.Pass))
            if body_is_pass:
                yield self.finding(
                    src, node.lineno, node.col_offset,
                    "handler body is only `pass` — the classic swallow",
                    marker_lines)
                continue
            has_raise = any(isinstance(n, ast.Raise) for n in ast.walk(node))
            if (_is_broad(node.type) and not has_raise
                    and not _stashes_error(node)
                    and (src.rel, self._enclosing_function(src, node))
                    not in SWALLOW_ALLOWLIST):
                yield self.finding(
                    src, node.lineno, node.col_offset,
                    "broad handler swallows the error (no raise / no "
                    "deferred stash)", marker_lines)


#: files allowed to call np.load (PR 5's lint): checkpoint ``.npz`` bytes
#: must only be read through the verified loader — dataset shards and
#: recorder histories have their own (non-checkpoint) formats.  Serving
#: must NEVER appear here (read-only consumers go through
#: ``load_for_inference``).
NP_LOAD_ALLOWED_PREFIXES = (
    "theanompi_tpu/utils/checkpoint.py",   # THE verified loader
    "theanompi_tpu/utils/recorder.py",     # history .npy snapshots
    "theanompi_tpu/models/data/",          # dataset shard reads
)


@register
class NpLoadRule(Rule):
    """``np.load`` outside the verified-loader allowlist (PR 5's lint).

    A ``np.load(ckpt_path)`` anywhere else bypasses manifest
    verification, the fingerprint check and the recovery chain.
    """

    name = "np-load"
    severity = SEV_ERROR
    description = ("np.load confined to the verified checkpoint loader / "
                   "recorder / dataset allowlist")

    def check(self, src: SourceFile) -> Iterator[Finding]:
        if src.rel.startswith(NP_LOAD_ALLOWED_PREFIXES):
            return
        for node in ast.walk(src.tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "load"
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id in ("np", "numpy")):
                yield self.finding(
                    src, node.lineno, node.col_offset,
                    "np.load outside the verified checkpoint loader "
                    "allowlist — go through theanompi_tpu.utils.checkpoint")


# ---------------------------------------------------------------------------
# donated-buffer escape (the PR 5 async-writer race class)
# ---------------------------------------------------------------------------

_ESCAPE_CALL_ATTRS = {"put", "put_nowait", "submit"}


def _is_np_asarray(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "asarray"
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id in ("np", "numpy"))


def _is_thread_ctor(call: ast.Call) -> bool:
    f = call.func
    name = f.attr if isinstance(f, ast.Attribute) else (
        f.id if isinstance(f, ast.Name) else "")
    return name == "Thread"


@register
class DonatedEscapeRule(Rule):
    """``np.asarray`` view escaping a return/thread/queue boundary.

    ``np.asarray`` on a jax array is zero-copy on the CPU backend: the
    numpy view aliases the device buffer, and if that buffer is later
    donated (``donate_argnums``) the next step rewrites the bytes under
    whoever kept the view — PR 5's torn-.npz race, rediscovered by CRC.
    Flags an ``np.asarray(...)`` whose result (directly or via a local
    name) is returned/yielded, handed to ``queue.put``/``executor.submit``
    / a ``Thread``, stored on ``self`` or into a container — unless a
    ``.copy()`` breaks the aliasing anywhere along the way.
    """

    name = "donated-escape"
    severity = SEV_ERROR
    description = ("np.asarray zero-copy view of a (possibly donated) "
                   "device buffer escapes without .copy()")

    def _sanitized(self, src: SourceFile, node: ast.AST) -> bool:
        """A `.copy()` call wraps ``node`` somewhere up the expression."""
        for anc in src.ancestors(node):
            if (isinstance(anc, ast.Call)
                    and isinstance(anc.func, ast.Attribute)
                    and anc.func.attr == "copy"):
                return True
            if isinstance(anc, ast.stmt):
                return False
        return False

    def _escape_reason(self, src: SourceFile, node: ast.AST) -> str | None:
        """Why ``node``'s value leaves the function, or None.

        Walks up through container displays (a tuple/list/dict keeps the
        view alive verbatim) but stops at an ordinary call — a function
        consuming the view (``np.percentile(arr)``, ``device_put(x)``)
        returns derived data, not the alias.  Queue/executor/thread calls
        are the exception: they hand the object itself across a thread
        boundary, which is exactly the PR 5 race shape.
        """
        for anc in src.ancestors(node):
            if isinstance(anc, (ast.Return, ast.Yield, ast.YieldFrom)):
                return "returned"
            if isinstance(anc, ast.Call):
                if (isinstance(anc.func, ast.Attribute)
                        and anc.func.attr in _ESCAPE_CALL_ATTRS):
                    return f"passed to .{anc.func.attr}()"
                if _is_thread_ctor(anc):
                    return "passed to a Thread"
                return None  # consumed by an ordinary call
            if isinstance(anc, (ast.BinOp, ast.UnaryOp, ast.Compare)):
                return None  # arithmetic/comparison yields derived data
            if isinstance(anc, ast.stmt):
                return None
            # containers, conditionals, attribute/subscript views: the
            # alias survives — keep walking up
        return None

    def _name_sanitized(self, fn: ast.AST, name: str) -> bool:
        """``name.copy()`` appears anywhere in the function (accepts the
        conditional ``a = a.copy()`` ownership-check idiom)."""
        for node in ast.walk(fn):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "copy"
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == name):
                return True
        return False

    def _name_escapes(self, src: SourceFile, fn: ast.AST, name: str,
                      bound_line: int) -> tuple[int, str] | None:
        """(line, reason) where the bound name leaves the function.

        Loads on lines before the binding are ignored — an early ``return
        x`` guard above a later ``x = np.asarray(x)`` rebinding returns
        the ORIGINAL object, not the view (flow-insensitivity fix).
        """
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Name) and node.id == name
                    and isinstance(node.ctx, ast.Load)
                    and node.lineno >= bound_line):
                continue
            reason = self._escape_reason(src, node)
            if reason is not None and not self._sanitized(src, node):
                return node.lineno, reason
            parent = src.parent_map().get(node)
            if isinstance(parent, ast.Assign) and node is parent.value:
                for tgt in parent.targets:
                    if isinstance(tgt, ast.Attribute):
                        return node.lineno, "stored on an attribute"
                    if isinstance(tgt, ast.Subscript):
                        return node.lineno, "stored into a container"
        return None

    def _nearest_function(self, src: SourceFile, node: ast.AST) -> ast.AST | None:
        for anc in src.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                return anc
        return None

    def check(self, src: SourceFile) -> Iterator[Finding]:
        for fn in ast.walk(src.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.Lambda)):
                continue
            for node in ast.walk(fn):
                if not _is_np_asarray(node):
                    continue
                # nested defs are walked once, in their OWN scope (name
                # tracking below is per-function)
                if self._nearest_function(src, node) is not fn:
                    continue
                if self._sanitized(src, node):
                    continue
                reason = self._escape_reason(src, node)
                if reason is None:
                    # value bound to a simple local name? track the name
                    parent = src.parent_map().get(node)
                    while isinstance(parent, ast.IfExp):
                        parent = src.parent_map().get(parent)
                    if (isinstance(parent, ast.Assign)
                            and len(parent.targets) == 1
                            and isinstance(parent.targets[0], ast.Name)):
                        bound = parent.targets[0].id
                        if not self._name_sanitized(fn, bound):
                            hit = self._name_escapes(src, fn, bound,
                                                     node.lineno)
                            if hit is not None:
                                line, why = hit
                                yield self.finding(
                                    src, node.lineno, node.col_offset,
                                    f"np.asarray view bound to "
                                    f"{bound!r} is {why} at line {line} "
                                    f"without .copy() — a donated buffer "
                                    f"would be rewritten under the reader")
                    continue
                yield self.finding(
                    src, node.lineno, node.col_offset,
                    f"np.asarray view {reason} without .copy() — a "
                    f"donated buffer would be rewritten under the reader")


# ---------------------------------------------------------------------------
# host-sync inside telemetry spans
# ---------------------------------------------------------------------------


def _is_span_call(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "span")


def _span_in_expr(expr: ast.AST) -> bool:
    """Does this with-item expression produce a telemetry span?  Handles
    the repo's ``with (tel.span(...) if tel else nullcontext()):`` idiom."""
    return any(_is_span_call(n) for n in ast.walk(expr))


@register
class HostSyncRule(Rule):
    """Device sync inside a telemetry span (the timed-path bug class).

    ``float()``/``bool()``/``np.asarray()``/``.item()`` on a device value
    blocks on the device INSIDE the span, so the span times the stall it
    created (PR 2 hoisted exactly these out of the step path).  A span
    that deliberately closes over materialized results — the documented
    "measure execution, not dispatch" pattern — marks the line
    ``lint: host-sync-ok — <why>``.
    """

    name = "host-sync"
    severity = SEV_WARNING
    description = ("float()/bool()/np.asarray/.item() inside a telemetry "
                   "span forces a device sync into the timed region")

    def _span_bound_names(self, fn: ast.AST) -> set[str]:
        """Local names assigned a span (``span = tel.span(...)``)."""
        names: set[str] = set()
        for node in ast.walk(fn):
            if (isinstance(node, ast.Assign)
                    and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and _span_in_expr(node.value)):
                names.add(node.targets[0].id)
        return names

    def _sync_calls(self, body: list[ast.stmt]) -> Iterator[ast.Call]:
        for stmt in body:
            for node in ast.walk(stmt):
                if not isinstance(node, ast.Call):
                    continue
                f = node.func
                if (isinstance(f, ast.Name) and f.id in ("float", "bool")
                        and len(node.args) == 1
                        and not isinstance(node.args[0], ast.Constant)):
                    yield node
                elif _is_np_asarray(node):
                    yield node
                elif isinstance(f, ast.Attribute) and f.attr == "item":
                    yield node

    def _enclosing_span_names(self, src: SourceFile,
                              node: ast.AST) -> set[str]:
        """Span-bound local names visible at ``node`` (its enclosing
        function's assignments, or the module's for top-level code)."""
        for anc in src.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return self._span_bound_names(anc)
        return self._span_bound_names(src.tree)

    def check(self, src: SourceFile) -> Iterator[Finding]:
        seen: set[int] = set()
        for with_node in ast.walk(src.tree):
            if not isinstance(with_node, (ast.With, ast.AsyncWith)):
                continue
            spanned = any(
                _span_in_expr(item.context_expr)
                or (isinstance(item.context_expr, ast.Name)
                    and item.context_expr.id
                    in self._enclosing_span_names(src, with_node))
                for item in with_node.items)
            if not spanned:
                continue
            for call in self._sync_calls(with_node.body):
                if id(call) in seen:
                    continue
                seen.add(id(call))
                yield self.finding(
                    src, call.lineno, call.col_offset,
                    "host sync inside a telemetry span — the span times "
                    "the stall it causes; hoist the pull past the span, "
                    "or mark 'lint: host-sync-ok — <why>' if the span "
                    "deliberately measures execution")


# ---------------------------------------------------------------------------
# untracked nondeterminism under jit / in the fault plan
# ---------------------------------------------------------------------------

#: files whose WHOLE body must stay deterministic (the PR 4 fault plan:
#: `site:action@index[@attempt]` replays bit-exactly across restarts)
DETERMINISTIC_FILES = (
    "theanompi_tpu/resilience/faults.py",
)

_NONDET_TIME = {"time", "time_ns"}
_NONDET_DATETIME = {"now", "today", "utcnow"}
#: np.random module-level entry points that are fine — seeded constructors
_NP_RANDOM_OK = {"RandomState", "default_rng", "Generator", "SeedSequence",
                 "PCG64", "Philox"}


def _jit_marked(expr: ast.AST) -> bool:
    """Does this expression mention a ``jit`` callable (jax.jit, jit,
    partial(jax.jit, ...))?"""
    for node in ast.walk(expr):
        if isinstance(node, ast.Attribute) and node.attr == "jit":
            return True
        if isinstance(node, ast.Name) and node.id == "jit":
            return True
    return False


@register
class JitNondetRule(Rule):
    """Nondeterminism burned into a jitted trace or the fault plan.

    Inside a function that gets jitted, ``time.time()``, global
    ``np.random.*`` and ``datetime.now()`` run at TRACE time: the value
    becomes a compile-time constant that silently changes on every
    recompile.  In :mod:`theanompi_tpu.resilience.faults` the same calls
    break the deterministic-replay contract outright.
    """

    name = "jit-nondet"
    severity = SEV_ERROR
    description = ("wall clock / global RNG in jitted or fault-plan-"
                   "deterministic code")

    def _jitted_functions(self, src: SourceFile) -> list[ast.AST]:
        """FunctionDefs that are jit-decorated, or whose name is passed
        to a ``jit(...)`` call anywhere in the file (covers the
        ``self._fn = jax.jit(self._impl, ...)`` idiom)."""
        defs: dict[str, list[ast.AST]] = {}
        jitted: list[ast.AST] = []
        for node in ast.walk(src.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, []).append(node)
                if any(_jit_marked(d) for d in node.decorator_list):
                    jitted.append(node)
        for node in ast.walk(src.tree):
            if not (isinstance(node, ast.Call) and _jit_marked(node.func)):
                continue
            for arg in node.args[:1]:
                name = (arg.id if isinstance(arg, ast.Name)
                        else arg.attr if isinstance(arg, ast.Attribute)
                        else None)
                if name:
                    jitted.extend(defs.get(name, ()))
        return jitted

    def _nondet_calls(self, scope: ast.AST, has_bare_random: bool,
                      ) -> Iterator[tuple[ast.Call, str]]:
        for node in ast.walk(scope):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)):
                continue
            f = node.func
            v = f.value
            if (isinstance(v, ast.Name) and v.id == "time"
                    and f.attr in _NONDET_TIME):
                yield node, f"time.{f.attr}()"
            elif (f.attr in _NONDET_DATETIME
                  and isinstance(v, ast.Name) and v.id == "datetime"):
                yield node, f"datetime.{f.attr}()"
            elif (f.attr in _NONDET_DATETIME
                  and isinstance(v, ast.Attribute) and v.attr == "datetime"):
                yield node, f"datetime.datetime.{f.attr}()"
            elif (isinstance(v, ast.Attribute) and v.attr == "random"
                  and isinstance(v.value, ast.Name)
                  and v.value.id in ("np", "numpy")):
                if f.attr not in _NP_RANDOM_OK:
                    yield node, f"np.random.{f.attr}()"
                elif not node.args and not node.keywords:
                    yield node, f"np.random.{f.attr}() with no seed"
            elif (has_bare_random and isinstance(v, ast.Name)
                  and v.id == "random" and f.attr != "seed"):
                yield node, f"random.{f.attr}()"

    def check(self, src: SourceFile) -> Iterator[Finding]:
        has_bare_random = any(
            isinstance(n, ast.Import)
            and any(a.name == "random" for a in n.names)
            for n in ast.walk(src.tree))
        scopes: list[tuple[ast.AST, str]] = []
        if src.rel in DETERMINISTIC_FILES:
            scopes.append((src.tree, "the deterministic fault plan"))
        else:
            scopes.extend((fn, f"jitted function {fn.name!r}")
                          for fn in self._jitted_functions(src))
        seen: set[int] = set()
        for scope, where in scopes:
            for call, what in self._nondet_calls(scope, has_bare_random):
                if id(call) in seen:
                    continue
                seen.add(id(call))
                yield self.finding(
                    src, call.lineno, call.col_offset,
                    f"{what} inside {where} — the value is nondeterministic"
                    f" (trace-time constant under jit); thread it in as an"
                    f" argument instead")


# ---------------------------------------------------------------------------
# exit-code literals
# ---------------------------------------------------------------------------

#: the codes the contract in resilience/codes.py owns (EXIT_CLEAN=0 and
#: argparse's 2 are universal; flagging them would drown the rule in noise)
EXIT_CODE_LITERALS = {70, 75, 76, 77, 78, 79}
EXIT_CODES_SOURCE = "theanompi_tpu/resilience/codes.py"

_EXIT_CALL_NAMES = {"exit", "SystemExit", "_exit"}


@register
class ExitCodeRule(Rule):
    """Bare exit-code literals outside ``resilience/codes.py``.

    A literal ``77`` in a ``sys.exit``/``SystemExit``/comparison is a
    drifted duplicate of the contract waiting to happen (PR 4 created
    ``codes.py`` precisely because two halves of the resilience layer
    must agree).  Import the named constant instead.
    """

    name = "exit-code"
    severity = SEV_ERROR
    description = ("bare 70/75/76/77/78/79 exit-code literal — import from "
                   "theanompi_tpu.resilience.codes")

    def _literals_in(self, node: ast.AST) -> Iterator[ast.Constant]:
        for sub in ast.walk(node):
            if (isinstance(sub, ast.Constant)
                    and type(sub.value) is int
                    and sub.value in EXIT_CODE_LITERALS):
                yield sub

    def check(self, src: SourceFile) -> Iterator[Finding]:
        if src.rel == EXIT_CODES_SOURCE:
            return
        flagged: set[int] = set()

        def emit(const: ast.Constant, ctx: str):
            if id(const) in flagged:
                return
            flagged.add(id(const))
            yield self.finding(
                src, const.lineno, const.col_offset,
                f"bare exit-code literal {const.value} in {ctx} — use the "
                f"named constant from theanompi_tpu.resilience.codes")

        for node in ast.walk(src.tree):
            if isinstance(node, ast.Call):
                f = node.func
                name = (f.attr if isinstance(f, ast.Attribute)
                        else f.id if isinstance(f, ast.Name) else "")
                if name in _EXIT_CALL_NAMES:
                    for arg in node.args:
                        for const in self._literals_in(arg):
                            yield from emit(const, f"{name}()")
            elif isinstance(node, ast.Compare):
                for side in (node.left, *node.comparators):
                    for const in self._literals_in(side):
                        yield from emit(const, "a comparison")


# ---------------------------------------------------------------------------
# data-plane determinism
# ---------------------------------------------------------------------------

#: the tree whose batch content must be a pure function of
#: (seed, epoch, position) — ISSUE 10's cursor-exact resume contract
DATA_PLANE_PREFIX = "theanompi_tpu/models/data/"


@register
class DataDeterminismRule(Rule):
    """Unseeded randomness anywhere in the data plane.

    Mid-epoch resume fast-forwards by cursor arithmetic instead of
    replaying consumed batches, which is only sound if every batch is
    recomputable in isolation from ``(seed, epoch, position)``.  One draw
    from the global numpy RNG (or an unseeded ``RandomState()``) makes
    batch content depend on call order and process history — state a
    checkpoint cannot capture, so the resumed run silently diverges.
    Derive per-call seeds with ``models.data.base.derive_seed`` and feed
    them to a local ``np.random.RandomState``.
    """

    name = "data-determinism"
    severity = SEV_ERROR
    description = ("unseeded np.random.* / global RNG under models/data/ "
                   "breaks cursor-exact mid-epoch resume")

    def check(self, src: SourceFile) -> Iterator[Finding]:
        if not src.rel.startswith(DATA_PLANE_PREFIX):
            return
        has_bare_random = any(
            isinstance(n, ast.Import)
            and any(a.name == "random" for a in n.names)
            for n in ast.walk(src.tree))
        for node in ast.walk(src.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)):
                continue
            f = node.func
            v = f.value
            what = None
            if (isinstance(v, ast.Attribute) and v.attr == "random"
                    and isinstance(v.value, ast.Name)
                    and v.value.id in ("np", "numpy")):
                if f.attr not in _NP_RANDOM_OK:
                    what = f"np.random.{f.attr}()"
                elif not node.args and not node.keywords:
                    what = f"np.random.{f.attr}() with no seed"
            elif (has_bare_random and isinstance(v, ast.Name)
                  and v.id == "random"):
                # random.seed() is flagged too: mutating the global RNG in
                # the data plane is the order-dependence this rule exists
                # to catch, not an exemption from it.
                what = f"random.{f.attr}()"
            if what is not None:
                yield self.finding(
                    src, node.lineno, node.col_offset,
                    f"{what} in the data plane — batch content must be a "
                    f"pure function of (seed, epoch, position) or mid-epoch "
                    f"resume diverges; use np.random.RandomState("
                    f"derive_seed(...)) instead")


# ---------------------------------------------------------------------------
# telemetry event-name registration
# ---------------------------------------------------------------------------

#: trees whose emission sites must use the registered-name tuples from
#: ``telemetry/metrics.py`` (ISSUE 13): the health detectors, tmhealth and
#: the fleet aggregator all key on event names, so a typo'd literal at an
#: emission site silently drops the event from every consumer.  Training
#: code is exempt for now — its names predate the registry.
REGISTERED_NAME_PREFIXES = (
    "theanompi_tpu/serving/",
    "theanompi_tpu/resilience/",
    # ISSUE 19: the router's dispatch/redistribute/scale decisions feed
    # the same consumers — its router.* names are registered too
    "theanompi_tpu/router/",
    # ISSUE 16: the attribution/ledger emitters live by the same contract
    # (their attr.*/prof.*/ledger.* names are registered in metrics.py)
    "theanompi_tpu/telemetry/profile.py",
    "theanompi_tpu/telemetry/ledger.py",
    "theanompi_tpu/telemetry/prof.py",
    # ISSUE 20: the async rules' per-round instants feed the
    # async_staleness detector — their easgd.*/gosgd.*/exchange.* names
    # bind from metrics.py (ASYNC_INSTANTS/ASYNC_GAUGES/EXCHANGE_COUNTS)
    "theanompi_tpu/parallel/easgd.py",
    "theanompi_tpu/parallel/gosgd.py",
)

#: emission entry points whose FIRST positional argument is an event name
#: (``Telemetry.span/instant/emit_span/observe/gauge/count`` plus the
#: ``self._emit(name, **fields)`` wrappers in the scheduler and sentinel)
_EMIT_NAME_ATTRS = {"span", "instant", "emit_span", "observe", "gauge",
                    "count", "_emit"}


@register
class TelemetryRegisteredNamesRule(Rule):
    """String-literal event names at serving/resilience emission sites.

    The name registry (``SERVE_SPANS``/``RESILIENCE_INSTANTS``/... in
    :mod:`theanompi_tpu.telemetry.metrics`) exists so the emitting site,
    the health detectors, tmhealth and the fleet aggregator all agree on
    one spelling.  A string literal at the call site bypasses it: the
    event still writes, nothing consumes it, and nothing fails loudly.
    Bind the registered tuple to a module constant and pass that.
    """

    name = "telemetry-registered-names"
    severity = SEV_ERROR
    description = ("string-literal telemetry event name in serving/ or "
                   "resilience/ — use the registered names from "
                   "telemetry/metrics.py")

    def check(self, src: SourceFile) -> Iterator[Finding]:
        if not src.rel.startswith(REGISTERED_NAME_PREFIXES):
            return
        for node in ast.walk(src.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _EMIT_NAME_ATTRS
                    and node.args):
                continue
            first = node.args[0]
            literal = (isinstance(first, ast.Constant)
                       and type(first.value) is str)
            if not (literal or isinstance(first, ast.JoinedStr)):
                continue
            shown = (repr(first.value) if literal
                     else "an f-string")
            yield self.finding(
                src, first.lineno, first.col_offset,
                f"event name {shown} passed as a literal to "
                f".{node.func.attr}() — bind the registered name from "
                f"theanompi_tpu.telemetry.metrics so detectors and "
                f"aggregators see the same spelling")


# ---------------------------------------------------------------------------
# the concurrency tier (ISSUE 15)
# ---------------------------------------------------------------------------


def _nearest_function(src: SourceFile, node: ast.AST) -> ast.AST | None:
    """The innermost enclosing function scope, or None at module level."""
    for anc in src.ancestors(node):
        if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda)):
            return anc
    return None


@register
class AtomicPublishRule(Rule):
    """Durable artifacts publish tmp→``os.replace`` — never directly.

    A reader (resume, tmhealth, the fleet aggregator, a human) that
    opens a half-written JSON file sees garbage; a crash between
    truncate and flush *loses the previous good artifact too*.  The
    proven idiom everywhere else in this repo (checkpoint manifests,
    HEALTH.json, flight-recorder dumps, the lint report itself) is
    write-to-``<path>.tmp`` then ``os.replace`` — crash-atomic on POSIX.

    Heuristics, per function scope: a write-mode ``open()`` whose path
    expression mentions ``.tmp`` (directly or via a name assigned in
    the same function) is the idiom's first half and must be paired
    with an ``os.replace`` in the same function; any other ``"w"``/
    ``"x"`` open is a direct write; ``"a"`` opens are torn-tail-prone
    appends.  Streams that provably tolerate torn tails (JSONL event
    sinks, append-only audit logs — their readers skip unparseable
    final lines) suppress with that justification:
    ``# lint: atomic-publish-ok — <why torn reads are safe>``.
    """

    name = "atomic-publish"
    severity = SEV_ERROR
    description = ("durable-file write outside the tmp→os.replace idiom — "
                   "fix or justify (JSONL torn-tail tolerance)")

    def check(self, src: SourceFile) -> Iterator[Finding]:
        opens: list[tuple[ast.AST | None, ast.Call, str]] = []
        replaced: set[ast.AST | None] = set()
        assigns: dict[tuple[ast.AST | None, str], ast.AST] = {}
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Call):
                if self._is_open(node):
                    mode = self._mode(node)
                    if mode and mode[0] in "wxa":
                        opens.append(
                            (_nearest_function(src, node), node, mode))
                elif (isinstance(node.func, ast.Attribute)
                        and node.func.attr == "replace"
                        and isinstance(node.func.value, ast.Name)
                        and node.func.value.id == "os"):
                    replaced.add(_nearest_function(src, node))
            elif (isinstance(node, ast.Assign)
                    and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                scope = _nearest_function(src, node)
                assigns[(scope, node.targets[0].id)] = node.value
        for scope, call, mode in opens:
            path = call.args[0] if call.args else None
            if mode[0] == "a":
                yield self.finding(
                    src, call.lineno, call.col_offset,
                    f"append-mode open({mode!r}) to a durable file — a "
                    f"crash mid-write leaves a torn tail; if every reader "
                    f"skips unparseable tails (JSONL), mark the line "
                    f"'lint: atomic-publish-ok — <why>'")
            elif self._tmpish(path, scope, assigns):
                if scope not in replaced:
                    yield self.finding(
                        src, call.lineno, call.col_offset,
                        "tmp file written but never published — pair the "
                        ".tmp write with os.replace in the same function")
            else:
                yield self.finding(
                    src, call.lineno, call.col_offset,
                    f"direct open({mode!r}) write to a durable path — "
                    f"write '<path>.tmp' then os.replace(tmp, path) so a "
                    f"crash never tears the artifact or loses the "
                    f"previous one")

    def _is_open(self, call: ast.Call) -> bool:
        return isinstance(call.func, ast.Name) and call.func.id == "open"

    def _mode(self, call: ast.Call) -> str | None:
        """The mode string when statically known, else None (skipped)."""
        expr = None
        if len(call.args) >= 2:
            expr = call.args[1]
        else:
            for kw in call.keywords:
                if kw.arg == "mode":
                    expr = kw.value
        if expr is None:
            return "r"
        if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
            return expr.value
        return None

    def _tmpish(self, path: ast.AST | None, scope: ast.AST | None,
                assigns: dict) -> bool:
        if path is None:
            return False
        for n in ast.walk(path):
            if (isinstance(n, ast.Constant) and isinstance(n.value, str)
                    and ".tmp" in n.value):
                return True
            if isinstance(n, ast.Name):
                bound = assigns.get((scope, n.id))
                if bound is not None and any(
                        isinstance(m, ast.Constant)
                        and isinstance(m.value, str) and ".tmp" in m.value
                        for m in ast.walk(bound)):
                    return True
        return False


@register
class GuardedStateRule(Rule):
    """Attribute assigned both under and outside ``with self._lock:``.

    The PR 10 shape: ``FleetScheduler._sups`` was written by the
    episode thread's callback and read by ``_preempt`` — one side held
    the lock, the other didn't, and a preemption arriving in the gap
    was silently lost.  In a class that owns a ``Lock``/``RLock``, an
    attribute rebound both inside and outside lock-guarded code is that
    bug waiting to recur.

    What counts as guarded: a lexical ``with self.<lock>:`` ancestor,
    or the whole body of a method whose every ``self.m()`` call site in
    the class sits under the lock (the ``EventSink._rotate`` idiom —
    helpers documented 'call with the lock held').  ``__init__`` is
    exempt: construction precedes sharing.
    """

    name = "guarded-state"
    severity = SEV_ERROR
    description = ("attribute assigned both under and outside the owning "
                   "class's lock — the registration-race shape")

    _LOCK_CTORS = ("Lock", "RLock")

    def check(self, src: SourceFile) -> Iterator[Finding]:
        for cls in ast.walk(src.tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            lock_attrs = self._lock_attrs(cls)
            if not lock_attrs:
                continue
            methods = {m.name: m for m in cls.body
                       if isinstance(m, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))}
            locked = self._locked_methods(src, methods, lock_attrs)
            guarded: dict[str, list] = {}
            unguarded: dict[str, list] = {}
            for mname, m in methods.items():
                if mname == "__init__":
                    continue
                for node in ast.walk(m):
                    for attr, line, col in self._self_assigns(node):
                        if attr in lock_attrs:
                            continue
                        bucket = (guarded if mname in locked
                                  or self._under_lock(src, node, m,
                                                      lock_attrs)
                                  else unguarded)
                        bucket.setdefault(attr, []).append((line, col))
            for attr in sorted(set(guarded) & set(unguarded)):
                for line, col in unguarded[attr]:
                    yield self.finding(
                        src, line, col,
                        f"self.{attr} is assigned here without the lock "
                        f"but under 'with self.{sorted(lock_attrs)[0]}:' "
                        f"elsewhere in the class — every writer must "
                        f"agree on whether the lock protects it")

    def _lock_attrs(self, cls: ast.ClassDef) -> set[str]:
        out: set[str] = set()
        for node in ast.walk(cls):
            if not (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)):
                continue
            f = node.value.func
            ctor = f.attr if isinstance(f, ast.Attribute) else (
                f.id if isinstance(f, ast.Name) else "")
            if ctor not in self._LOCK_CTORS:
                continue
            for t in node.targets:
                if (isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"):
                    out.add(t.attr)
        return out

    def _self_assigns(self, node: ast.AST):
        targets = []
        if isinstance(node, ast.Assign):
            targets = list(node.targets)
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            targets = [node.target]
        for t in targets:
            if isinstance(t, ast.Tuple):
                targets.extend(t.elts)
            elif (isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name)
                    and t.value.id == "self"):
                yield t.attr, t.lineno, t.col_offset

    def _is_lock_expr(self, expr: ast.AST, lock_attrs: set[str]) -> bool:
        return (isinstance(expr, ast.Attribute)
                and isinstance(expr.value, ast.Name)
                and expr.value.id == "self"
                and expr.attr in lock_attrs)

    def _under_lock(self, src: SourceFile, node: ast.AST, method: ast.AST,
                    lock_attrs: set[str]) -> bool:
        for anc in src.ancestors(node):
            if anc is method:
                return False
            if isinstance(anc, (ast.With, ast.AsyncWith)) and any(
                    self._is_lock_expr(it.context_expr, lock_attrs)
                    for it in anc.items):
                return True
        return False

    def _locked_methods(self, src: SourceFile, methods: dict,
                        lock_attrs: set[str]) -> set[str]:
        """Methods whose every ``self.m()`` call site runs under the
        lock (directly or from another such method) — their bodies
        count as guarded.  One call site outside the lock disqualifies:
        ambiguity is exactly the bug this rule exists to surface."""
        sites: dict[str, list[bool]] = {}
        for mname, m in methods.items():
            for node in ast.walk(m):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and isinstance(node.func.value, ast.Name)
                        and node.func.value.id == "self"
                        and node.func.attr in methods):
                    under = self._under_lock(src, node, m, lock_attrs)
                    sites.setdefault(node.func.attr, []).append(
                        under or mname)  # True, or the calling method
        locked: set[str] = set()
        changed = True
        while changed:
            changed = False
            for callee, callers in sites.items():
                if callee in locked:
                    continue
                if all(c is True or c in locked for c in callers):
                    locked.add(callee)
                    changed = True
        return locked


@register
class ThreadLifecycleRule(Rule):
    """Every ``threading.Thread`` is named; non-daemon threads join.

    An anonymous thread shows up as ``Thread-3`` in ``tmhealth``
    blackbox dumps, the flight recorder, and py-spy — useless when
    diagnosing exactly the hung-seam incidents those tools exist for.
    And a non-daemon thread nobody joins outlives the run: the process
    can't exit, the supervisor escalates to SIGKILL, and the crash
    looks like a hang.  Daemon threads (all seven seams in this repo)
    need only the name.
    """

    name = "thread-lifecycle"
    severity = SEV_ERROR
    description = ("threading.Thread must carry name=...; non-daemon "
                   "threads need a reachable join()")

    def check(self, src: SourceFile) -> Iterator[Finding]:
        has_join = any(
            isinstance(n, ast.Call)
            and isinstance(n.func, ast.Attribute)
            and n.func.attr == "join"
            and not self._path_or_str_join(n.func.value)
            for n in ast.walk(src.tree))
        for node in ast.walk(src.tree):
            if not (isinstance(node, ast.Call) and _is_thread_ctor(node)):
                continue
            kws = {k.arg: k.value for k in node.keywords if k.arg}
            if "name" not in kws:
                yield self.finding(
                    src, node.lineno, node.col_offset,
                    "unnamed thread — pass name='<seam>' so health "
                    "dumps, the flight recorder and py-spy can identify "
                    "it")
            d = kws.get("daemon")
            daemon = isinstance(d, ast.Constant) and d.value is True
            if not daemon and not has_join:
                yield self.finding(
                    src, node.lineno, node.col_offset,
                    "non-daemon thread with no join() anywhere in this "
                    "file — it outlives the run and turns clean exits "
                    "into apparent hangs; join it or make it a daemon")

    def _path_or_str_join(self, value: ast.AST) -> bool:
        """``os.path.join`` / ``"sep".join`` are not thread joins."""
        if isinstance(value, ast.Constant):
            return True
        if isinstance(value, ast.Attribute) and value.attr == "path":
            return True
        return False


#: Declared lock-ordering DAG (``layers.LAYER_DAG`` style): innermost
#: locks first, and an entry may only allow inner locks declared EARLIER
#: — so the declaration is acyclic by construction, exactly like the
#: import DAG.  Entry: (name, (file-prefix, lock-attr), allowed-inner,
#: reentrant).  The telemetry leaves allow NOTHING inside them — in
#: particular ``health`` must never acquire ``sink``'s lock: the ticker
#: releases the monitor's lock before emitting (the documented contract
#: in ``telemetry/core.py:_health_tick``).  The fleet scheduler's RLock
#: sits outermost: its passes emit telemetry while holding it, so the
#: sink/flight/health locks may nest inside (that nesting is cross-file
#: and runtime-only; the entry documents it for the day it becomes
#: lexical).
LOCK_ORDER_DAG: tuple = (
    ("sink", ("theanompi_tpu/telemetry/sink.py", "_lock"), (), False),
    ("flight", ("theanompi_tpu/telemetry/flight_recorder.py", "_lock"),
     (), False),
    # ISSUE 16: both leaf locks — the attributor computes under its lock
    # and emits only after release; the ledger's lock guards the
    # append+dedup read-modify-write and never wraps another lock
    ("attrib", ("theanompi_tpu/telemetry/profile.py", "_lock"), (), False),
    ("ledger", ("theanompi_tpu/telemetry/ledger.py", "_lock"), (), False),
    ("health", ("theanompi_tpu/telemetry/health.py", "_lock"), (), False),
    ("watchdog", ("theanompi_tpu/resilience/watchdog.py", "_lock"),
     (), False),
    ("data-hooks", ("theanompi_tpu/models/data/base.py", "_HOOKS_LOCK"),
     (), False),
    ("shm-busy", ("theanompi_tpu/models/data/shm_loader.py", "_busy"),
     (), False),
    ("native-build", ("theanompi_tpu/native/__init__.py", "_build_lock"),
     (), False),
    ("interleave", ("theanompi_tpu/analysis/interleave.py", "_cond"),
     (), False),
    ("scheduler", ("theanompi_tpu/fleet/scheduler.py", "_lock"),
     ("sink", "flight", "health"), True),
)


def validate_lock_order(dag=None) -> None:
    """Reject duplicate names and forward references, like
    ``layers.validate_dag`` — an allowed-inner lock must be declared
    earlier (further inward), which makes cycles unrepresentable."""
    dag = LOCK_ORDER_DAG if dag is None else dag
    seen: list[str] = []
    for name, (prefix, attr), allowed, _reentrant in dag:
        if name in seen:
            raise ValueError(f"lock-order: duplicate lock name {name!r}")
        if not prefix or not attr:
            raise ValueError(f"lock-order: empty prefix/attr on {name!r}")
        for a in allowed:
            if a not in seen:
                raise ValueError(
                    f"lock-order: {name!r} allows {a!r} which is not "
                    f"declared earlier — inner locks must be declared "
                    f"first")
        seen.append(name)


@register
class LockOrderRule(Rule):
    """Nested ``with``-lock acquisitions obey :data:`LOCK_ORDER_DAG`.

    Two threads taking the same two locks in opposite orders is the
    classic deadlock; a declared global order makes it impossible.  The
    check is lexical (same-file nested ``with`` statements, including
    multi-item ``with a, b:`` read left-to-right): acquiring a declared
    lock while holding another is legal only if the held lock's entry
    allows it; re-acquiring a non-reentrant lock is flagged as a
    self-deadlock.  Cross-file nesting (scheduler → telemetry emit) is
    declared in the DAG for documentation but only runtime tools can
    see it — the interleave harness exists for those.
    """

    name = "lock-order"
    severity = SEV_ERROR
    description = ("nested with-lock acquisition not allowed by the "
                   "declared LOCK_ORDER_DAG")

    def check(self, src: SourceFile) -> Iterator[Finding]:
        validate_lock_order()
        decls = [(name, attr, set(allowed), reentrant)
                 for name, (prefix, attr), allowed, reentrant
                 in LOCK_ORDER_DAG if src.rel.startswith(prefix)]
        if not decls:
            return
        for node in ast.walk(src.tree):
            if not isinstance(node, (ast.With, ast.AsyncWith)):
                continue
            held = self._held_above(src, node, decls)
            for item in node.items:
                acq = self._declared(item.context_expr, decls)
                if acq is None:
                    continue
                aname, _allowed, _reent = acq
                for hname, hallowed, hreent in held:
                    if hname == aname:
                        if not hreent:
                            yield self.finding(
                                src, item.context_expr.lineno,
                                item.context_expr.col_offset,
                                f"re-acquiring non-reentrant lock "
                                f"{aname!r} while holding it — "
                                f"self-deadlock")
                    elif aname not in hallowed:
                        yield self.finding(
                            src, item.context_expr.lineno,
                            item.context_expr.col_offset,
                            f"acquiring lock {aname!r} while holding "
                            f"{hname!r} — not allowed by LOCK_ORDER_DAG; "
                            f"declare the order or restructure so the "
                            f"locks never nest")
                held.append(acq)

    def _declared(self, expr: ast.AST, decls):
        key = (expr.attr if isinstance(expr, ast.Attribute)
               else expr.id if isinstance(expr, ast.Name) else None)
        for name, attr, allowed, reentrant in decls:
            if key == attr:
                return (name, allowed, reentrant)
        return None

    def _held_above(self, src: SourceFile, node: ast.AST, decls) -> list:
        held = []
        for anc in src.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                break  # a nested def runs on its caller's schedule,
                # not inside the enclosing with — out of lexical scope
            if isinstance(anc, (ast.With, ast.AsyncWith)):
                for item in anc.items:
                    acq = self._declared(item.context_expr, decls)
                    if acq is not None:
                        held.append(acq)
        return held
