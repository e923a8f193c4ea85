"""Declared import-layering DAG + the ``import-dag`` rule.

PR 6 drew one wall (serving never imports training machinery) as a
hand-rolled test.  This module generalizes it: every module in the
package is assigned to a named LAYER (longest-prefix match), and the
DAG below declares which lower layers each layer may import at module
level.  The declaration is acyclic BY CONSTRUCTION — an allowed-set may
only reference layers declared earlier in the ordered list, which
:func:`validate_dag` enforces (and a test locks).

Two kinds of check:

- **layering** (module-level imports only): a top-of-module import is an
  import-time dependency; it must point at the same layer or one the
  declaration allows.  Function-local imports are deliberate lazy edges
  (the repo's cycle-breaking idiom — e.g. ``ops/opt.py`` lazily pulling
  ``parallel.tensor``) and are exempt from layering.
- **walls** (ANY-depth imports): the hard boundaries no lazy import may
  cross — serving must never touch training machinery even lazily, and
  the bottom layers stay (near-)leaves so everything above can depend
  on them without cycles: telemetry imports nothing in-package, and
  resilience reaches only down (codes, telemetry — relaxed in ISSUE 13
  so the watchdog/sentinel emit through the registered names in
  ``telemetry/metrics.py``).  ``resilience/codes.py`` staying
  import-free is what lets both halves of the supervisor share it; the
  companion ``exit-code`` rule keeps it the only source of exit codes.
"""

from __future__ import annotations

import ast
import os
from typing import Iterator

from theanompi_tpu.analysis.core import (
    REPO_ROOT,
    SEV_ERROR,
    Finding,
    Rule,
    SourceFile,
    register,
)

PKG = "theanompi_tpu"

#: The layer DAG, bottom-up.  Each entry: (layer, module prefixes,
#: allowed lower layers).  Assignment is by LONGEST matching prefix, so
#: ``resilience.codes`` lands in ``codes`` even though ``resilience``
#: also matches; the bare ``theanompi_tpu`` prefix makes ``tooling`` the
#: default for new top-level modules.  In-layer imports are always
#: allowed.
LAYER_DAG: tuple[tuple[str, tuple[str, ...], tuple[str, ...]], ...] = (
    # the interleave harness is stdlib-only sync-points (ISSUE 15) —
    # a bottom layer like codes, so the instrumented seams (telemetry
    # ticker, checkpoint writer, fleet passes) may import sp() without
    # puncturing their walls; longest-prefix assignment peels it off
    # the analysis layer above
    ("syncpoint",  (f"{PKG}.analysis.interleave",), ()),
    ("codes",      (f"{PKG}.resilience.codes",), ()),
    # the durable serving file contracts (queue.jsonl / REQUESTS.jsonl /
    # SERVE_SNAPSHOT.json, ISSUE 19) are stdlib-only — a bottom layer,
    # peeled off ``serving`` by longest-prefix so the ROUTER may speak
    # the wire format without importing the engine/scheduler machinery
    ("serve_lifecycle", (f"{PKG}.serving.lifecycle",), ()),
    ("native",     (f"{PKG}.native",), ()),
    ("telemetry",  (f"{PKG}.telemetry",), ("syncpoint",)),
    ("resilience", (f"{PKG}.resilience",), ("codes", "telemetry")),
    ("mesh",       (f"{PKG}.parallel.mesh",), ()),
    ("kernels",    (f"{PKG}.ops.initializers", f"{PKG}.ops.layers",
                    f"{PKG}.ops.losses", f"{PKG}.ops.quant",
                    f"{PKG}.ops.pallas_attention",
                    f"{PKG}.ops.pallas_paged_attention",
                    f"{PKG}.ops.pallas_grouped_matmul"),
                   ("mesh",)),
    ("sharding",   (f"{PKG}.parallel.tensor", f"{PKG}.parallel.ring_attention",
                    f"{PKG}.parallel.pipeline"),
                   ("mesh", "kernels")),
    ("ops",        (f"{PKG}.ops",), ("mesh", "kernels", "sharding")),
    # "telemetry" admitted in ISSUE 25: the Recorder's segments are spans
    # of the process's ring (telemetry.spans: stdlib-only, always on)
    ("utils_base", (f"{PKG}.utils.helper_funcs", f"{PKG}.utils.recorder",
                    f"{PKG}.utils.divergence"),
                   ("mesh", "telemetry")),
    ("exchange",   (f"{PKG}.parallel.exchanger", f"{PKG}.parallel.overlap"),
                   ("mesh", "kernels")),
    ("data",       (f"{PKG}.models.data",),
                   ("codes", "telemetry", "resilience", "utils_base")),
    ("models",     (f"{PKG}.models",),
                   ("mesh", "kernels", "sharding", "ops", "utils_base",
                    "exchange", "data")),
    ("ckpt",       (f"{PKG}.utils.checkpoint",),
                   ("syncpoint", "codes", "telemetry", "resilience",
                    "utils_base")),
    # the async rules (parallel.easgd / parallel.gosgd, ISSUE 20) live
    # in this layer: their host-side state (round ordinals, gossip draws
    # via models.data.base.derive_seed, fault-plan hooks) imports only
    # downward — and they stay forbidden any-depth in the serving/fleet/
    # router walls below like the rest of the training machinery
    ("training",   (f"{PKG}.parallel",),
                   ("codes", "telemetry", "resilience", "mesh", "kernels",
                    "sharding", "ops", "utils_base", "exchange", "data",
                    "models", "ckpt")),
    ("tooling",    (f"{PKG}.launcher", f"{PKG}.utils", PKG),
                   ("codes", "native", "telemetry", "resilience", "mesh",
                    "kernels", "sharding", "ops", "utils_base", "exchange",
                    "data", "models", "ckpt", "training")),
    # the fleet scheduler runs training JOBS as subprocesses — it must
    # never import the training (or serving) machinery it supervises;
    # its world is exit codes, the run_job seam, fault plans, telemetry
    ("fleet",      (f"{PKG}.fleet",),
                   ("syncpoint", "codes", "telemetry", "resilience",
                    "utils_base")),
    # the router composes serving REPLICAS as fleet jobs (ISSUE 19): its
    # world is the fleet scheduler, the durable lifecycle file contracts,
    # exit codes and telemetry — the serving engine/scheduler machinery
    # and training both stay subprocesses (any-depth wall below)
    ("router",     (f"{PKG}.router",),
                   ("syncpoint", "codes", "serve_lifecycle", "telemetry",
                    "resilience", "utils_base", "fleet")),
    # serving is a read-only consumer: kernels (shared int8 wire format),
    # verified checkpoint loads, telemetry, the launcher's config surface
    # — NEVER exchange/training (see the any-depth wall below).
    # "resilience" admitted in ISSUE 14 for the FAULT GRAMMAR + exit
    # codes only; the supervisor/sentinel/watchdog machinery stays
    # walled off any-depth below
    ("serving",    (f"{PKG}.serving",),
                   ("codes", "serve_lifecycle", "telemetry", "kernels",
                    "utils_base", "ckpt", "tooling", "resilience")),
    ("analysis",   (f"{PKG}.analysis",),
                   ("syncpoint", "codes", "serve_lifecycle", "native",
                    "telemetry", "resilience", "mesh",
                    "kernels", "sharding", "ops", "utils_base", "exchange",
                    "data", "models", "ckpt", "training", "tooling",
                    "fleet", "router", "serving")),
)

#: training-side modules serving must never import at ANY depth (PR 6's
#: wall): a gradient, optimizer, exchanger or supervisor import there
#: means training machinery leaked into the inference path
SERVING_FORBIDDEN_IMPORTS = (
    f"{PKG}.parallel.trainer",
    f"{PKG}.parallel.bsp",
    f"{PKG}.parallel.easgd",
    f"{PKG}.parallel.gosgd",
    f"{PKG}.parallel.exchanger",
    f"{PKG}.parallel.pipeline",
    f"{PKG}.ops.opt",
    f"{PKG}.resilience.supervisor",
    f"{PKG}.resilience.sentinel",
    f"{PKG}.resilience.watchdog",
    # NOTE (ISSUE 14): ``resilience.faults`` was deliberately REMOVED from
    # this wall — the serving chaos sites (serve:raise/stall/
    # rollout_corrupt) fire inside the serving process, and the fault
    # grammar is leaf machinery (stdlib-only), not training machinery.
    # The supervisor half stays forbidden: ``tmserve --supervise`` reaches
    # ``run_job`` through ``resilience/replica.py`` (a resilience-layer
    # module) via a lazy import, mirroring the launcher seam.
    # serving ⊥ fleet (ISSUE 11): a replica must not reach into the
    # scheduler that may be preempting it — coordination flows the other
    # way, through processes and exit codes
    f"{PKG}.fleet",
    # serving ⊥ router (ISSUE 19), same shape: a replica must not reach
    # into the router that balances/drains it — it reads queue.jsonl and
    # writes REQUESTS.jsonl/SERVE_SNAPSHOT.json, nothing more
    f"{PKG}.router",
)

#: the mirror half of the serving ⊥ fleet wall, any depth: the scheduler
#: composes training JOBS as subprocesses; importing the machinery it
#: supervises (even lazily) would couple its process lifetime to a jax
#: runtime it exists to babysit
FLEET_FORBIDDEN_IMPORTS = (
    f"{PKG}.serving",
    f"{PKG}.parallel",
    f"{PKG}.models",
    f"{PKG}.ops",
    f"{PKG}.launcher",
    # fleet ⊥ router (ISSUE 19): the scheduler does not know replicas
    # exist — the router submits serving JobSpecs downward, never the
    # reverse
    f"{PKG}.router",
)

#: the router's world (ISSUE 19) is fleet jobs + the durable lifecycle
#: file contracts + telemetry/codes: the serving engine/scheduler
#: machinery and the training stack always run in replica/training
#: SUBPROCESSES.  Any-depth, like the serving wall — a lazy engine
#: import in the router would couple the balancing loop's lifetime to a
#: jax runtime it exists to supervise.  ``serving.lifecycle`` is the one
#: serving module the router may touch (the stdlib-only wire format);
#: the supervisor machinery is reached only through the fleet layer's
#: run_job seam, never directly.
ROUTER_FORBIDDEN_IMPORTS = (
    f"{PKG}.parallel",
    f"{PKG}.models",
    f"{PKG}.ops",
    f"{PKG}.launcher",
    f"{PKG}.serving.engine",
    f"{PKG}.serving.scheduler",
    f"{PKG}.serving.kv_cache",
    f"{PKG}.serving.prefix_cache",
    f"{PKG}.serving.rollout",
    f"{PKG}.serving.quant",
    f"{PKG}.serving.cli",
    f"{PKG}.resilience.supervisor",
    f"{PKG}.resilience.sentinel",
    f"{PKG}.resilience.watchdog",
)

#: subpackages that must stay import leaves at ANY depth: everything
#: above depends on them, so even a lazy upward import risks a cycle
#: (and telemetry in particular must stay importable before jax init)
LEAF_SUBPACKAGES = {
    # telemetry may additionally reach the stdlib-only sync-point module
    # (ISSUE 15: the health ticker is an instrumented seam) — interleave
    # imports nothing in-package, so the leaf stays cycle-free.  ISSUE
    # 16's profile.py (step attribution), ledger.py (perf trajectory) and
    # prof.py (the tmprof CLI) live INSIDE this leaf: they import only
    # telemetry siblings, so the wall holds unchanged
    f"{PKG}.telemetry": (f"{PKG}.telemetry", f"{PKG}.analysis.interleave"),
    # resilience may reach telemetry (ISSUE 13: registered event names +
    # the watchdog's flight-recorder dump) — still downward-only, so the
    # no-cycles property holds: telemetry itself stays a strict leaf
    f"{PKG}.resilience": (f"{PKG}.resilience", f"{PKG}.telemetry"),
    f"{PKG}.native": (f"{PKG}.native",),
}


def validate_dag() -> None:
    """Raise if the declaration is not a DAG (an allowed-set referencing
    a later or unknown layer) or a layer name repeats."""
    seen: list[str] = []
    for layer, prefixes, allowed in LAYER_DAG:
        if layer in seen:
            raise ValueError(f"duplicate layer {layer!r}")
        for ref in allowed:
            if ref not in seen:
                raise ValueError(
                    f"layer {layer!r} allows {ref!r}, which is not "
                    f"declared EARLIER — the declaration must stay "
                    f"acyclic by construction")
        if not prefixes:
            raise ValueError(f"layer {layer!r} has no module prefixes")
        seen.append(layer)


def module_layer(module: str) -> str | None:
    """Layer of a dotted module name, by longest matching prefix."""
    best, best_len = None, -1
    for layer, prefixes, _ in LAYER_DAG:
        for p in prefixes:
            if (module == p or module.startswith(p + ".")) \
                    and len(p) > best_len:
                best, best_len = layer, len(p)
    return best


def _allowed(layer: str) -> tuple[str, ...]:
    for name, _, allowed in LAYER_DAG:
        if name == layer:
            return allowed
    raise KeyError(layer)


def _package_modules(root: str) -> set[str]:
    """Every real dotted module name under the package (used to resolve
    ``from pkg import sub`` to ``pkg.sub`` only when sub IS a module)."""
    mods = set()
    pkg_dir = os.path.join(root, PKG)
    for dirpath, dirnames, filenames in os.walk(pkg_dir):
        dirnames.sort()
        for f in filenames:
            if not f.endswith(".py"):
                continue
            rel = os.path.relpath(os.path.join(dirpath, f), root)
            mod = rel[:-3].replace(os.sep, ".")
            if mod.endswith(".__init__"):
                mod = mod[: -len(".__init__")]
            mods.add(mod)
    return mods


def _file_module(rel: str) -> str | None:
    """Dotted module name of a repo-relative path, None outside the
    package (root scripts carry no layer)."""
    if not rel.startswith(PKG + "/") or not rel.endswith(".py"):
        return None
    mod = rel[:-3].replace("/", ".")
    if mod.endswith(".__init__"):
        mod = mod[: -len(".__init__")]
    return mod


def _resolve_from(module: str, names: list[str], known: set[str]) -> set[str]:
    out = set()
    for n in names:
        full = f"{module}.{n}"
        out.add(full if full in known else module)
    return out


def _module_level_imports(tree: ast.Module, known: set[str]
                          ) -> Iterator[tuple[int, str]]:
    """In-package imports reachable at import time: top-level statements,
    descending through ``try``/``if``/``with`` wrappers (the version-
    probe idiom) and class bodies (which ALSO execute at import time)
    but NOT into function bodies — a function-local import is a
    deliberate lazy edge."""
    stack: list[ast.stmt] = list(tree.body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.Try, ast.If, ast.ClassDef,
                             ast.With, ast.AsyncWith)):
            stack.extend(node.body)
            stack.extend(getattr(node, "orelse", ()))
            for h in getattr(node, "handlers", ()):
                stack.extend(h.body)
            stack.extend(getattr(node, "finalbody", ()))
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name.startswith(PKG):
                    yield node.lineno, a.name
        elif isinstance(node, ast.ImportFrom) and node.module \
                and node.module.startswith(PKG):
            for mod in sorted(_resolve_from(
                    node.module, [a.name for a in node.names], known)):
                yield node.lineno, mod


def _all_imports(tree: ast.Module) -> Iterator[tuple[int, str]]:
    """Every in-package module an import touches, at any depth.  For
    ``from pkg import name`` both ``pkg`` and ``pkg.name`` are yielded —
    the wall must catch submodule binds without needing resolution."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name.startswith(PKG):
                    yield node.lineno, a.name
        elif isinstance(node, ast.ImportFrom) and node.module \
                and node.module.startswith(PKG):
            yield node.lineno, node.module
            for a in node.names:
                yield node.lineno, f"{node.module}.{a.name}"


def _under(mod: str, prefix: str) -> bool:
    return mod == prefix or mod.startswith(prefix + ".")


@register
class ImportDagRule(Rule):
    """Package layering: module-level imports obey the declared DAG;
    hard walls hold at any depth.

    The declaration lives in :data:`LAYER_DAG` (this module's
    docstring explains the two check kinds).  A deliberate one-off
    exception marks the import line ``lint: import-dag-ok — <why>`` —
    but prefer moving the symbol to the layer that owns it.
    """

    name = "import-dag"
    severity = SEV_ERROR
    description = ("declared package-layer DAG (module-level) + any-depth "
                   "walls: serving⊥training, leaf subpackages stay leaves")

    _known_cache: dict[str, set[str]] = {}

    def _known(self, root: str) -> set[str]:
        if root not in self._known_cache:
            self._known_cache[root] = _package_modules(root)
        return self._known_cache[root]

    def check(self, src: SourceFile) -> Iterator[Finding]:
        validate_dag()
        mod = _file_module(src.rel)
        if mod is None:
            return
        root = src.path[: -len(src.rel) - 1] if src.path.endswith(src.rel) \
            else REPO_ROOT
        known = self._known(root)
        layer = module_layer(mod)
        if layer is None:
            yield self.finding(
                src, 1, 0,
                f"module {mod} is not assigned to any layer in "
                f"analysis/layers.py — declare its place in the DAG")
            return
        allowed = set(_allowed(layer))
        for lineno, imp in _module_level_imports(src.tree, known):
            if _under(imp, mod):
                continue
            tgt = module_layer(imp)
            if tgt is None or tgt == layer or tgt in allowed:
                continue
            yield self.finding(
                src, lineno, 0,
                f"layer {layer!r} ({mod}) imports {imp} (layer {tgt!r}) "
                f"at module level — not in its declared allowed set "
                f"{sorted(allowed)}")
        # -- any-depth walls -------------------------------------------------
        if _under(mod, f"{PKG}.serving"):
            for lineno, imp in _all_imports(src.tree):
                if any(_under(imp, bad) for bad in SERVING_FORBIDDEN_IMPORTS):
                    yield self.finding(
                        src, lineno, 0,
                        f"serving imports training machinery {imp} — the "
                        f"inference path must stay a read-only consumer")
        if _under(mod, f"{PKG}.fleet"):
            for lineno, imp in _all_imports(src.tree):
                if any(_under(imp, bad) for bad in FLEET_FORBIDDEN_IMPORTS):
                    yield self.finding(
                        src, lineno, 0,
                        f"fleet imports {imp} — the scheduler supervises "
                        f"training/serving as subprocesses and must never "
                        f"import that machinery, even lazily")
        if _under(mod, f"{PKG}.router"):
            for lineno, imp in _all_imports(src.tree):
                if any(_under(imp, bad) for bad in ROUTER_FORBIDDEN_IMPORTS):
                    yield self.finding(
                        src, lineno, 0,
                        f"router imports {imp} — replicas and training are "
                        f"subprocesses; the router speaks only the durable "
                        f"lifecycle file contracts and the fleet job seam")
        for leaf, ok_prefixes in LEAF_SUBPACKAGES.items():
            if not _under(mod, leaf):
                continue
            for lineno, imp in _all_imports(src.tree):
                if imp.startswith(PKG) and not any(
                        _under(imp, p) for p in ok_prefixes):
                    yield self.finding(
                        src, lineno, 0,
                        f"{leaf} is a leaf subpackage (everything above "
                        f"depends on it) but imports {imp}")
