"""Compiled-artifact auditor: invariants only visible in the HLO.

The AST rules catch what source says; this module catches what XLA
*built*.  Three invariants the repo has already been burned by (or
armored against) are statically checkable on any backend by compiling a
representative step and reading the module text:

- **donation applied** — ``donate_argnums`` is a *request*; a refactor
  that copies a tree before the jit boundary silently doubles HBM and
  no numeric test notices.  Donation that took effect shows up as
  ``input_output_alias`` entries in the module header.
- **collective counts** — the PR 2 lock, generalized: a fused-bucket
  step must compile to O(buckets) all-reduces (not O(leaves)), and
  ``zero1`` must show its reduce-scatter/all-gather pair.  Reuses
  :func:`theanompi_tpu.telemetry.metrics.hlo_collective_counts`.
- **no host callbacks** — a ``pure_callback``/``io_callback`` smuggled
  into a jitted step stalls every step on the host; it compiles to a
  ``custom-call`` with a python-callback target.

One XLA compile per audited program per process (``lru_cache``): the
tier-1 collective-lint shim and the audit tests share the artifacts.
"""

from __future__ import annotations

import functools
import re

from theanompi_tpu.telemetry.metrics import hlo_collective_counts


class HLOAuditError(AssertionError):
    """A compiled artifact violates a locked invariant."""


# -- HLO text parsers --------------------------------------------------------

#: one aliased (donated) parameter entry inside the header's
#: ``input_output_alias={ {0}: (0, {}, may-alias), ... }`` map
_ALIAS_ENTRY_RE = re.compile(r"\{[\d,\s]*\}:\s*\(")
_ALIAS_MAP_RE = re.compile(r"input_output_alias=\{(.*)")
_CUSTOM_CALL_RE = re.compile(r'custom_call_target="([^"]+)"')

#: custom-call targets that mean "the compiled step re-enters python /
#: the host" — the exact spelling varies by backend and jax version, so
#: match substrings
_CALLBACK_MARKERS = ("callback", "python", "host_compute")


def donation_alias_count(hlo_text: str) -> int:
    """How many parameter buffers the compiled module aliases to outputs
    (donation that actually took effect)."""
    for line in hlo_text.splitlines():
        if "input_output_alias=" not in line:
            continue
        m = _ALIAS_MAP_RE.search(line)
        if m:
            return len(_ALIAS_ENTRY_RE.findall(m.group(1)))
    return 0


def host_callbacks(hlo_text: str) -> list[str]:
    """Python/host custom-call targets appearing in the module."""
    hits = []
    for target in _CUSTOM_CALL_RE.findall(hlo_text):
        low = target.lower()
        if any(mark in low for mark in _CALLBACK_MARKERS):
            hits.append(target)
    return sorted(set(hits))


def audit_text(hlo_text: str) -> dict:
    """Backend-independent facts about one compiled module's text."""
    return {
        "collectives": hlo_collective_counts(hlo_text),
        "alias_count": donation_alias_count(hlo_text),
        "host_callbacks": host_callbacks(hlo_text),
    }


# -- entry-computation dataflow (the overlap-schedule discriminator) ---------
#
# Text POSITION cannot prove a collective schedule: the CPU scheduler
# already interleaves op definitions positionally even when the collectives
# are mutually independent and free to sink to the end.  What the overlap
# transform actually guarantees — and what survives every optimization
# pass — is DATAFLOW: with ``exch_overlap`` on, bucket k+1's collective
# transitively depends on bucket k's result (the select fence in
# ``parallel/overlap.py``), while the fused schedule's per-bucket
# collectives have no edges between them at all.  So the auditor parses
# the optimized entry computation into an operand graph and counts
# collective->collective reachability.

_ENTRY_OP_RE = re.compile(r"\)?\s*([a-z][\w\-]*)\(")
_ENTRY_OPERAND_RE = re.compile(r"%([\w.\-]+)")

#: op kinds the chain discriminator follows (same spellings as
#: ``telemetry.metrics.COLLECTIVE_OPS`` definitions)
_CHAIN_COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather",
                      "collective-permute")


def entry_dependency_graph(hlo_text: str):
    """Parse the ENTRY computation -> ``(graph, order)``.

    ``graph`` maps instruction name -> ``(op_kind, operand_names)``;
    ``order`` is definition order.  Operand extraction is by ``%name``
    reference, which over-approximates (attribute refs like ``to_apply=``
    point at non-entry computations and resolve to nothing) — safe for
    reachability, which only follows names defined in the entry.
    """
    in_entry = False
    graph: dict = {}
    order: list = []
    for ln in hlo_text.splitlines():
        if ln.startswith("ENTRY"):
            in_entry = True
            continue
        if in_entry:
            if ln.startswith("}"):
                break
            s = ln.strip()
            if " = " not in s:
                continue
            lhs, rhs = s.split(" = ", 1)
            name = lhs.strip().removeprefix("ROOT ").lstrip("%")
            m = _ENTRY_OP_RE.search(rhs)
            op = m.group(1) if m else "?"
            args = rhs.split("(", 1)[1] if "(" in rhs else ""
            graph[name] = (op, _ENTRY_OPERAND_RE.findall(args))
            order.append(name)
    return graph, order


def collective_chain_stats(hlo_text: str) -> dict:
    """Dataflow facts that discriminate overlapped from fused schedules.

    - ``chained_same_kind``: ordered pairs (A, B) of SAME-KIND collectives
      where B transitively depends on A.  The overlap chain makes this
      >= n_buckets - 1 (transitively n*(n-1)/2 for a full chain); the
      fused schedule's grad collectives are mutually independent, so it
      is 0.  Same-kind only, because zero1's all-gathers inherently
      depend on reduce-scatters (through the update) in EITHER schedule.
    - ``interleaved_pairs``: chained pairs whose downstream collective
      depends on at least one fusion the upstream one does not — i.e.
      backward compute sits ON the chain between the two collectives,
      which is the overlap claim itself (comm k || compute k+1).
    """
    graph, order = entry_dependency_graph(hlo_text)
    colls = [(n, graph[n][0]) for n in order
             if graph[n][0] in _CHAIN_COLLECTIVES]
    # transitive closure in definition order (operands precede uses in
    # printed HLO, so one forward pass resolves every ancestor set; an
    # iterative walk — entry computations run to thousands of ops)
    memo: dict = {}
    for name in order:
        acc: set = set()
        for o in graph[name][1]:
            if o in graph:
                acc.add(o)
                acc |= memo.get(o, set())
        memo[name] = acc

    def ancestors(name):
        return memo.get(name, set())

    chained = 0
    interleaved = 0
    for b, kind_b in colls:
        anc_b = ancestors(b)
        for a, kind_a in colls:
            if a == b or kind_a != kind_b or a not in anc_b:
                continue
            chained += 1
            between = {x for x in anc_b - ancestors(a) - {a}
                       if graph[x][0] in ("fusion", "convolution", "dot")}
            if between:
                interleaved += 1
    return {
        "n_collectives": len(colls),
        "chained_same_kind": chained,
        "interleaved_pairs": interleaved,
    }


# -- representative train step ----------------------------------------------

#: depth 16 -> 43 param leaves: past the >=30-leaf bar the PR 2
#: acceptance set (bucketing is only provable on a many-leaf model),
#: still tiny enough to compile in seconds on the CPU mesh
TRAIN_MODEL_CFG = {
    "depth": 16, "widen": 1, "batch_size": 2, "image_size": 8,
    "n_train": 32, "n_val": 16, "n_epochs": 1, "precision": "fp32",
    "augment": False, "verbose": False,
}

#: the PR 2 collective-count lock, per audited strategy:
#: op kind -> (min, max) definitions in the compiled step (None = unbounded).
#: psum_bucket: one fused grad bucket + fused metrics pmean + fused state
#: pmean <= 4 all-reduces.  zero1: the scatter/gather pair must exist, and
#: at most 3 all-reduces ride along (grad-clip norm psum + the two fused
#: pmeans).
TRAIN_COLLECTIVE_BUDGETS: dict[str, dict[str, tuple[int, int | None]]] = {
    "psum_bucket": {"all-reduce": (1, 4)},
    "zero1": {"reduce-scatter": (1, None), "all-gather": (1, None),
              "all-reduce": (0, 3)},
    # the leaf-wise strategy: it ASKS for one all-reduce per grad leaf, but
    # the optimized module no longer shows that — XLA's all-reduce
    # combiner (jax 0.9.0, the CPU pipeline included) merges independent
    # all-reduces, 43 leaves -> 1 op.  So the compiled count cannot
    # separate psum from psum_bucket any more; what the bucket lock still
    # proves is its own ceiling above.
    "psum": {"all-reduce": (1, None)},
}


@functools.lru_cache(maxsize=None)
def _train_artifact(strategy: str, n_data: int = 4, overlap: bool = False,
                    bucket_mb: float | None = None) -> dict:
    """Compile the BSP train step for ``strategy``; -> facts + HLO text.

    Cached: one XLA compile per (strategy, mesh, overlap, bucket size)
    per process, shared by the legacy collective-lint shim and the audit
    tests.  ``bucket_mb`` shrinks the fused-bucket cap (the overlap audit
    needs >= 2 grad buckets out of this tiny model; the default 4 MiB
    packs everything into one).
    """
    import jax

    from theanompi_tpu.models.wide_resnet import WideResNet
    from theanompi_tpu.parallel.bsp import BSPTrainer
    from theanompi_tpu.parallel.mesh import make_mesh
    from theanompi_tpu.utils.helper_funcs import shard_batch
    from theanompi_tpu.utils.recorder import Recorder

    model = WideResNet(dict(TRAIN_MODEL_CFG))
    mesh = make_mesh(n_data=n_data, devices=jax.devices()[:n_data])
    kw = {} if bucket_mb is None else {"exch_bucket_mb": bucket_mb}
    t = BSPTrainer(model, mesh=mesh, exch_strategy=strategy,
                   exch_overlap=overlap,
                   recorder=Recorder(verbose=False, print_freq=10**9), **kw)
    t.compile_iter_fns()
    t.init_state()
    batch = shard_batch(
        mesh,
        next(iter(model.data.train_batches(t.global_batch, 0, seed=0))),
        spec=t.batch_spec)
    text = t.compiled_step_text(batch)
    buckets = t.exchanger.bucket_summary(
        t._shard_param_structs(), t._exchange_axis_size())
    return {
        "n_param_leaves": len(jax.tree.leaves(t.params)),
        "n_buckets": None if buckets is None else buckets["n_buckets"],
        "chain": collective_chain_stats(text),
        **audit_text(text),
    }


def audit_train_step(strategy: str, n_data: int = 4) -> dict:
    """Audit one exchange strategy's compiled train step.

    -> report dict with ``violations`` (empty = clean) alongside the
    measured facts; raises nothing — callers decide (the CLI raises via
    :func:`run_default_audits`, tests assert on the report).
    """
    facts = _train_artifact(strategy, n_data)
    violations: list[str] = []
    counts = facts["collectives"]
    for op, (lo, hi) in TRAIN_COLLECTIVE_BUDGETS.get(strategy, {}).items():
        n = counts.get(op, 0)
        if n < lo:
            violations.append(
                f"{op}: {n} < locked minimum {lo} (strategy {strategy})")
        if hi is not None and n > hi:
            violations.append(
                f"{op}: {n} > locked maximum {hi} (strategy {strategy}) — "
                f"bucketing regressed to leaf-wise collectives?")
    # donation: params/state/opt/step are donated leaf-wise; if XLA
    # aliased fewer buffers than the params tree alone has leaves, the
    # donation request silently stopped taking effect
    if facts["alias_count"] < facts["n_param_leaves"]:
        violations.append(
            f"donation not applied: {facts['alias_count']} aliased "
            f"buffers < {facts['n_param_leaves']} param leaves")
    if facts["host_callbacks"]:
        violations.append(
            f"host callbacks in the compiled step: "
            f"{facts['host_callbacks']}")
    return {"kind": "train", "strategy": strategy, "n_data": n_data,
            "ok": not violations, "violations": violations, **facts}


# -- overlapped-exchange schedule audit (ISSUE 12) ---------------------------

#: bucket cap for the overlap artifacts — small enough that the depth-16
#: WRN's fp32 grads split into several buckets (the chain needs >= 2)
OVERLAP_AUDIT_BUCKET_MB = 0.125

#: strategies the default overlap audit locks (the all-reduce family and
#: the scatter/gather family — one representative of each chained shape)
DEFAULT_OVERLAP_STRATEGIES = ("psum_bucket", "zero1")


def audit_overlap_schedule(strategy: str, n_data: int = 2) -> dict:
    """Prove the ``exch_overlap`` schedule in the optimized HLO.

    Compiles the step twice at :data:`OVERLAP_AUDIT_BUCKET_MB` — fused
    and overlapped — and checks, on the operand graph:

    - the overlapped module carries a same-kind collective dependency
      chain of >= n_buckets - 1 edges, and the chain passes through
      backward fusions (``interleaved_pairs``) — collectives issue
      *during* backward, not after it;
    - the fused module still audits as trailing (ZERO same-kind edges) —
      the negative proof that the discriminator measures the transform,
      not scheduler noise;
    - overlap changes the SCHEDULE only: the same kinds of collective,
      never fewer than the fused module and never more than one per
      bucket, and donation is intact.  (Not "identical counts": XLA's
      combiner merges the fused module's independent all-reduces — 8
      buckets compile to 1 op on jax 0.9.0 — which is exactly what the
      chain's dependencies forbid in the overlapped module.)
    """
    fused = _train_artifact(strategy, n_data,
                            bucket_mb=OVERLAP_AUDIT_BUCKET_MB)
    over = _train_artifact(strategy, n_data, overlap=True,
                           bucket_mb=OVERLAP_AUDIT_BUCKET_MB)
    violations: list[str] = []
    n_buckets = over["n_buckets"] or 0
    if n_buckets < 2:
        violations.append(
            f"overlap artifact packed only {n_buckets} grad bucket(s) at "
            f"{OVERLAP_AUDIT_BUCKET_MB} MiB — nothing to chain; shrink "
            f"OVERLAP_AUDIT_BUCKET_MB")
    need = max(1, n_buckets - 1)
    if over["chain"]["chained_same_kind"] < need:
        violations.append(
            f"overlap ON but only {over['chain']['chained_same_kind']} "
            f"collective chain edges < {need} (buckets={n_buckets}) — the "
            f"fence chain was optimized away; collectives can sink behind "
            f"backward again")
    if over["chain"]["interleaved_pairs"] < need:
        violations.append(
            f"overlap chain exists but only "
            f"{over['chain']['interleaved_pairs']} chained pairs run "
            f"through backward fusions < {need} — comm is chained but not "
            f"interleaved with compute")
    if fused["chain"]["chained_same_kind"] != 0:
        violations.append(
            f"fused baseline shows {fused['chain']['chained_same_kind']} "
            f"same-kind collective chain edges (expected 0: trailing / "
            f"unconstrained) — the discriminator no longer isolates the "
            f"overlap transform")
    same_kinds = set(over["collectives"]) == set(fused["collectives"])
    if not same_kinds or any(
            not (fused["collectives"][k] <= n
                 <= max(fused["collectives"][k], n_buckets))
            for k, n in over["collectives"].items()):
        violations.append(
            f"overlap changed the collectives: {over['collectives']} vs "
            f"fused {fused['collectives']} at {n_buckets} buckets — the "
            f"fence must reorder (and may keep apart what XLA would "
            f"combine, one per bucket), never add or split collectives")
    if over["alias_count"] < over["n_param_leaves"]:
        violations.append(
            f"donation not applied under overlap: {over['alias_count']} "
            f"aliased buffers < {over['n_param_leaves']} param leaves")
    if over["host_callbacks"]:
        violations.append(
            f"host callbacks in the overlapped step: "
            f"{over['host_callbacks']}")
    return {"kind": "train-overlap", "strategy": strategy, "n_data": n_data,
            "n_buckets": n_buckets, "ok": not violations,
            "violations": violations,
            "chain": over["chain"], "fused_chain": fused["chain"],
            "collectives": over["collectives"],
            "alias_count": over["alias_count"]}


# -- representative serve step ----------------------------------------------

#: tiny TransformerLM (the serving tests' shape) — structure is what the
#: audit reads; no training needed
SERVE_MODEL_CFG = {
    "batch_size": 2, "n_train": 64, "n_val": 32, "seq_len": 32,
    "vocab": 61, "dim": 32, "heads": 2, "n_layers": 2,
    "dropout": 0.0, "n_epochs": 1, "precision": "fp32",
}


@functools.lru_cache(maxsize=None)
def _serve_artifact() -> dict:
    """Compile the fixed-batch decode step; -> facts + metadata."""
    import jax
    import jax.numpy as jnp

    from theanompi_tpu.models.transformer_lm import TransformerLM
    from theanompi_tpu.serving.engine import InferenceEngine

    model = TransformerLM(dict(SERVE_MODEL_CFG))
    params, _ = model.init_params(jax.random.PRNGKey(0))
    eng = InferenceEngine(model, params, block_size=8, max_batch=2)
    b = eng.max_batch
    args = (
        eng.params, eng._k, eng._v,
        jnp.zeros((b, eng.max_blocks_per_seq), jnp.int32),
        jnp.zeros((b,), jnp.int32),
        jnp.zeros((b,), jnp.int32),
        jnp.zeros((b,), jnp.float32),
        jnp.zeros((b,), jnp.int32),
        eng._base_key,
    )
    text = eng._decode_fn.lower(*args).compile().as_text()
    return {"max_batch": b, **audit_text(text)}


def audit_serve_step() -> dict:
    """Audit the serving decode step: k/v pools donated (the paged-cache
    in-place contract), no collectives (single-device serve), no host
    callbacks."""
    facts = _serve_artifact()
    violations: list[str] = []
    if facts["alias_count"] < 2:
        violations.append(
            f"k/v pool donation not applied: {facts['alias_count']} "
            f"aliased buffers < 2 — decode copies the whole cache per "
            f"token")
    if facts["collectives"]:
        violations.append(
            f"collectives in the serve step: {facts['collectives']}")
    if facts["host_callbacks"]:
        violations.append(
            f"host callbacks in the serve step: {facts['host_callbacks']}")
    return {"kind": "serve", "ok": not violations,
            "violations": violations, **facts}


@functools.lru_cache(maxsize=None)
def _serve_prefill_artifact() -> dict:
    """Compile one partial-prefill (suffix) program — the prefix-cache
    hit path (ISSUE 17), one-block suffix bucket; -> facts + metadata."""
    import jax
    import jax.numpy as jnp

    from theanompi_tpu.models.transformer_lm import TransformerLM
    from theanompi_tpu.serving.engine import InferenceEngine

    model = TransformerLM(dict(SERVE_MODEL_CFG))
    params, _ = model.init_params(jax.random.PRNGKey(0))
    eng = InferenceEngine(model, params, block_size=8, max_batch=2)
    s_pad = eng.block_size  # smallest suffix bucket: one block
    fn = jax.jit(eng._prefill_suffix_impl, donate_argnums=(1, 2))
    args = (
        eng.params, eng._k, eng._v,
        jnp.zeros((eng.max_blocks_per_seq,), jnp.int32),
        jnp.zeros((s_pad // eng.block_size,), jnp.int32),
        jnp.zeros((s_pad,), jnp.int32),
        jnp.asarray(eng.block_size, jnp.int32),
        jnp.asarray(eng.block_size + 1, jnp.int32),
        jnp.asarray(0.0, jnp.float32),
        jnp.asarray(0, jnp.int32),
        eng._base_key,
    )
    text = fn.lower(*args).compile().as_text()
    return {"s_pad": s_pad, **audit_text(text)}


def audit_serve_prefill() -> dict:
    """Audit the prefix-cache partial-prefill step (ISSUE 17): same
    contract as decode — k/v pools donated (a cache hit must not copy the
    pools to append suffix K/V), no collectives, no host callbacks."""
    facts = _serve_prefill_artifact()
    violations: list[str] = []
    if facts["alias_count"] < 2:
        violations.append(
            f"k/v pool donation not applied in partial prefill: "
            f"{facts['alias_count']} aliased buffers < 2 — every "
            f"prefix-cache hit copies the whole cache")
    if facts["collectives"]:
        violations.append(
            f"collectives in the partial-prefill step: "
            f"{facts['collectives']}")
    if facts["host_callbacks"]:
        violations.append(
            f"host callbacks in the partial-prefill step: "
            f"{facts['host_callbacks']}")
    return {"kind": "serve-prefill", "ok": not violations,
            "violations": violations, **facts}


# -- serve decode kernel dispatch (ISSUE 18) ---------------------------------

#: TPU-legal shape for the kernel-dispatch lowering: the paged-decode
#: gate needs ``head_dim % 128 == 0`` and ``heads % 8 == 0`` (fp32), and
#: the fused int8 matmul needs 128-divisible bands — dim 1024 over 8
#: heads at the default 1024-element quant chunk is the smallest config
#: satisfying both.  This model is only TRACED and LOWERED (never
#: compiled or run), so the big dims cost trace time, not compile time.
SERVE_KERNEL_CFG = {**SERVE_MODEL_CFG, "dim": 1024, "heads": 8}


_SHLO_DEF = re.compile(r"^\s*(%[\w#]+)(?::\d+)? = \"?stablehlo\.(\w+)\"?[ (]"
                       r"(%[\w#]+)?")
_SHLO_PAGED_CALL = re.compile(
    r"stablehlo\.custom_call @tpu_custom_call\(([^)]*)\).*"
    r"kernel_name = \"_decode_kernel\".* : \(([^)]*)\) -> ")


def paged_call_operands(stablehlo_text: str) -> list[dict]:
    """The K and V operands of every paged-decode ``tpu_custom_call`` in a
    TPU lowering's StableHLO text (ISSUE 26); -> one record per call:
    ``{"shapes": [k, v], "sliced": bool}`` with each shape a list of ints
    and ``sliced`` true when a ``slice`` / ``dynamic_slice`` (seen through
    reshapes) produces either operand.

    A custom call's operand has to be a whole buffer, so a sliced pool in
    front of the kernel is a copy of that slice on the chip, per layer and
    step (:mod:`theanompi_tpu.ops.pallas_paged_attention`)."""
    calls, defs = [], {}
    for line in stablehlo_text.splitlines():
        if "func.func" in line:
            defs = {}  # SSA names are per function
        m = _SHLO_DEF.match(line)
        if m:
            defs[m.group(1)] = (m.group(2), m.group(3))
        c = _SHLO_PAGED_CALL.search(line)
        if not c:
            continue
        names = [n.strip() for n in c.group(1).split(",")][-2:]
        types = [t.strip() for t in c.group(2).split(", ")][-2:]
        sliced = False
        for name in names:
            op, src = defs.get(name, (None, None))
            while op == "reshape":
                op, src = defs.get(src, (None, None))
            sliced |= op in ("slice", "dynamic_slice")
        calls.append({
            "shapes": [[int(n) for n in t[len("tensor<"):].split("x")[:-1]]
                       for t in types],
            "sliced": sliced})
    return calls


@functools.lru_cache(maxsize=None)
def _serve_decode_kernel_artifact() -> dict:
    """Gather the decode-kernel dispatch facts (ISSUE 18).

    Three artifact families, all produced on whatever host backend runs
    the audit (CPU in CI):

    - **TPU lowerings** of the full decode step at :data:`SERVE_KERNEL_CFG`
      with the kernel pinned on vs off.  ``decode_impl`` is a static
      cache field, so pinning ``"kernel"`` lowers the COMPILED pallas
      call even on a CPU host (``lowering_platforms=("tpu",)``) — the
      positive proof is ``tpu_custom_call`` per layer, the negative proof
      is zero custom calls in the ``"off"`` lowering.  The ``"on"``
      lowering also gives the paged calls' K/V operands
      (:func:`paged_call_operands`): each must be the whole pool.
    - a **direct int8 lowering** of :func:`~theanompi_tpu.ops.quant.
      int8_matmul` over an actual quantized engine weight leaf (the
      engine-level lowering above keeps int8 in interpret mode off-TPU,
      so the custom call is proven at the kernel boundary).
    - a **CPU-compiled kernel-on step** at :data:`SERVE_MODEL_CFG` plus a
      bit-parity run: the kernel variant must keep the pool-donation /
      zero-collective contract of :func:`audit_serve_step`, and
      ``interpret=True`` must match the fallback BIT-for-bit over
      crafted tables covering null-block padding, a prefix-shared block
      and ragged (non-block-multiple) positions.
    """
    import jax
    import jax.numpy as jnp

    from theanompi_tpu.models.transformer_lm import TransformerLM
    from theanompi_tpu.ops.quant import (
        QuantizedTensor,
        int8_matmul,
        int8_matmul_supported,
        quantize_chunked,
    )
    from theanompi_tpu.serving.engine import InferenceEngine
    from theanompi_tpu.serving.kv_cache import PagedKVCache

    facts: dict = {"n_layers": SERVE_KERNEL_CFG["n_layers"]}

    # -- TPU lowerings: kernel pinned on vs off --------------------------
    model = TransformerLM(dict(SERVE_KERNEL_CFG))
    params, _ = model.init_params(jax.random.PRNGKey(0))
    int8_leaf = None
    for variant in ("on", "off"):
        eng = InferenceEngine(model, params, block_size=8, max_batch=2,
                              quantize_int8=True, decode_kernel=variant)
        if variant == "on":
            # pin the COMPILED kernel (off-TPU "on" resolves to the
            # interpreter); static aux, so the TPU lowering is exactly
            # what a TPU host would build
            eng.decode_impl = "kernel"
            int8_leaf = next(
                w for w in jax.tree.leaves(
                    eng.params,
                    is_leaf=lambda x: isinstance(x, QuantizedTensor))
                if isinstance(w, QuantizedTensor)
                and int8_matmul_supported(w.shape, int(w.q.shape[1]),
                                          compiled=True))
        b = eng.max_batch
        args = (
            eng.params, eng._k, eng._v,
            jnp.zeros((b, eng.max_blocks_per_seq), jnp.int32),
            jnp.zeros((b,), jnp.int32),
            jnp.zeros((b,), jnp.int32),
            jnp.zeros((b,), jnp.float32),
            jnp.zeros((b,), jnp.int32),
            eng._base_key,
        )
        text = jax.jit(eng._decode_impl, donate_argnums=(1, 2)) \
            .trace(*args).lower(lowering_platforms=("tpu",)).as_text()
        facts[f"custom_calls_{variant}"] = text.count("tpu_custom_call")
        if variant == "on":
            facts["pool_shape"] = list(eng._k.shape)
            facts["paged_calls"] = paged_call_operands(text)

    # -- direct int8 kernel lowering over a real engine weight -----------
    x = jnp.zeros((8, int(int8_leaf.shape[0])), jnp.float32)
    text = jax.jit(lambda xx, ww: int8_matmul(xx, ww, interpret=False)) \
        .trace(x, int8_leaf).lower(lowering_platforms=("tpu",)).as_text()
    facts["custom_calls_int8"] = text.count("tpu_custom_call")

    # -- CPU-compiled kernel-on step: donation contract survives ---------
    model_s = TransformerLM(dict(SERVE_MODEL_CFG))
    params_s, _ = model_s.init_params(jax.random.PRNGKey(0))
    eng_s = InferenceEngine(model_s, params_s, block_size=8, max_batch=2,
                            decode_kernel="on")
    b = eng_s.max_batch
    args = (
        eng_s.params, eng_s._k, eng_s._v,
        jnp.zeros((b, eng_s.max_blocks_per_seq), jnp.int32),
        jnp.zeros((b,), jnp.int32),
        jnp.zeros((b,), jnp.int32),
        jnp.zeros((b,), jnp.float32),
        jnp.zeros((b,), jnp.int32),
        eng_s._base_key,
    )
    text = eng_s._decode_fn.lower(*args).compile().as_text()
    facts.update(audit_text(text))

    # -- bit-parity: kernel (interpret) vs fallback ----------------------
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(0x18), 4)
    bs, h, d, nblocks = 4, 2, 16, 6
    kpool = jax.random.normal(k1, (1, nblocks, bs, h, d), jnp.float32)
    vpool = jax.random.normal(k2, (1, nblocks, bs, h, d), jnp.float32)
    # slot 0 spans blocks (1, 2) with a mid-block position; slot 1 SHARES
    # prefix block 1 (the refcounted copy-on-write case) and pads with
    # null blocks
    tables = jnp.asarray([[1, 2, 0], [1, 3, 0]], jnp.int32)
    positions = jnp.asarray([6, 2], jnp.int32)
    q = jax.random.normal(k3, (2, h, d), jnp.float32)
    outs = {}
    for impl in ("kernel_interpret", "fallback"):
        cache = PagedKVCache(kpool, vpool, tables, bs, decode_impl=impl)
        outs[impl] = cache.attend_decode(0, q, positions)
    facts["decode_parity_bitwise"] = bool(
        (outs["kernel_interpret"] == outs["fallback"]).all())

    # -- int8 kernel vs dequantize-then-matmul tolerance -----------------
    w = jax.random.normal(k4, (64, 24), jnp.float32)
    qq, ss = quantize_chunked(w, jax.random.PRNGKey(7), 24)
    qt = QuantizedTensor(qq, ss, (64, 24), jnp.dtype(jnp.float32))
    xs = jax.random.normal(jax.random.PRNGKey(8), (3, 64), jnp.float32)
    got = int8_matmul(xs, qt, interpret=True)
    ref = xs @ qt.dequantize()
    denom = float(jnp.max(jnp.abs(ref))) or 1.0
    facts["int8_rel_err"] = float(jnp.max(jnp.abs(got - ref))) / denom
    return facts


#: int8 kernel vs dequantize-then-matmul: same int8 payload, so only the
#: scale-application association differs — normal fp32 rounding, ~1e-7
INT8_REL_TOL = 1e-5


def audit_serve_decode_kernel() -> dict:
    """Audit the serving decode fast path (ISSUE 18): the pallas paged
    decode kernel and fused int8 matmul actually dispatch as TPU custom
    calls (with the kernel-off lowering as the negative proof), every
    paged call reads the whole K/V pool (ISSUE 26: no per-layer slice, so
    no per-layer copy), the kernel-on step keeps the donation /
    zero-collective contract, and the kernel is bit-identical to the
    fallback on CPU."""
    facts = _serve_decode_kernel_artifact()
    violations: list[str] = []
    if facts["custom_calls_on"] < facts["n_layers"]:
        violations.append(
            f"kernel-on TPU lowering has {facts['custom_calls_on']} "
            f"tpu_custom_call(s) < n_layers={facts['n_layers']} — the "
            f"paged decode kernel is not dispatching per layer")
    partial = [c for c in facts["paged_calls"]
               if c["sliced"] or c["shapes"] != [facts["pool_shape"]] * 2]
    if len(facts["paged_calls"]) < facts["n_layers"] or partial:
        violations.append(
            f"{len(partial)} of {len(facts['paged_calls'])} paged-decode "
            f"call(s) (n_layers={facts['n_layers']}) take K/V operands "
            f"that are not the whole {facts['pool_shape']} pool "
            f"({partial[:1]}) — a sliced pool is copied on the chip "
            f"before every call")
    if facts["custom_calls_off"] != 0:
        violations.append(
            f"kernel-off TPU lowering has {facts['custom_calls_off']} "
            f"tpu_custom_call(s) — the negative proof failed, so the "
            f"positive count above proves nothing")
    if facts["custom_calls_int8"] < 1:
        violations.append(
            "int8_matmul TPU lowering has no tpu_custom_call — the "
            "fused int8 kernel is not compiling to a Mosaic call")
    if facts["alias_count"] < 2:
        violations.append(
            f"k/v pool donation not applied in the kernel-on step: "
            f"{facts['alias_count']} aliased buffers < 2")
    if facts["collectives"]:
        violations.append(
            f"collectives in the kernel-on serve step: "
            f"{facts['collectives']}")
    if facts["host_callbacks"]:
        violations.append(
            f"host callbacks in the kernel-on serve step: "
            f"{facts['host_callbacks']}")
    if not facts["decode_parity_bitwise"]:
        violations.append(
            "pallas paged decode (interpret) is NOT bit-identical to the "
            "fallback across null blocks / shared prefix / ragged "
            "positions")
    if facts["int8_rel_err"] > INT8_REL_TOL:
        violations.append(
            f"int8 kernel deviates from dequantize-then-matmul: rel err "
            f"{facts['int8_rel_err']:.2e} > {INT8_REL_TOL:.0e}")
    return {"kind": "serve-kernel", "ok": not violations,
            "violations": violations, **facts}


# -- expert layer: the grouped products (ISSUE 28) ---------------------------

#: a small ``HybridLM`` whose expert widths the grouped-product kernel's
#: gate admits (latent and expert width whole numbers of 128, bf16):
#: traced and lowered for the TPU, never compiled or run
SERVE_EXPERT_CFG = {
    "pattern": "MEM*E", "dim": 128, "vocab": 256, "seq_len": 64,
    "heads": 4, "kv_heads": 2, "head_dim": 32, "mamba_heads": 4,
    "mamba_head_dim": 32, "state_size": 16, "n_groups": 2, "chunk_size": 8,
    "n_experts": 16, "experts_held": (4, 8), "top_k": 4, "latent": 128,
    "expert_dim": 256, "shared_dim": 128, "weights": "bf16",
    "precision": "bf16"}

_SHLO_GROUPED_CALL = re.compile(
    r"stablehlo\.custom_call @tpu_custom_call\(([^)]*)\).*"
    r"kernel_name = \"grouped_matmul\".* : \(([^)]*)\) -> ")
_SHLO_RAGGED_DOT = re.compile(
    r"\"chlo\.ragged_dot\"\(([^)]*)\).* : \(([^)]*)\) -> ")


def grouped_product_calls(stablehlo_text: str) -> list[dict]:
    """Every grouped product in a TPU lowering's StableHLO text; -> one
    record per product: ``{"via": "kernel" | "ragged_dot", "weight": the
    ``[E, K, N]`` operand's shape, "weight_from": "parameter" when that
    operand is an argument of the program itself, else the op that made
    it}``.

    The kernel's weight operand has to be the parameter leaf: a custom
    call's operand is a whole buffer, so a ``convert`` (a leaf held in
    another dtype), a ``slice`` or any other op in front of it is a copy of
    the whole stack of experts on the chip ahead of every call
    (:mod:`theanompi_tpu.ops.pallas_grouped_matmul`)."""
    calls, defs = [], {}
    for line in stablehlo_text.splitlines():
        if "func.func" in line:
            defs = {}  # SSA names are per function
        m = _SHLO_DEF.match(line)
        if m:
            defs[m.group(1)] = m.group(2)
        for via, pattern, at in (("kernel", _SHLO_GROUPED_CALL, -1),
                                 ("ragged_dot", _SHLO_RAGGED_DOT, 1)):
            c = pattern.search(line)
            if not c:
                continue
            name = [n.strip() for n in c.group(1).split(",")][at]
            kind = [t.strip() for t in c.group(2).split(", ")][at]
            calls.append({
                "via": via,
                "weight": [int(n) for n in
                           kind[len("tensor<"):].split("x")[:-1]],
                "weight_from": ("parameter" if name.startswith("%arg")
                                else defs.get(name, "unknown"))})
    return calls


def _hybrid_decode_tpu_text(eng) -> str:
    """The StableHLO text of a ``HybridLM`` engine's decode program, traced
    here and lowered for the TPU (never compiled or run)."""
    import jax
    import jax.numpy as jnp

    b = eng.max_batch
    args = (eng.params, eng._k, eng._v,
            jnp.zeros((b, eng.max_blocks_per_seq), jnp.int32),
            jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
            jnp.zeros((b,), jnp.float32), jnp.zeros((b,), jnp.int32),
            eng._base_key, eng._state)
    return jax.jit(eng._decode_impl, donate_argnums=eng._donate) \
        .trace(*args).lower(lowering_platforms=("tpu",)).as_text()


@functools.lru_cache(maxsize=None)
def _expert_products_artifact() -> dict:
    """The decode program of a small ``HybridLM`` lowered for the TPU with
    the grouped-product kernel pinned on and with it off."""
    import jax

    from theanompi_tpu.models.hybrid_lm import HybridLM
    from theanompi_tpu.serving.engine import InferenceEngine

    facts: dict = {}
    for variant in ("on", "off"):
        model = HybridLM(dict(SERVE_EXPERT_CFG))
        params, _ = model.init_params(jax.random.PRNGKey(0))
        eng = InferenceEngine(model, params, block_size=8, max_batch=2,
                              decode_kernel=variant)
        if variant == "on":
            # pin the COMPILED kernel (off-TPU "on" resolves to the
            # interpreter): what a TPU host would lower
            model.set_expert_products("kernel")
        facts[f"products_{variant}"] = grouped_product_calls(
            _hybrid_decode_tpu_text(eng))
    layer = model.expert_layer
    e = layer.held[1] - layer.held[0]
    facts["expected"] = model.expert_products
    facts["stacks"] = sorted([[e, layer.latent, layer.expert_dim],
                              [e, layer.expert_dim, layer.latent]])
    return facts


def audit_expert_products() -> dict:
    """Audit the expert layer's grouped products (ISSUE 28): with the
    kernel on, every product of the decode program is a ``grouped_matmul``
    custom call whose weight operand is the ``[E, K, N]`` parameter leaf
    itself (nothing sliced, converted or copied in front of it) and no
    ``ragged_dot`` is left; with it off, every product is a
    ``ragged_dot`` and there is no such call."""
    facts = _expert_products_artifact()
    violations: list[str] = []
    on, off, n = facts["products_on"], facts["products_off"], facts["expected"]
    left = [c for c in on if c["via"] != "kernel"]
    if len(on) != n or left:
        violations.append(
            f"kernel-on decode program has {len(on) - len(left)} "
            f"grouped_matmul call(s) and {len(left)} ragged_dot(s) for "
            f"{n} grouped products — the kernel is not dispatching for "
            f"every product")
    copied = [c for c in on if c["via"] == "kernel" and (
        c["weight_from"] != "parameter" or c["weight"] not in facts["stacks"])]
    if copied:
        violations.append(
            f"{len(copied)} grouped_matmul call(s) take a weight operand "
            f"that is not a whole parameter leaf ({copied[:1]}) — the "
            f"stack of experts is copied on the chip before every call")
    if [c["via"] for c in off] != ["ragged_dot"] * n:
        violations.append(
            f"kernel-off decode program has {[c['via'] for c in off]} for "
            f"{n} grouped products — the negative proof failed")
    return {"kind": "serve-experts", "ok": not violations,
            "violations": violations, **facts}


# -- recurrent-state layer: the decode state update (ISSUE 30) ----------------

#: :data:`SERVE_EXPERT_CFG` with a state the state-update kernel's gate
#: admits (``state_size`` a whole number of 128 lanes)
SERVE_STATE_CFG = {**SERVE_EXPERT_CFG, "state_size": 128}

_SHLO_STATE_CALL = re.compile(
    r"(%[\w#]+)(?::\d+)? = stablehlo\.custom_call @tpu_custom_call"
    r"\(([^)]*)\).*kernel_name = \"mamba_state_update\".* : \(([^)]*)\) -> ")
_SHLO_ALIAS = re.compile(r"output_operand_alias<output_tuple_indices = "
                         r"\[(\d*)\], operand_index = (\d+)")


def state_update_calls(stablehlo_text: str) -> list[dict]:
    """Every ``mamba_state_update`` call in a TPU lowering's StableHLO
    text; -> one record per call: ``{"pool": the state-pool operand's shape
    (the call's last operand), "pool_from": "parameter" when that operand
    is an argument of the program itself, "state_update" when it is the
    pool an earlier such call returned, else the op that made it,
    "aliased": the call's first output is that operand's buffer}``.

    The pool has to reach the kernel whole: a ``slice`` of one layer (or
    anything else) in front of the call is a copy of it on the chip ahead
    of every call, and a call whose output does not alias the pool writes a
    second pool (:mod:`theanompi_tpu.ops.pallas_state_update`)."""
    calls, defs, pools = [], {}, set()
    for line in stablehlo_text.splitlines():
        if "func.func" in line:
            defs, pools = {}, set()  # SSA names are per function
        m = _SHLO_DEF.match(line)
        if m:
            defs[m.group(1)] = m.group(2)
        c = _SHLO_STATE_CALL.search(line)
        if not c:
            continue
        name = [n.strip() for n in c.group(2).split(",")][-1]
        kinds = [t.strip() for t in c.group(3).split(", ")]
        pools.add(f"{c.group(1)}#0")
        calls.append({
            "pool": [int(n) for n in
                     kinds[-1][len("tensor<"):].split("x")[:-1]],
            "pool_from": ("parameter" if name.startswith("%arg")
                          else "state_update" if name in pools
                          else defs.get(name.split("#")[0], "unknown")),
            "aliased": ("0", str(len(kinds) - 1))
            in _SHLO_ALIAS.findall(line)})
    return calls


@functools.lru_cache(maxsize=None)
def _state_update_artifact() -> dict:
    """The decode program of a small ``HybridLM`` lowered for the TPU with
    the state-update kernel pinned on (a CPU host resolves the plain lines)
    and as this host resolves it."""
    import jax

    from theanompi_tpu.models.hybrid_lm import HybridLM
    from theanompi_tpu.ops.mamba2 import pin_state_update
    from theanompi_tpu.serving.engine import InferenceEngine

    facts: dict = {}
    for variant in ("kernel", "plain"):
        with pin_state_update(variant):
            model = HybridLM(dict(SERVE_STATE_CFG))
            params, _ = model.init_params(jax.random.PRNGKey(0))
            eng = InferenceEngine(model, params, block_size=8, max_batch=2,
                                  decode_kernel="off")
            facts[f"calls_{variant}"] = state_update_calls(
                _hybrid_decode_tpu_text(eng))
        facts[f"resolved_{variant}"] = eng.state_update_impl
    facts["expected"] = model.cache_spec()["state_layers"]
    facts["pool_shape"] = list(eng._state["ssm"].shape)
    return facts


def audit_state_update() -> dict:
    """Audit the decode step's recurrent-state update (ISSUE 30): with the
    kernel resolved, every state layer of the decode program is one
    ``mamba_state_update`` custom call whose pool operand is the whole
    ``[L, B, H, P, N]`` float32 pool — the program's own donated argument
    for the first layer, the pool the previous call returned for the next,
    nothing sliced or copied in front — and whose output aliases it; with
    the plain lines resolved there is no such call."""
    facts = _state_update_artifact()
    violations: list[str] = []
    on, n = facts["calls_kernel"], facts["expected"]
    if len(on) != n:
        violations.append(
            f"kernel-on decode program has {len(on)} mamba_state_update "
            f"call(s) for {n} state layer(s) — the kernel is not "
            f"dispatching for every layer")
    copied = [c for c in on
              if c["pool"] != facts["pool_shape"]
              or c["pool_from"] not in ("parameter", "state_update")]
    if copied or (on and on[0]["pool_from"] != "parameter"):
        violations.append(
            f"{len(copied)} mamba_state_update call(s) take a pool operand "
            f"that is not the whole {facts['pool_shape']} pool handed from "
            f"the program's argument ({(copied or on)[:1]}) — a layer of "
            f"the pool is copied on the chip before every call")
    loose = [c for c in on if not c["aliased"]]
    if loose:
        violations.append(
            f"{len(loose)} mamba_state_update call(s) do not alias the "
            f"pool to their output — each writes a second pool")
    if facts["calls_plain"]:
        violations.append(
            f"plain decode program has {len(facts['calls_plain'])} "
            f"mamba_state_update call(s) — the negative proof failed")
    return {"kind": "serve-state", "ok": not violations,
            "violations": violations, **facts}


# -- entry point -------------------------------------------------------------

#: what ``tmlint --hlo-audit`` (and the tier-1 test) audits: the two
#: strategies the acceptance criteria name, their overlapped-schedule
#: locks (ISSUE 12 — the BASELINE step-7 gate), plus the serve decode,
#: partial-prefill (prefix-cache hit, ISSUE 17) and decode-kernel
#: dispatch (ISSUE 18) steps, the expert layer's grouped products
#: (ISSUE 28) and the recurrent-state layer's decode update (ISSUE 30)
DEFAULT_TRAIN_STRATEGIES = ("psum_bucket", "zero1")


def run_default_audits(n_data: int = 4) -> list[dict]:
    """Audit the default artifact set; raise :class:`HLOAuditError` on
    any violation (the CLI maps this to exit 1; the completed reports
    ride on the exception's ``reports`` attribute so the CLI can still
    publish the artifact that shows WHAT failed)."""
    import os

    # the device-count fix must land BEFORE the first backend touch —
    # jax.devices() initializes the backend and latches the count, after
    # which force_host_devices is a no-op for this process
    if "--xla_force_host_platform_device_count=" \
            not in os.environ.get("XLA_FLAGS", ""):
        from theanompi_tpu.parallel.mesh import force_host_devices

        force_host_devices(max(n_data, 8))

    import jax

    if len(jax.devices()) < n_data:
        raise HLOAuditError(
            f"need {n_data} devices for the train-step audit, have "
            f"{len(jax.devices())} — set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_data} "
            f"before jax initializes")
    reports = [audit_train_step(s, n_data) for s in DEFAULT_TRAIN_STRATEGIES]
    # the overlap audits run at n_data=2 (the signature default, shared
    # with the test suite's lru entries): the chain/interleave facts are
    # device-count-independent and the fused-vs-overlapped comparison is
    # at matched n, so extra devices only add compile time
    reports += [audit_overlap_schedule(s)
                for s in DEFAULT_OVERLAP_STRATEGIES]
    reports.append(audit_serve_step())
    reports.append(audit_serve_prefill())
    reports.append(audit_serve_decode_kernel())
    reports.append(audit_expert_products())
    reports.append(audit_state_update())
    bad = [r for r in reports if not r["ok"]]
    if bad:
        err = HLOAuditError("; ".join(
            f"[{r['kind']}:{r.get('strategy', 'decode')}] {v}"
            for r in bad for v in r["violations"]))
        err.reports = reports
        raise err
    return reports
