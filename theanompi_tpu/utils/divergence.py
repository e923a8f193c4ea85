"""Cross-replica divergence check (SURVEY.md §5 race-detection row).

The reference had no race detection; under SPMD the one real invariant is
that *replicated* values stay bit-identical across their device copies —
BSP params after the fused all-reduce, EASGD's center, batch-norm state
under sync-BN.  A divergence means a non-deterministic op, a wrong
``grad_reduce_axes``, or an exchange bug (exactly the class the round-1
Megatron-gradient bug belonged to), and shard_map's ``check_vma=False``
hides it silently.

The check is host-side and collective-free: every device copy of a
replicated leaf is an addressable shard covering the same index, so the
copies can be fetched and compared directly.  Cost is a device→host pull
of the tree — a debug tool, not a per-step assertion; wire it at epoch
boundaries via ``BaseTrainer.check_divergence()``.
"""

from __future__ import annotations

import jax
import numpy as np


def replica_divergence(tree) -> float:
    """Max |difference| between same-index device copies across the tree.

    Leaves without multiple same-index addressable shards (fully sharded
    arrays, scalars on one device) contribute nothing.  0.0 means every
    replicated copy is bit-identical.
    """
    worst = 0.0
    for leaf in jax.tree.leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards is None or len(shards) < 2:
            continue
        by_index: dict = {}
        for s in shards:
            key = tuple(
                (sl.start, sl.stop, sl.step) for sl in s.index
            ) if s.index else ()
            by_index.setdefault(key, []).append(s)
        for copies in by_index.values():
            if len(copies) < 2:
                continue
            arrs = [np.asarray(s.data).astype(np.float64) for s in copies]
            ref_nan = np.isnan(arrs[0])
            for a in arrs[1:]:
                if (np.isnan(a) != ref_nan).any():
                    # a NaN on one copy but not another IS divergence (the
                    # prime symptom of the bugs this tool exists to catch);
                    # naive max() would silently drop the NaN comparison
                    return float("inf")
            # max PAIRWISE spread via elementwise min/max over all copies
            # (comparing only against copies[0] under-reports by up to 2x);
            # matching NaN/inf positions are equal, mixed inf-vs-finite
            # yields inf spread
            stack = np.where(np.isnan(arrs), 0.0, np.stack(arrs))
            hi, lo = stack.max(axis=0), stack.min(axis=0)
            # subtract only where copies differ: matching infs would warn
            # (inf - inf) even though the result is masked
            spread = np.zeros_like(hi)
            np.subtract(hi, lo, out=spread, where=hi != lo)
            worst = max(worst, float(spread.max()) if spread.size else 0.0)
    return worst


def assert_replicas_in_sync(tree, atol: float = 0.0, what: str = "tree") -> float:
    """Raise if replicated copies diverge beyond ``atol``; -> measured max."""
    d = replica_divergence(tree)
    if d > atol:
        raise AssertionError(
            f"replica divergence in {what}: max |delta| = {d} > {atol} — "
            "a replicated value differs between device copies (wrong "
            "reduce axes, non-determinism, or an exchange bug)"
        )
    return d
