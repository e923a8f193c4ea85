"""Grouped matrix product for the dropless expert layer (ISSUE 28).

``rows[M, K] · w[E, K, N]`` by ``group_sizes[E]``: the first
``group_sizes[0]`` rows are multiplied by ``w[0]``, the next
``group_sizes[1]`` by ``w[1]``, and so on — what ``jax.lax.ragged_dot``
computes, as a Pallas TPU kernel that reads each expert's weights once,
and only where the expert has a row.  In decode the expert layer is all
bytes (a few rows against 5.5 MB of weights an expert), so the kernel's
time is the time the weights take to stream from HBM.

**What a visit moves.**  The grid is ``(N tiles, visits)``; a visit is one
(group, row tile) pair in which the group has rows, found from
``group_sizes`` before the call (:func:`group_visits`) and handed to the
index maps by scalar prefetch — the scheme of JAX's
``pallas.ops.tpu.megablox.gmm``, with K whole and no accumulator.  A visit
brings in the row tile ``[tm, K]`` and the group's weight tile
``[K, tn]``, multiplies them with float32 accumulation and stores the rows
that belong to the group into the output tile ``[tm, tn]``.  Visits are in
row order, so successive visits of one group name the same weight tile
and successive groups inside one row tile name the same output tile:
Pallas then skips the copy, a group's weights come in once however many
row tiles it spans, and an output tile goes out once, after its last
group.  A group with no row has no visit and costs no read.  ``tn`` is
the widest multiple of 128 dividing ``N`` whose double-buffered weight
tile fits the budget below; both of the layer's products at the served
widths (1024 x 2688) take ``N`` whole.

**The row tile is 128 rows whatever a group holds.**  The number of
visits is that of the groups with rows plus the row-tile boundaries that
fall inside a group, and a visit's time is its weight tile's: on a v5e the
MXU needs ``tm / 240`` of the time the DMA needs for the same ``[K, tn]``
tile (197 TFLOP/s over 819 GB/s), so up to the MXU's 128 rows a larger
tile costs nothing and cuts fewer groups in two.  ISSUE 28 asked for a
tile read from the expected rows of a group, ``M / n_experts`` (16 rows of
bf16 in decode, 128 in the long prefill buckets); measured on the chip at
the served widths (PERF.md section 6, PR 28) a tile of 16 / 32 / 64 / 128
gave 1.04 / 1.00 / 0.99 / 0.99 ms a product in decode (166 / 145 / 135 /
131 visits) and 1.28 / 1.15 / 1.11 / 1.09 ms in the 512-token bucket, so
:func:`row_tile` reads from ``M`` only what it must: no more rows than
there are.

**Rows past the last group** (``sum(group_sizes) < M``: the expert layer
sorts the assignments to experts it does not hold there) belong to no
visit.  Their output is never written and holds whatever the buffer
held — not zeros, and possibly not finite.  The same holds for the rows
of a visited tile that lie past the last group.  A caller must discard
them by selection (``jnp.where``), never by a product with zero;
``DroplessMoE`` does, and ``tests/test_grouped_matmul_kernel.py`` plants a
NaN there to show nothing of it reaches the layer's output.  A NaN in
such a row of ``rows`` stays in its own output row: every row of a
product depends on that row alone.

``w`` must be handed over whole — the parameter leaf itself, not a slice
or a re-cast of it: a custom call's operand is a whole buffer, and XLA
copies whatever else it is given ahead of every call (ISSUE 26).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the name the custom call carries in a trace (``DEVICE_SCOPES["kernels"]``)
SCOPE = "grouped_matmul"
#: what a double-buffered weight tile may take of VMEM
_WEIGHT_TILE_BYTES = 12 * 1024 * 1024
_MAX_ROW_TILE = 128


def _sublane(dtype) -> int:
    return 32 // jnp.dtype(dtype).itemsize  # 8 fp32, 16 bf16


def row_tile(m: int, dtype=jnp.bfloat16) -> int:
    """The row tile for ``m`` sorted rows: 128 (module docstring), or ``m``
    rounded up to the dtype's sublane tile where there are fewer rows."""
    sub = _sublane(dtype)
    return min(_MAX_ROW_TILE, -(-m // sub) * sub)


def _n_tile(k: int, n: int, itemsize: int) -> int | None:
    """The widest multiple of 128 dividing ``n`` whose ``[k, tn]`` tile,
    held twice, fits the weight budget; None where not even 128 does."""
    fits = [tn for tn in range(128, n + 1, 128)
            if n % tn == 0 and 2 * k * tn * itemsize <= _WEIGHT_TILE_BYTES]
    return max(fits) if fits else None


def grouped_matmul_supported(k: int, n: int, dtype=jnp.bfloat16) -> bool:
    """Shape gate for the COMPILED kernel: bf16 operands (the precision the
    expert layer is served in, and the only one compiled and measured on
    the chip), ``k`` and ``n`` whole numbers of 128 lanes, and a weight
    tile that fits.  Callers take ``lax.ragged_dot`` when False; the
    interpreter (tests) runs any shape and dtype."""
    if jnp.dtype(dtype) != jnp.bfloat16:
        return False
    if k % 128 or n % 128:
        return False
    return _n_tile(k, n, 2) is not None


def group_visits(group_sizes, m: int, tm: int):
    """The kernel's schedule, from ``group_sizes`` ``[E]`` int32 over ``m``
    rows in tiles of ``tm``: -> (``offsets`` ``[E + 1]``: the row where each
    group starts, ``group_ids`` and ``tile_ids`` ``[V]``: the group and the
    row tile of each visit, in row order, ``n_visits``: how many of the
    ``V = tiles + E - 1`` slots are real; the rest repeat the last).  One
    schedule serves both products of an expert layer."""
    e = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_end = jnp.cumsum(tiles)
    n_visits = visit_end[-1]
    v = jnp.arange(pl.cdiv(m, tm) + e - 1, dtype=jnp.int32)
    # a slot past the last visit repeats it: the grid never runs it
    v = jnp.minimum(v, jnp.maximum(n_visits - 1, 0))
    # the group of a visit: how many groups end at or before it (one
    # comparison of [V, E]: a binary search is a loop of tiny device ops)
    group_ids = jnp.minimum(
        jnp.sum(visit_end[None, :] <= v[:, None], axis=1, dtype=jnp.int32),
        e - 1)
    tile_ids = first[group_ids] + v - (visit_end - tiles)[group_ids]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group_ids, tile_ids.astype(jnp.int32), n_visits


def _kernel(offsets_ref, group_ref, tile_ref, rows_ref, w_ref, o_ref, *, tm):
    v = pl.program_id(1)
    g = group_ref[v]
    acc = jnp.dot(rows_ref[...], w_ref[...],
                  preferred_element_type=jnp.float32)
    row = tile_ref[v] * tm + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
    mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
    # the tile's other rows keep what earlier groups stored there
    o_ref[...] = jnp.where(mine, acc.astype(o_ref.dtype), o_ref[...])


def grouped_matmul(rows, w, group_sizes, *, tm: int, out_dtype=None,
                   visits=None, interpret: bool | None = None):
    """``rows`` ``[M, K]`` · ``w`` ``[E, K, N]`` by ``group_sizes`` ``[E]``
    -> ``[M, N]`` in ``out_dtype`` (``rows.dtype`` when None), float32
    accumulation.  ``tm``: the row tile (:func:`row_tile`).  ``visits``:
    :func:`group_visits` of the same sizes, ``M`` and ``tm``, where a
    caller has it already.  Rows past ``sum(group_sizes)`` come out
    unwritten (module docstring).  ``interpret=None``: compiled on a TPU
    (gate with :func:`grouped_matmul_supported`), the interpreter
    elsewhere."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, k = rows.shape
    e, k_w, n = w.shape
    if k_w != k or group_sizes.shape != (e,):
        raise ValueError(f"grouped_matmul: rows {rows.shape}, w {w.shape}, "
                         f"group_sizes {group_sizes.shape} do not agree")
    if rows.dtype != w.dtype:
        raise ValueError(f"grouped_matmul: rows are {rows.dtype}, w is "
                         f"{w.dtype}: hand over the leaf in the rows' dtype")
    out_dtype = jnp.dtype(out_dtype or rows.dtype)
    if not interpret and not grouped_matmul_supported(k, n, rows.dtype):
        raise ValueError(
            f"grouped_matmul: unsupported K={k} N={n} ({rows.dtype}) for "
            "the compiled kernel; gate with grouped_matmul_supported()")
    # the interpreter takes any width: N whole where no multiple of 128 fits
    tn = _n_tile(k, n, rows.dtype.itemsize) or n
    if visits is None:
        visits = group_visits(group_sizes, m, tm)
    offsets, group_ids, tile_ids, n_visits = visits

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n // tn, n_visits),
        in_specs=[
            pl.BlockSpec((tm, k), lambda j, v, off, gid, tid: (tid[v], 0)),
            pl.BlockSpec((None, k, tn),
                         lambda j, v, off, gid, tid: (gid[v], 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn),
                               lambda j, v, off, gid, tid: (tid[v], j)),
    )
    held = (2 * (k * tn + tm * k) * rows.dtype.itemsize
            + 2 * tm * tn * out_dtype.itemsize + 2 * tm * tn * 4)
    fn = pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=held + 8 * 1024 * 1024),
        interpret=interpret,
        name=SCOPE,
    )
    with jax.named_scope(SCOPE):  # the custom call's name in a trace
        return fn(offsets, group_ids, tile_ids, rows, w)
