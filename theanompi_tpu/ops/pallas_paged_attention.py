"""Fused paged-attention decode kernel (the serving fast path, ISSUE 18).

One Pallas kernel per (layer, decode step) over the WHOLE pool: grid
``(batch, blocks)`` with the **block table driving the KV index_map** —
each grid step DMAs exactly the pool block the table names, out of the
``[L, num_blocks, block_size, H, Dh]`` pool at the layer (a constant of
the call, or a traced entry of a looped stack: ISSUE 31), so the
gather that ``serving/kv_cache.py`` does with a materialized
``[B, T_max, H, Dh]`` ``jnp.take`` never touches HBM here.  Nothing may
slice the pool before the call: a custom call's operand is a whole
buffer, so XLA copies a ``k_pool[layer]`` operand ahead of every call
(ISSUE 26: 134 MB each for K and V at the served size, a third of the
step's device time over 24 layers).  Online softmax carries
(running max, normalizer, accumulator) in VMEM scratch with the block
index innermost, the same Mosaic accumulation layout as the flash train
kernels (:mod:`theanompi_tpu.ops.pallas_attention`).

Null-block gating: by the cache contract, table entries past a sequence's
length all name the reserved null block (block 0) — exactly the entries
with ``j * block_size > positions[b]``.  Those grid steps are gated off
with ``pl.when`` (no MXU/VPU work) and their DMA is elided by clamping the
KV index_map at the last needed block (consecutive steps re-reference the
same block, so Mosaic's pipeline skips the copy).  Inside the last real
block, tail positions mask with ``_NEG_INF`` like every attention path in
the repo.  Inactive slots (position 0, all-null table) attend over exactly
one garbage token — finite garbage out, discarded by the scheduler,
identical to the fallback's contract.

Bit-equality lock: the CPU fallback (``PagedKVCache.attend_decode``)
computes the SAME blockwise online-softmax recurrence in the same op
order, so ``interpret=True`` here is bit-identical to it — not merely
close — across null-block padding, prefix-shared blocks, and ragged
positions (tests/test_paged_decode_kernel.py).  Fully-masked blocks are
exact no-ops of the recurrence (correction ``exp(0) == 1.0``, masked
probabilities underflow to ``0.0``), which is what makes gating them off
here exact rather than approximate.

Score and context products are elementwise multiply + ``jnp.sum``
reductions rather than ``dot_general``: gemm kernels pick different
accumulation strategies per shape, which breaks bit-equality between the
kernel's per-head 2D dots and the fallback's batched einsums (observed
at the ulp level), while trailing/sublane reductions are order-stable
across batching layouts.  At decode's one-query-per-slot shape the
kernel is DMA-bound, not MXU-bound, so forgoing the MXU costs nothing —
the flash PREFILL kernels keep their dots.

**Grouped K/V pools have a kernel of their own** (ISSUE 34, the second half
of this module): a pool of fewer K/V heads than query heads never met the
kernel above (one K/V head per query head, one 16-token block a grid step,
its time its grid's), and its pure-JAX gather was 65 ms of a 70 ms decode
step at 32 slots x 16 384-token tables.  ``paged_attend_decode_grouped``
shares no code with the kernel above and owes no bit-equality: a grid step
is a SLOT, and inside it a loop as long as the slot's own context fetches
``_GROUPED_STEP_TOKENS`` of it a step — whole blocks by their table
entries, ``pltpu.make_async_copy`` out of the pools left in HBM, into one
of two VMEM buffers while the step before is computed — so a table entry
past the context costs neither a grid step nor a byte.  The buffer ``[blocks,
block_size, Hkv, Dh]`` is read as rows ``[tokens x Hkv, Dh]`` and EVERY
query head meets every row in one MXU product, keeping the columns of its
own K/V head by a mask: no head is sliced out of a ``(Hkv, Dh)`` tile, and
the pool goes in as the cache holds it (a v5e lays ``[..., 8, 128]`` and
``[..., 2, 128]`` bf16 out as ``T(8,128)(2,1)`` / ``T(2,128)(2,1)`` and
Mosaic reads either with no copy ahead of the call).  Operands and
accumulation are the grouped fallback's: products of the pool's dtype into
float32, a float32 online softmax.  Measured alone on a v5e (PERF.md
section 6, PR 34): 0.915 ms a layer for the 0.61 GB in context at 48 over 8
heads, 671 GB/s, the copies alone 0.889 ms; 0.95 ms for 0.13 GB at 32 over
2 (copies alone 0.40 ms: there the products and masks lead).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
#: the names the two kernels' custom calls carry in a trace
#: (``DEVICE_SCOPES["kernels"]``): one K/V head a query head, and a pool of
#: fewer (grouped) K/V heads (ISSUE 34)
SCOPE = "paged_decode"
SCOPE_GROUPED = "paged_decode_grouped"


def _decode_kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, block_size, nb, heads):
    b = pl.program_id(0)
    j = pl.program_id(1)
    bs = block_size

    @pl.when(j == 0)
    def _():
        m_scr[:, :] = jnp.full_like(m_scr[:, :], _NEG_INF)
        l_scr[:, :] = jnp.zeros_like(l_scr[:, :])
        acc_scr[:, :] = jnp.zeros_like(acc_scr[:, :])

    pos_b = pos_ref[b]

    # null-block gate: table entries past the sequence all point at block
    # 0 by contract; their recurrence step is an exact no-op (see module
    # docstring), so skipping it preserves bit-equality with the fallback
    @pl.when(j * bs <= pos_b)
    def _():
        d = q_ref.shape[-1]
        scale = d ** -0.5
        t_abs = j * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
        for h in range(heads):
            qf = q_ref[h:h + 1, :].astype(jnp.float32) * scale  # [1, Dh]
            k_h = k_ref[:, h, :].astype(jnp.float32)            # [bs, Dh]
            s = jnp.sum(k_h * qf, axis=-1, keepdims=True)       # [bs, 1]
            s = jnp.where(t_abs <= pos_b, s, _NEG_INF)
            m = m_scr[h:h + 1, :1]                              # [1, 1]
            m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)                              # [bs, 1]
            m_scr[h:h + 1, :] = jnp.broadcast_to(
                m_new, (1, m_scr.shape[1]))
            l_scr[h:h + 1, :] = (l_scr[h:h + 1, :] * corr
                                 + jnp.sum(p, axis=0, keepdims=True))
            ctx = jnp.sum(p * v_ref[:, h, :].astype(jnp.float32),
                          axis=0, keepdims=True)                # [1, Dh]
            acc_scr[h:h + 1, :] = acc_scr[h:h + 1, :] * corr + ctx

    @pl.when(j == nb - 1)
    def _():
        o_ref[:, :] = (acc_scr[:, :]
                       / l_scr[:, :][:, :1]).astype(o_ref.dtype)


def _decode_kernel_at(tables_ref, pos_ref, layer_ref, *refs, **static):
    """:func:`_decode_kernel` under a third scalar-prefetch operand: a
    traced layer, which the K/V ``index_map`` alone reads."""
    del layer_ref
    _decode_kernel(tables_ref, pos_ref, *refs, **static)


def paged_decode_supported(heads: int, head_dim: int,
                           dtype=jnp.float32) -> bool:
    """Shape gate for the COMPILED kernel: the KV block's trailing
    ``(heads, head_dim)`` dims must tile ((8, 128) fp32 / (16, 128)
    bf16).  Callers fall back to the pure-JAX gather when False — tiny
    test shapes run the kernel under ``interpret=True`` only."""
    sublane = 16 if jnp.dtype(dtype) == jnp.bfloat16 else 8
    return heads % sublane == 0 and head_dim % 128 == 0


def paged_attend_decode(k_pool, v_pool, layer, tables,
                        block_size: int, q, positions,
                        interpret: bool | None = None):
    """Paged decode attention over layer ``layer`` of the whole pools.

    ``k_pool``/``v_pool`` ``[L, num_blocks, block_size, H, Dh]`` (the
    cache's own arrays, never a slice of them), ``layer`` a Python int (a
    constant of the ``index_map``) or a traced int32 scalar (a loop's
    entry: it rides as a third scalar-prefetch operand of the same kernel
    and must lie inside the pool), ``tables`` ``[B, max_blocks_per_seq]``
    int32, ``q`` ``[B, H, Dh]``, ``positions`` ``[B]`` (each query's own
    0-based position, already written) -> context ``[B, H, Dh]``.
    ``interpret=None`` auto-selects: compiled on TPU (gate with
    :func:`paged_decode_supported`), interpreter elsewhere.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, d = q.shape
    nb = tables.shape[1]
    bs = block_size
    traced = isinstance(layer, jax.Array)
    if not traced:
        layer = int(layer)
        if not 0 <= layer < k_pool.shape[0]:
            raise ValueError(f"paged_attend_decode: layer {layer} outside "
                             f"the pool's {k_pool.shape[0]} layers")
    if not interpret and not paged_decode_supported(h, d, q.dtype):
        raise ValueError(
            f"paged_attend_decode: unsupported shape H={h} Dh={d} "
            f"({q.dtype}) for compiled Mosaic tiling; gate with "
            "paged_decode_supported()")

    def kv_map(i, j, t, p, *at):
        # DMA elision: past-the-end (null-block) steps re-reference the
        # last needed block, so their copies never issue; compute stays
        # gated on the REAL j, so numerics are untouched.  The layer is
        # a constant of this call's index_map, or its third prefetched
        # scalar
        return (at[0][0] if at else layer,
                t[i, jnp.minimum(j, p[i] // bs)], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 if traced else 2,
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec((None, h, d), lambda i, j, *_: (i, 0, 0)),
            pl.BlockSpec((None, None, bs, h, d), kv_map),
            pl.BlockSpec((None, None, bs, h, d), kv_map),
        ],
        out_specs=pl.BlockSpec((None, h, d), lambda i, j, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 128), jnp.float32),   # running max (lane-bcast)
            pltpu.VMEM((h, 128), jnp.float32),   # normalizer (lane-bcast)
            pltpu.VMEM((h, d), jnp.float32),     # output accumulator
        ],
    )
    fn = pl.pallas_call(
        functools.partial(_decode_kernel_at if traced else _decode_kernel,
                          block_size=bs, nb=nb, heads=h),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )
    at = (jnp.asarray(layer, jnp.int32).reshape(1),) if traced else ()
    with jax.named_scope("paged_decode"):  # the custom call's name in a trace
        return fn(tables, jnp.asarray(positions, jnp.int32), *at, q, k_pool,
                  v_pool)


# -- grouped K/V pools (ISSUE 34) ------------------------------------------------
#: tokens of one slot's context a loop step of the grouped kernel reads
#: (PERF.md section 6, PR 34: swept on a v5e at both served geometries)
_GROUPED_STEP_TOKENS = 256


def _grouped_kernel(tables_ref, pos_ref, layer_ref, q_ref, k_hbm, v_hbm,
                    o_ref, k_buf, v_buf, sems, *, block_size, per, group):
    """One slot of the batch: its context in steps of ``per`` blocks, each
    fetched whole by the table into one of two buffers while the step
    before it is computed.  Every query head meets every (token, K/V head)
    row of a step in ONE product and keeps the columns of its own K/V head:
    the ``[per, block_size, Hkv, Dh]`` buffer is read as rows ``[per *
    block_size * Hkv, Dh]`` and no head is ever sliced out of it."""
    b = pl.program_id(0)
    layer = layer_ref[0]
    pos = pos_ref[b]
    bs = block_size
    hkv = k_buf.shape[3]
    h, d = q_ref.shape
    t = per * bs
    rows = t * hkv
    last = pos // bs              # the block that holds the query's own token
    steps = pos // t + 1          # steps that hold a position <= ``pos``

    def fetch(c, slot):
        # blocks past the last one re-read it: finite values that the mask
        # drops, and nothing outside the slot's own blocks is touched
        for r in range(per):
            blk = tables_ref[b, jnp.minimum(c * per + r, last)]
            pltpu.make_async_copy(k_hbm.at[layer, blk], k_buf.at[slot, r],
                                  sems.at[0, slot]).start()
            pltpu.make_async_copy(v_hbm.at[layer, blk], v_buf.at[slot, r],
                                  sems.at[1, slot]).start()

    def arrived(pool, buf, which, slot):
        for r in range(per):      # a wait reads its copy's size only
            pltpu.make_async_copy(pool.at[0, 0], buf.at[slot, r],
                                  sems.at[which, slot]).wait()

    fetch(0, 0)
    # the fallback's operands: the scaled query in the pool's dtype
    q = (q_ref[...].astype(jnp.float32) * d ** -0.5).astype(k_buf.dtype)
    # row ``r`` of a step is token ``r // hkv`` under K/V head ``r % hkv``
    col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
    own = (jax.lax.broadcasted_iota(jnp.int32, (h, 1), 0) // group
           == col % hkv)
    token = col // hkv

    def step(c, carry):
        m, l, acc = carry
        slot = c % 2

        @pl.when(c + 1 < steps)
        def _():
            fetch(c + 1, 1 - slot)

        arrived(k_hbm, k_buf, 0, slot)
        s = jax.lax.dot_general(q, k_buf[slot].reshape(rows, d),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(own & (c * t + token <= pos), s, _NEG_INF)
        # every step holds a position <= ``pos``, so ``m_new`` is a real
        # score and a masked column's ``exp`` underflows to exactly 0
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        arrived(v_hbm, v_buf, 1, slot)
        ctx = jnp.dot(p.astype(v_buf.dtype), v_buf[slot].reshape(rows, d),
                      preferred_element_type=jnp.float32)
        return (m_new, l * corr + jnp.sum(p, axis=-1, keepdims=True),
                acc * corr + ctx)

    _, l, acc = jax.lax.fori_loop(
        0, steps, step, (jnp.full((h, 1), _NEG_INF, jnp.float32),
                         jnp.zeros((h, 1), jnp.float32),
                         jnp.zeros((h, d), jnp.float32)))
    o_ref[...] = (acc / l).astype(o_ref.dtype)


def paged_decode_grouped_supported(heads: int, kv_heads: int, head_dim: int,
                                   block_size: int,
                                   dtype=jnp.bfloat16) -> bool:
    """Shape gate for the COMPILED grouped kernel, as wide as a v5e compile
    has shown it (tests/test_looped_lm.py, the gate's cases): a bfloat16
    or float32 pool of fewer K/V heads than query heads, each a whole
    group of them; the K/V heads a power of two (a block's trailing ``[Hkv,
    Dh]`` is then one tile of the pool's layout, or whole tiles: 3 or 6
    heads are padded in HBM or refused by Mosaic), at least one packed row
    of them; query heads of whole sublanes, head dims of whole lanes, and a
    block's ``block_size x kv_heads`` rows whole tiles.  Callers take the
    pure-JAX gather when False; the interpreter (tests) runs any grouped
    shape."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return False
    packed = 4 // dtype.itemsize          # rows in one 32-bit sublane
    return (packed <= kv_heads < heads and heads % kv_heads == 0
            and kv_heads & (kv_heads - 1) == 0
            and heads % 8 == 0 and head_dim % 128 == 0
            and (block_size * kv_heads) % (8 * packed) == 0)


def paged_attend_decode_grouped(k_pool, v_pool, layer, tables,
                                block_size: int, q, positions,
                                interpret: bool | None = None,
                                blocks_per_step: int | None = None):
    """:func:`paged_attend_decode` for pools of fewer K/V heads than ``q``
    has query heads (query head ``h`` reads K/V head ``h // (H // Hkv)``).

    ``k_pool``/``v_pool`` ``[L, num_blocks, block_size, Hkv, Dh]`` whole,
    ``layer`` a Python int or a traced int32 scalar, ``tables`` ``[B,
    max_blocks_per_seq]`` int32, ``q`` ``[B, H, Dh]`` with ``H`` a whole
    number of ``Hkv``, ``positions`` ``[B]`` -> context ``[B, H, Dh]``.
    A grid step is one slot; inside it a loop as long as the slot's own
    context reads ``blocks_per_step`` blocks a step (None: the module's
    ``_GROUPED_STEP_TOKENS``), each by its table entry, double-buffered —
    so a table entry past the context costs nothing, neither a grid step
    nor a byte.  Operand and accumulation types are the grouped fallback's
    (``PagedKVCache._attend_decode_grouped``): products of the pool's dtype
    accumulated in float32, a float32 online softmax.  The two differ by
    rounding (another order of the same sums), not to the bit.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, d = q.shape
    n_layers, _, bs, hkv, _ = k_pool.shape
    if bs != block_size:
        raise ValueError(f"paged_attend_decode_grouped: block_size "
                         f"{block_size} over a pool of {bs}-token blocks")
    if not 0 < hkv < h or h % hkv:
        raise ValueError(f"paged_attend_decode_grouped: {h} query heads "
                         f"over {hkv} K/V heads is no grouped pool")
    traced = isinstance(layer, jax.Array)
    if not traced and not 0 <= int(layer) < n_layers:
        raise ValueError(f"paged_attend_decode_grouped: layer {layer} "
                         f"outside the pool's {n_layers} layers")
    if not interpret and not paged_decode_grouped_supported(
            h, hkv, d, bs, k_pool.dtype):
        raise ValueError(
            f"paged_attend_decode_grouped: unsupported shape H={h} "
            f"Hkv={hkv} Dh={d} block={bs} ({k_pool.dtype}) for compiled "
            "Mosaic tiling; gate with paged_decode_grouped_supported()")
    per = blocks_per_step or max(1, _GROUPED_STEP_TOKENS // bs)
    per = min(int(per), tables.shape[1])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((None, h, d), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # the pools stay in HBM,
            pl.BlockSpec(memory_space=pl.ANY),   # whole: blocks by DMA
        ],
        out_specs=pl.BlockSpec((None, h, d), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, per, bs, hkv, d), k_pool.dtype),
            pltpu.VMEM((2, per, bs, hkv, d), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),     # (K | V, buffer)
        ],
    )
    fn = pl.pallas_call(
        functools.partial(_grouped_kernel, block_size=bs, per=per,
                          group=h // hkv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )
    with jax.named_scope(SCOPE_GROUPED):
        return fn(jnp.asarray(tables, jnp.int32),
                  jnp.asarray(positions, jnp.int32),
                  jnp.asarray(layer, jnp.int32).reshape(1), q, k_pool,
                  v_pool)
