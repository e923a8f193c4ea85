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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


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
