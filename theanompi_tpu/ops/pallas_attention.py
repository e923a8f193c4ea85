"""Fused flash attention as Pallas TPU kernels (forward + backward).

The hot op of the transformer stack (SURVEY.md §5 long-context row), written
for the hardware rather than left to XLA's generic lowering.  All three
kernels use the standard Mosaic accumulation layout: the KV (or Q) tile
index is the *innermost grid dimension*, carries live in VMEM scratch that
is reset when that index wraps to 0, and outputs are written on its last
step.  K/V stream through as tiles — nothing O(T) beyond the operand
arrays is ever resident in VMEM, so sequence length is bounded by HBM, not
by the 16 MB VMEM (a full-array-in-VMEM variant died at T=16384).

Why a backward kernel at all: XLA's full-scores backward materializes
[T, T] outright, and autodiff of the blockwise loop saves every per-block
probability residual — T² bytes either way, which is what dies first at
long context.  These kernels recompute probabilities from (q, k, v, lse)
tile by tile, so training memory stays O(T·d).  Measured on the shared
v5e chip (chained-dispatch slope timing, B8/H8/D64-class shapes): at the
(512,1024) default blocks the train step beats XLA blockwise ~3.2x at
T=2048 and ~4.7x at T=8192, and T=16384 trains where both XLA paths
out-of-memory.  Block size is the whole game — the same kernels at
(128,128) LOSE to XLA; small tiles drown in DMA latency.  Short
sequences clamp the blocks down automatically.

The causal loop skips tiles strictly above the diagonal twice over: their
MXU work is gated off with ``pl.when``, and their K/V (resp. q/dO) DMA is
elided by clamping the streamed operand's ``index_map`` at the diagonal —
Mosaic's pipeline skips the copy when consecutive steps reference the same
block, so masked tiles are never fetched from HBM.  Measured effect
(interleaved A/B vs the round-2 kernels, wide-spread slope protocol):
neutral at T<=8192 — the kernels are VPU/softmax-bound there and DMA fully
overlaps — and 1.10x at T=16384 where the K/V streams start to matter.
Per-component bisect at T=8192 (B2/H8/D64, fwd): matmuls+DMA 0.77 ms,
+max/exp 1.75 ms, full online-softmax 2.8 ms — the softmax VPU chain, not
the MXU or HBM, is the kernel's floor; exp2 tricks and parallel
dimension_semantics both measured SLOWER, and the only cheap win kept is
the scale folded onto the small q tile instead of the full score matrix.
``interpret=True`` runs the same kernels on CPU for tests; on TPU the
Mosaic compiler takes them.  T must divide by ``block_q``/``block_k`` and
the row-vector transport tiles require ``block_q % 128 == 0`` on TPU
(callers fall back to the XLA blockwise path otherwise — see
``flash_attention_supported``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _tile_needed(qi, ki, block_q, block_k, causal, window=None, block=None):
    """Whether tile (qi, ki) has any visible keys (causal skip predicate;
    with a ``window`` also: its last key is not behind the first query's;
    with a ``block``: its first key is in the last query's block or an
    earlier one)."""
    if not causal:
        return True
    if block is not None:
        return (qi * block_q + block_q - 1) // block >= (ki * block_k) // block
    needed = qi * block_q + block_q - 1 >= ki * block_k
    if window is not None:
        needed = jnp.logical_and(
            needed, ki * block_k + block_k - 1 > qi * block_q - window)
    return needed


def _last_needed_k(qi, block_q, block_k, block=None):
    """Last k-tile index with visible keys for q-tile ``qi`` (causal; with
    a ``block``: the last key of the last query's block)."""
    if block is not None:
        return (((qi * block_q + block_q - 1) // block + 1) * block - 1) \
            // block_k
    return (qi * block_q + block_q - 1) // block_k


def _first_needed_k(qi, block_q, block_k, window):
    """First k-tile index with keys inside q-tile ``qi``'s ``window``."""
    return jnp.maximum(qi * block_q - window + 1, 0) // block_k


def _first_needed_q(ki, block_q, block_k):
    """First q-tile index that can see k-tile ``ki`` (causal)."""
    return (ki * block_k) // block_q


# Causal DMA elision: Mosaic's pipeline only issues a copy when an operand's
# block index CHANGES between consecutive grid steps.  Clamping the streamed
# operand's index_map to the last/first tile the causal mask can ever need
# makes every masked-tile iteration re-reference the previous tile — so
# tiles strictly above the diagonal are never fetched from HBM at all
# (previously only their MXU work was skipped; their K/V DMA still burned
# ~2x bandwidth at long T).  Compute stays gated on the REAL program ids
# via ``_tile_needed``, so numerics are untouched.


def _causal_tile_mask(qi, ki, block_q, block_k, window=None, block=None):
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    if block is not None:  # block-causal: ``j < (i // block + 1) * block``
        return q_pos // block >= k_pos // block
    if window is None:
        return q_pos >= k_pos
    return jnp.logical_and(q_pos >= k_pos, q_pos - k_pos < window)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dimension_numbers=(dims, ((), ())),
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _tile_full(qi, ki, block_q, block_k, window=None, block=None):
    """Tile entirely below the diagonal (and, with a ``window``, entirely
    inside the last query's; with a ``block``, its last key in the first
    query's block or an earlier one): every key visible, no mask ops."""
    if block is not None:
        return (qi * block_q) // block >= (ki * block_k + block_k - 1) // block
    full = qi * block_q >= ki * block_k + block_k - 1
    if window is not None:
        full = jnp.logical_and(
            full, ki * block_k >= qi * block_q + block_q - window)
    return full


def _when_causal_tiles(causal, qi, ki, block_q, block_k, body, window=None,
                       block=None):
    """Run ``body(masked: bool)`` per tile, splitting full from diagonal.

    Only diagonal-straddling tiles pay the mask's VPU cost (2 iotas +
    compare + 2 selects over block_q x block_k fp32) — on the old
    every-tile mask that elementwise work rivaled the matmuls themselves.
    Non-causal runs the unmasked body unconditionally; above-diagonal
    tiles run nothing (and their DMA is elided via the clamped index_map),
    nor do tiles wholly behind a ``window``.
    """
    if not causal:
        body(False)
        return
    needed = _tile_needed(qi, ki, block_q, block_k, True, window, block)
    full = _tile_full(qi, ki, block_q, block_k, window, block)
    pl.when(jnp.logical_and(needed, full))(lambda: body(False))
    pl.when(jnp.logical_and(needed, jnp.logical_not(full)))(lambda: body(True))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, acc_scr,
                *, scale, causal, block_q, block_k, d, window=None,
                block=None):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _():
        m_scr[:, :] = jnp.full_like(m_scr[:, :], _NEG_INF)
        acc_scr[:, :] = jnp.zeros_like(acc_scr[:, :])

    def body(masked: bool):
        # matmul operands stay in the INPUT dtype (bf16 on the training
        # path) with fp32 MXU accumulation — upcasting first would run the
        # MXU at its ~8x-slower fp32 rate.  The softmax scale rides on the
        # small [block_q, d] q tile, not the [block_q, block_k] scores —
        # the kernels are VPU-bound, so every full-scores elementwise pass
        # dropped is wall time (profiled: ~46% of the LM step is here).
        # The normalizer l ALSO rides in the accumulator: V is padded with
        # a ones column so p @ [v | 1 | 0...] yields output and row-sum in
        # one MXU pass — no l scratch, no rowsum reduce, no second
        # broadcast write (measured: fwd 2.79 -> 2.47 ms at T=8192).
        q = q_ref[0, 0] * jnp.asarray(scale, q_ref.dtype)
        kb = k_ref[0, 0]
        vb = v_ref[0, 0]
        s = _dot(q, kb, ((1,), (1,)))
        if masked:
            mask = _causal_tile_mask(qi, ki, block_q, block_k, window, block)
            s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        if masked:
            p = jnp.where(mask, p, 0.0)  # exp(0)=1 hazard on masked rows
        corr = jnp.exp(m_prev - m_new)
        m_scr[:, :] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        pad = acc_scr.shape[1] - d
        vcat = jnp.concatenate(
            [vb, jnp.ones((vb.shape[0], 1), vb.dtype),
             jnp.zeros((vb.shape[0], pad - 1), vb.dtype)], axis=1)
        acc_scr[:, :] = (acc_scr[:, :] * corr[:, None]
                         + _dot(p.astype(vb.dtype), vcat, ((1,), (0,))))

    _when_causal_tiles(causal, qi, ki, block_q, block_k, body, window, block)

    @pl.when(ki == nk - 1)
    def _():
        l_safe = jnp.maximum(acc_scr[:, d], 1e-30)
        o_ref[0, 0] = (acc_scr[:, :d] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0, 0] = jnp.broadcast_to(
            m_scr[:, 0] + jnp.log(l_safe), (8, block_q))


@jax.named_scope("flash_fwd")  # names the custom call in a device trace
def _fwd_call(q, k, v, *, causal, block_q, block_k, interpret, window=None,
              block=None):
    """q/k/v: [B, H, T, D] -> (out [B,H,T,D], lse [B,H,nq,8,block_q]);
    ``v`` may be narrower than ``q`` and ``k`` (latent attention's values):
    the output then has v's width.
    ``window``: the causal band ``i - window < j <= i``; tiles wholly behind
    it are skipped like tiles above the diagonal, compute and DMA alike.
    ``block``: block-causal, ``j < (i // block + 1) * block`` (bidirectional
    inside a block of ``block`` positions aligned from 0); tiles wholly
    above the block diagonal are skipped the same way.

    lse rows are broadcast across the 8 sublanes: Mosaic rejects output
    blocks thinner than an (8, 128) tile, so the per-row vector rides in a
    padded tile (row 0 is authoritative; all rows are equal).
    """
    b, h, t, d = q.shape
    dv = v.shape[3]
    scale = d ** -0.5
    nq, nk = t // block_q, t // block_k
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, d=dv,
                               window=window, block=block)
    # accumulator width: dv data columns + a lane-aligned block whose first
    # column carries the softmax normalizer (see kernel comment)
    acc_cols = dv + (128 - dv % 128 if dv % 128 else 128)

    def kv_map(bi, hi, qi, ki):
        if causal:  # masked tiles re-reference the diagonal tile: DMA elided
            ki = jnp.minimum(ki, _last_needed_k(qi, block_q, block_k, block))
        if window is not None:  # and the window's first tile, from below
            ki = jnp.maximum(ki, _first_needed_k(qi, block_q, block_k, window))
        return (bi, hi, ki, 0)

    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
            pl.BlockSpec((1, 1, block_k, dv), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, dv),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, 1, 8, block_q),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, nq, 8, block_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),      # running max
            # output accumulator + normalizer column (col d)
            pltpu.VMEM((block_q, acc_cols), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, scale, causal, block_q, block_k):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _():
        dq_scr[:, :] = jnp.zeros_like(dq_scr[:, :])

    def body(masked: bool):
        # scale rides on the small q tile (for s) and the final dq write —
        # never on [block_q, block_k] tensors (VPU-bound kernel)
        q = q_ref[0, 0] * jnp.asarray(scale, q_ref.dtype)
        kb = k_ref[0, 0]
        vb = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0, 0, 0, :]
        delta = delta_ref[0, 0, 0, 0, :]
        s = _dot(q, kb, ((1,), (1,)))
        p = jnp.exp(s - lse[:, None])
        if masked:
            p = jnp.where(_causal_tile_mask(qi, ki, block_q, block_k), p, 0.0)
        dp = _dot(do, vb, ((1,), (1,)))
        ds = (p * (dp - delta[:, None])).astype(kb.dtype)  # scale deferred
        dq_scr[:, :] = dq_scr[:, :] + _dot(ds, kb, ((1,), (0,)))

    _when_causal_tiles(causal, qi, ki, block_q, block_k, body)

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0, 0] = (dq_scr[:, :] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                    block_q, block_k):
    ki, qi = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _():
        dk_scr[:, :] = jnp.zeros_like(dk_scr[:, :])
        dv_scr[:, :] = jnp.zeros_like(dv_scr[:, :])

    def body(masked: bool):
        # the SCALED q tile serves both s and the dk accumulation:
        # dk = scale * sum(ds_unscaled^T @ q) == sum(ds_unscaled^T @ (q*scale)),
        # so no full-scores scale pass and no corrective write either
        qt = q_ref[0, 0] * jnp.asarray(scale, q_ref.dtype)
        kb = k_ref[0, 0]
        vb = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0, 0, 0, :]
        delta = delta_ref[0, 0, 0, 0, :]
        s = _dot(qt, kb, ((1,), (1,)))
        p = jnp.exp(s - lse[:, None])
        if masked:
            p = jnp.where(_causal_tile_mask(qi, ki, block_q, block_k), p, 0.0)
        dv_scr[:, :] = dv_scr[:, :] + _dot(p.astype(do.dtype), do, ((0,), (0,)))
        dp = _dot(do, vb, ((1,), (1,)))
        ds = (p * (dp - delta[:, None])).astype(qt.dtype)
        dk_scr[:, :] = dk_scr[:, :] + _dot(ds, qt, ((0,), (0,)))

    _when_causal_tiles(causal, qi, ki, block_q, block_k, body)

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0, 0] = dk_scr[:, :].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:, :].astype(dv_ref.dtype)


def _bwd_call(q, k, v, out, lse, g, *, causal, block_q, block_k, interpret):
    b, h, t, d = q.shape
    scale = d ** -0.5
    nq, nk = t // block_q, t // block_k
    # delta = rowsum(dO * O), padded into the same (8, block_q) tile layout
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(
        delta.reshape(b, h, nq, 1, block_q), (b, h, nq, 8, block_q))

    def kv_map(bi, hi, qi, ki):
        if causal:  # masked tiles re-reference the diagonal tile: DMA elided
            ki = jnp.minimum(ki, _last_needed_k(qi, block_q, block_k))
        return (bi, hi, ki, 0)

    q_tile = pl.BlockSpec((1, 1, block_q, d),
                          lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    k_tile = pl.BlockSpec((1, 1, block_k, d), kv_map)
    row_q = pl.BlockSpec((1, 1, 1, 8, block_q),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0, 0))
    dq_call = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(b, h, nq, nk),
        in_specs=[q_tile, k_tile, k_tile, q_tile, row_q, row_q],
        out_specs=q_tile,
        out_shape=jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )
    with jax.named_scope("flash_bwd_dq"):
        dq = dq_call(q, k, v, g, lse, delta)

    # grid transposed: k-tile outer, q-tile inner (the accumulated axis).
    # Causal clamp runs the OTHER way here: q-tiles before the diagonal
    # re-reference the first visible one.
    def q_map(bi, hi, ki, qi):
        if causal:
            qi = jnp.maximum(qi, _first_needed_q(ki, block_q, block_k))
        return (bi, hi, qi, 0)

    def row_map(bi, hi, ki, qi):
        if causal:
            qi = jnp.maximum(qi, _first_needed_q(ki, block_q, block_k))
        return (bi, hi, qi, 0, 0)

    q_tile2 = pl.BlockSpec((1, 1, block_q, d), q_map)
    k_tile2 = pl.BlockSpec((1, 1, block_k, d),
                           lambda bi, hi, ki, qi: (bi, hi, ki, 0))
    row_q2 = pl.BlockSpec((1, 1, 1, 8, block_q), row_map)
    dkv_call = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(b, h, nk, nq),
        in_specs=[q_tile2, k_tile2, k_tile2, q_tile2, row_q2, row_q2],
        out_specs=[k_tile2, k_tile2],
        out_shape=[jax.ShapeDtypeStruct((b, h, t, d), k.dtype),
                   jax.ShapeDtypeStruct((b, h, t, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
    )
    with jax.named_scope("flash_bwd_dkv"):
        dk, dv = dkv_call(q, k, v, g, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_k, interpret):
    out, _ = _fwd_call(q, k, v, causal=causal, block_q=block_q,
                       block_k=block_k, interpret=interpret)
    return out


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _fwd_call(q, k, v, causal=causal, block_q=block_q,
                         block_k=block_k, interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    return _bwd_call(q, k, v, out, lse, g, causal=causal, block_q=block_q,
                     block_k=block_k, interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _fit_block(block: int, t: int) -> int:
    """Largest power-of-two-shrunk block <= ``block`` that divides ``t``.

    Keeps big-block defaults from dropping support for lengths like 1536
    (divisible by 512, not 1024) — the block halves until it fits, floored
    at the 128-lane tile."""
    b = min(block, t)
    while b > 128 and t % b:
        b //= 2
    return b


def flash_attention_supported(t: int, d: int, block_q: int = 512,
                              block_k: int = 1024) -> bool:
    """Shape gate: T divides by both (fitted) blocks, lane-friendly head
    dim, and a full-tile block_q for the lse/delta transport tiles.

    Callers (``MultiHeadAttention``) fall back to the XLA blockwise path
    when this is False — tiny test shapes, ragged sequence lengths.
    """
    block_q, block_k = _fit_block(block_q, t), _fit_block(block_k, t)
    return (t % block_q == 0 and t % block_k == 0 and d % 64 == 0
            and block_q % 128 == 0)


def flash_attention(q, k, v, causal: bool = False, block_q: int = 512,
                    block_k: int = 1024, interpret: bool | None = None,
                    window: int | None = None, block: int | None = None):
    """Flash attention over ``[B, T, H, D]`` (the stack's layout).
    ``window`` (causal only, forward only: no gradient is defined through
    it): query ``i`` sees keys ``i - window < j <= i``.  ``block`` (the
    same terms): block-causal, query ``i`` sees keys ``j < (i // block +
    1) * block`` — every key of its own block of ``block`` positions
    (aligned from position 0) and of every earlier block.  ``v`` narrower
    than ``q`` and ``k`` (the same terms: forward only) gives a context of
    v's width, at the FLOPs of the widths as they are.

    ``interpret=None`` auto-selects: compiled on TPU, interpreter elsewhere
    (so the same code path is unit-testable on the CPU mesh).  In
    interpreter mode the Mosaic tiling rules don't apply, so any
    divisible ``block_q`` works there; compiled requires the
    ``flash_attention_supported`` gate.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    t = q.shape[1]
    if window is not None:
        # a key tile no wider than the band: a query tile then meets two
        block_k = min(block_k, max(128, 1 << (window - 1).bit_length()))
    block_q, block_k = _fit_block(block_q, t), _fit_block(block_k, t)
    ok = (t % block_q == 0 and t % block_k == 0
          and (interpret or flash_attention_supported(
              t, q.shape[3], block_q, block_k)))
    if not ok:
        raise ValueError(
            f"flash_attention: unsupported shape T={q.shape[1]} D={q.shape[3]}"
            f" for blocks ({block_q},{block_k}); gate with"
            " flash_attention_supported()"
        )
    # [B,T,H,D] -> [B,H,T,D] for head-major tiling
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    if window is None and block is None and v.shape[3] == q.shape[3]:
        out = _flash(qt, kt, vt, causal, block_q, block_k, interpret)
    else:  # a band, a block mask or narrower values: forward only
        if not causal or (window is not None and window < 1) or (
                block is not None and (block < 1 or window is not None)):
            raise ValueError(f"flash_attention: window={window} / block="
                             f"{block} / narrower values need causal "
                             f"attention, at least the query itself, and "
                             f"not both masks")
        out, _ = _fwd_call(qt, kt, vt, causal=True, block_q=block_q,
                           block_k=block_k, interpret=interpret, window=window,
                           block=block)
    return out.transpose(0, 2, 1, 3)
