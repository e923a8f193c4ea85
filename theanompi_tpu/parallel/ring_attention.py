"""Ring attention: sequence/context parallelism over the ``seq`` mesh axis.

Beyond the reference's capability set (a 2016 framework has no attention at
all — SURVEY.md §5 "long-context: absent"), but first-class here: long-context
training is part of this framework's scale contract, and the communication
shape is exactly the exchanger's ring (``theanompi_tpu.parallel.exchanger``)
applied to keys/values instead of gradients.

Mechanism (Liu et al. 2023, "Ring Attention with Blockwise Transformers"):
shard the sequence over the ``seq`` axis; each device keeps its Q block
resident and circulates KV blocks around the ICI ring with ``ppermute``,
accumulating attention with an online (flash-style) softmax, so the full
S×S score matrix never materializes and per-device memory is O(S/n · d).
Causal masking uses global block offsets: whole KV-future blocks are skipped
numerically (their contribution is masked), intra-block masking applies on
the diagonal block.

All functions are pure and run inside ``shard_map``; XLA overlaps each
ppermute hop with the current block's compute where dependencies allow.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from theanompi_tpu.parallel.mesh import SEQ_AXIS

_NEG_INF = -1e30  # fp32-safe mask value (finite: avoids NaN from inf-inf)


def _block_attend(q, k, v, m_prev, l_prev, acc, mask=None):
    """One online-softmax accumulation step.

    q: [B, Tq, H, D]; k/v: [B, Tk, H, D]; running max ``m_prev`` [B, H, Tq],
    normalizer ``l_prev`` [B, H, Tq], accumulator ``acc`` [B, Tq, H, D].
    """
    scale = q.shape[-1] ** -0.5
    # scores: [B, H, Tq, Tk]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    # renormalize previous accumulation to the new max
    correction = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[..., None])  # [B, H, Tq, Tk]
    if mask is not None:
        # a fully-masked row while m is still at the -1e30 init would give
        # p = exp(s - m_new) = exp(0) = 1 per entry — bogus mass.  Zeroing
        # masked positions makes accumulation order-independent (no
        # "diagonal block first" invariant needed); XLA fuses the select.
        p = jnp.where(mask, p, 0.0)
    l_new = l_prev * correction + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    acc_new = acc * correction.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, acc_new


def blockwise_attention(q, k, v, causal: bool = False, block_size: int | None = None,
                        window: int | None = None, block: int | None = None):
    """Single-device flash-style attention (the ring's n=1 case / reference
    implementation for tests).  [B, T, H, D] layout.  ``window`` (with
    ``causal``): query ``i`` sees keys ``i - window < j <= i``, a band (the queries
    are not tiled here, so no key block lies behind all of them: the band
    is a mask and skips nothing; the flash kernel skips its tiles).
    ``block`` (with ``causal``): block-causal, query ``i`` sees keys ``j <
    (i // block + 1) * block``, bidirectional inside its block (a mask
    here too)."""
    if (window is not None or block is not None) and not causal:
        raise ValueError("a window or a block mask is causal")
    if window is not None and block is not None:
        raise ValueError("a window or a block mask, not both")
    b, t, h, d = q.shape
    if block_size is None or block_size >= k.shape[1]:
        blocks = [(0, k.shape[1])]
    else:
        blocks = [
            (i, min(i + block_size, k.shape[1]))
            for i in range(0, k.shape[1], block_size)
        ]
    qf = q.astype(jnp.float32)
    m = jnp.full((b, h, t), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, t), jnp.float32)
    acc = jnp.zeros((b, t, h, v.shape[3]), jnp.float32)  # v may be narrower
    q_pos = jnp.arange(t)
    for start, stop in blocks:
        kb = k[:, start:stop].astype(jnp.float32)
        vb = v[:, start:stop]
        mask = None
        if causal and block is not None:
            mask = (q_pos[:, None] // block
                    >= jnp.arange(start, stop)[None, :] // block)[None, None]
        elif causal:
            mask = q_pos[:, None] >= jnp.arange(start, stop)[None, :]
            if window is not None:
                mask &= (q_pos[:, None] - jnp.arange(start, stop)[None, :]
                         < window)
            mask = mask[None, None]
        m, l, acc = _block_attend(qf, kb, vb, m, l, acc, mask)
    out = acc / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _ring_mask(me, src, t):
    """Causal mask tile for local q rows vs the KV block that originated at
    ``src`` (global block offsets): [1, 1, t, t]."""
    q_pos = me * t + jnp.arange(t)
    k_pos = src * t + jnp.arange(t)
    return (q_pos[:, None] >= k_pos[None, :])[None, None]


def _ring_forward(q, k, v, causal, axis_name):
    """The KV-circulating forward; -> (out [B,T,H,D], lse [B,H,T])."""
    n = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    b, t, h, d = q.shape

    qf = q.astype(jnp.float32)
    m = jnp.full((b, h, t), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, t), jnp.float32)
    acc = jnp.zeros((b, t, h, d), jnp.float32)
    ring = [(i, (i + 1) % n) for i in range(n)]

    kv = (k, v)
    for hop in range(n):
        # after `hop` forwards along the ring, we hold the block that
        # originated at (me - hop) mod n
        src = (me - hop) % n
        kb, vb = kv
        mask = _ring_mask(me, src, t) if causal else None
        m, l, acc = _block_attend(
            qf, kb.astype(jnp.float32), vb, m, l, acc, mask
        )
        if hop < n - 1:
            kv = jax.tree.map(lambda x: lax.ppermute(x, axis_name, ring), kv)

    # fully-masked rows (can't happen with causal self-attention since the
    # diagonal is always visible, but guard the division anyway)
    l_safe = jnp.maximum(l, 1e-30)
    out = acc / l_safe.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype), m + jnp.log(l_safe)


def ring_attention(q, k, v, causal: bool = False, axis_name: str = SEQ_AXIS):
    """Sequence-parallel attention inside ``shard_map`` over ``axis_name``.

    q/k/v: the LOCAL sequence shard, [B, T_local, H, D].  Equivalent to full
    attention over the gathered sequence (see tests), with KV circulating the
    ring instead of being gathered.

    The backward is a custom second ring pass (Liu et al. 2023 §3): plain
    autodiff of the forward would save every hop's [T_local, T_local]
    probability block — O(T²/n) per device, the exact thing ring attention
    exists to avoid.  Instead the VJP recomputes probabilities per hop from
    the saved (q, k, v, out, lse) and circulates a (k, v, dk, dv) bundle a
    full lap, so each shard's dk/dv accumulate contributions from every
    query shard and arrive back home; residual memory stays O(T·d/n).
    """
    n = lax.axis_size(axis_name)
    if n == 1:
        return blockwise_attention(q, k, v, causal=causal)
    return _ring_flash(q, k, v, causal, axis_name)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ring_flash(q, k, v, causal, axis_name):
    out, _ = _ring_forward(q, k, v, causal, axis_name)
    return out


def _ring_flash_fwd(q, k, v, causal, axis_name):
    out, lse = _ring_forward(q, k, v, causal, axis_name)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd(causal, axis_name, res, g):
    q, k, v, out, lse = res
    n = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    b, t, h, d = q.shape
    scale = d ** -0.5
    ring = [(i, (i + 1) % n) for i in range(n)]

    qf = q.astype(jnp.float32)
    do = g.astype(jnp.float32)
    # delta_i = sum_d dO_i * O_i : [B, H, T] (lse's layout)
    delta = jnp.sum(do * out.astype(jnp.float32), axis=-1).transpose(0, 2, 1)

    dq = jnp.zeros((b, t, h, d), jnp.float32)
    bundle = (k, v,
              jnp.zeros((b, t, h, d), jnp.float32),
              jnp.zeros((b, t, h, d), jnp.float32))
    for hop in range(n):
        src = (me - hop) % n
        kb, vb, dkb, dvb = bundle
        kbf, vbf = kb.astype(jnp.float32), vb.astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kbf,
                       preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse[..., None])
        if causal:
            p = jnp.where(_ring_mask(me, src, t), p, 0.0)
        dp = jnp.einsum("bqhd,bkhd->bhqk", do, vbf,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bhqk,bkhd->bqhd", ds, kbf)
        dkb = dkb + jnp.einsum("bhqk,bqhd->bkhd", ds, qf)
        dvb = dvb + jnp.einsum("bhqk,bqhd->bkhd", p, do)
        # permute after EVERY hop (n total): each KV block visits all query
        # shards and its accumulated dk/dv land back on its home shard
        bundle = jax.tree.map(
            lambda x: lax.ppermute(x, axis_name, ring), (kb, vb, dkb, dvb)
        )
    _, _, dk, dv = bundle
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)
