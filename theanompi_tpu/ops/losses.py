"""Losses and error metrics.

Reference (unverified — SURVEY.md §2.1): the ``Softmax`` layer in
``theanompi/models/layers2.py`` fused log-softmax + NLL and reported
categorical error; top-1/top-5 error tracked AlexNet-paper metrics.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean cross entropy; ``labels`` are int class ids ``[B]`` (or ``[B,T]``).

    Computed in fp32 regardless of logits dtype — softmax in bf16 loses the
    small-probability tail and destabilizes late training.
    """
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def sigmoid_binary_cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean BCE on raw logits (DCGAN discriminator/generator losses)."""
    logits = logits.astype(jnp.float32)
    targets = targets.astype(jnp.float32)
    return jnp.mean(
        jnp.maximum(logits, 0.0) - logits * targets + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )


# ---------------------------------------------------------------------------
# fused LM head + cross entropy (chunked — never materializes [N, V] fp32)
# ---------------------------------------------------------------------------
#
# The LM hot path's last un-TPU-native op: ``Dense head -> fp32 softmax CE``
# materializes [B, T, V] logits in fp32 — at T=2048, B=16, V=32768 that is
# 4 GB of HBM traffic per direction, which dwarfs the attention the Pallas
# kernels just optimized.  This path fuses the head matmul into the loss and
# streams the logits in token chunks: each chunk's [C, V] fp32 scores live
# only transiently inside one scan step (tens of MB at V=32k — HBM-cheap and
# never part of the residual set), the per-token logsumexp ([N] fp32) is the
# ONLY O(N) residual, and the backward recomputes chunk scores from
# (h, w, lse) — the same rematerialization trade flash attention makes for
# the [T, T] score matrix.  Top-1/top-5 error ride in the same forward pass
# so metrics don't re-run the head.  Token counts that don't divide the
# chunk are zero-padded and masked, so any chunk size serves any N.


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _lm_xent(h3, w, b, y2, mask2, cfg, axis):
    loss, e1, e5, _ = _lm_xent_scan(h3, w, b, y2, mask2, cfg, axis)
    return loss, e1, e5


def _chunk_scores(hc, w, b):
    """One chunk's fp32 scores [C, V]: bf16 MXU matmul, fp32 accumulate."""
    s = lax.dot_general(hc, w.astype(hc.dtype), (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
    return s + b.astype(jnp.float32)


def _chunk_stats(hc, yc, w, b, v, axis):
    """-> (lse, gold, rank) for one chunk.

    ``axis=None``: ``w``/``b`` hold the FULL vocab.  ``axis`` set
    (Megatron parallel CE): they hold this shard's vocab slice and three
    small collectives assemble the softmax — pmax for the row max, one
    psum for (normalizer, gold logit), one for the tie-aware rank count.
    One implementation serves both so the sharded and unsharded training
    paths cannot diverge.
    """
    s = _chunk_scores(hc, w, b)
    if axis is None:
        m = jnp.max(s, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(s - m[:, None]), axis=-1))
        gold = jnp.take_along_axis(s, yc[:, None], axis=-1)[:, 0]
        # >= rank: ties score against the model (same rule as top_k_error)
        rank = jnp.sum(s >= gold[:, None], axis=-1) - 1
        return lse, gold, rank
    m = lax.pmax(jnp.max(s, axis=-1), axis)
    e = jnp.exp(s - m[:, None])
    y_loc = yc - lax.axis_index(axis) * v
    in_range = (y_loc >= 0) & (y_loc < v)
    idx = jnp.clip(y_loc, 0, v - 1)
    gold_loc = jnp.take_along_axis(s, idx[:, None], axis=-1)[:, 0]
    gold_loc = jnp.where(in_range, gold_loc, 0.0)
    l, gold = lax.psum(jnp.stack([jnp.sum(e, axis=-1), gold_loc]), axis)
    lse = m + jnp.log(l)
    rank = lax.psum(jnp.sum(s >= gold[:, None], axis=-1), axis) - 1
    return lse, gold, rank


@jax.named_scope("loss")
def _lm_xent_scan(h3, w, b, y2, mask2, cfg, axis):
    n, v, unroll = cfg

    def body(carry, xs):
        hc, yc, mc = xs
        lse, gold, rank = _chunk_stats(hc, yc, w, b, v, axis)
        mf = mc.astype(jnp.float32)
        ls, c1, c5 = carry
        return (
            ls + jnp.sum((lse - gold) * mf),
            c1 + jnp.sum((rank >= 1).astype(jnp.float32) * mf),
            c5 + jnp.sum((rank >= 5).astype(jnp.float32) * mf),
        ), lse

    zero = jnp.zeros((), jnp.float32)
    (ls, c1, c5), lse2 = lax.scan(body, (zero, zero, zero), (h3, y2, mask2),
                                  unroll=unroll)
    return ls / n, c1 / n, c5 / n, lse2


def _lm_xent_fwd(h3, w, b, y2, mask2, cfg, axis):
    loss, e1, e5, lse2 = _lm_xent_scan(h3, w, b, y2, mask2, cfg, axis)
    return (loss, e1, e5), (h3, w, b, y2, mask2, lse2)


@jax.named_scope("loss")
def _lm_xent_bwd(cfg, axis, res, cts):
    h3, w, b, y2, mask2, lse2 = res
    n, v, unroll = cfg
    g = cts[0] / n  # error cotangents drop: step functions, zero-grad a.e.
    ids = jnp.arange(v, dtype=y2.dtype)
    # vocab-sharded: labels offset to local ids (out-of-range matches none)
    lo = 0 if axis is None else lax.axis_index(axis) * v

    def body(carry, xs):
        hc, yc, mc, lsec = xs
        s = _chunk_scores(hc, w, b)
        p = jnp.exp(s - lsec[:, None])
        dl = (p - ((yc - lo)[:, None] == ids[None, :])) * (g * mc[:, None])
        dlc = dl.astype(hc.dtype)  # bf16 for the MXU, like the naive path
        dh = lax.dot_general(dlc, w.astype(dlc.dtype),
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        dw_acc, db_acc = carry
        dw_acc = dw_acc + lax.dot_general(
            hc, dlc, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        db_acc = db_acc + jnp.sum(dl, axis=0)
        return (dw_acc, db_acc), dh

    dw0 = jnp.zeros(w.shape, jnp.float32)
    db0 = jnp.zeros(b.shape, jnp.float32)
    (dw, db), dh3 = lax.scan(body, (dw0, db0), (h3, y2, mask2, lse2),
                             unroll=unroll)
    if axis is not None:
        # h is replicated over the vocab axis; each shard's dh is the
        # partial from its slice (the Megatron-f pin, explicit here)
        dh3 = lax.psum(dh3, axis)
    f0 = jax.dtypes.float0
    return (dh3.astype(h3.dtype), dw.astype(w.dtype), db.astype(b.dtype),
            np.zeros(y2.shape, f0), np.zeros(mask2.shape, f0))


_lm_xent.defvjp(_lm_xent_fwd, _lm_xent_bwd)


def _chunk_and_pad(h, labels, v: int, chunk_tokens: int | None):
    """Shared fused-loss prologue: flatten, pick the chunk, zero-pad, mask.

    -> (h3 [nc, C, D], y2 [nc, C], mask2 [nc, C], n).  One definition so
    the sharded and unsharded paths can never diverge on chunking.
    """
    d = h.shape[-1]
    h2 = h.reshape(-1, d)
    y1 = labels.reshape(-1)
    n = h2.shape[0]
    if chunk_tokens is None:
        chunk_tokens = max(256, min(2048, (256 << 20) // max(4 * v, 1)))
    c = max(8, min(n, chunk_tokens))
    nc = -(-n // c)
    pad = nc * c - n
    if pad:
        h2 = jnp.concatenate([h2, jnp.zeros((pad, d), h2.dtype)])
        y1 = jnp.concatenate([y1, jnp.zeros((pad,), y1.dtype)])
    mask = jnp.arange(nc * c) < n
    return (h2.reshape(nc, c, d), y1.reshape(nc, c),
            mask.reshape(nc, c), n)


def fused_lm_xent(h: jax.Array, w: jax.Array, b: jax.Array | None,
                  labels: jax.Array, chunk_tokens: int | None = None,
                  unroll: int = 1):
    """Fused LM-head softmax cross entropy -> ``(loss, top1_err, top5_err)``.

    ``h``: trunk output ``[..., D]``; ``w``: head weight ``[D, V]``; ``b``:
    head bias ``[V]`` or None; ``labels``: int ids matching ``h``'s leading
    dims.  Logits are computed in fp32-accumulated token chunks and never
    stored; backward recomputes them from the saved per-token logsumexp.
    The default chunk is 2048 tokens, shrinking once V pushes the
    transient fp32 scores past ~256 MB (chip-swept at V=32k: 256-token
    chunks starve the MXU at 88 ms where 1024-4096 all sit near 60 ms —
    within ~4% of the naive [N, V]-materializing path's speed while
    keeping O(N) memory).  N is zero-padded to the chunk and masked, so
    no divisibility is required of the caller.  ``unroll`` feeds the
    chunk scans (fwd + custom bwd) — a V=32k profile attributed ~27 %
    of the LM step to ``while`` self-time (carry/slice overhead and
    inter-iteration stalls; measured in round r4 under jax 0.4.3x, not
    re-measured), which unrolling lets XLA software-pipeline away at the
    cost of code size.
    """
    v = w.shape[-1]
    h3, y2, mask2, n = _chunk_and_pad(h, labels, v, chunk_tokens)
    if b is None:
        b = jnp.zeros((v,), jnp.float32)
    return _lm_xent(h3, w, b, y2, mask2, (n, v, unroll), None)


def fused_lm_xent_vp(h: jax.Array, w_local: jax.Array,
                     b_local: jax.Array | None, labels: jax.Array,
                     axis_name: str, chunk_tokens: int | None = None,
                     unroll: int = 1):
    """Vocab-parallel fused LM loss -> ``(loss, top1_err, top5_err)``.

    Megatron parallel cross entropy: ``w_local``/``b_local`` are this
    shard's vocab slice (``P(None, model)`` / ``P(model)``); ``h`` and
    ``labels`` are replicated over ``axis_name``.  Semantics match
    :func:`fused_lm_xent` on the gathered head exactly (same chunking,
    masking, and tie-rank rules — it IS the same implementation with the
    per-chunk softmax assembled by collectives); no rank ever
    materializes more than ``[chunk, V/tp]`` scores.
    """
    v_local = w_local.shape[-1]
    h3, y2, mask2, n = _chunk_and_pad(h, labels, v_local, chunk_tokens)
    if b_local is None:
        b_local = jnp.zeros((v_local,), jnp.float32)
    return _lm_xent(h3, w_local, b_local, y2, mask2, (n, v_local, unroll),
                    axis_name)


def top_k_error(logits: jax.Array, labels: jax.Array, k: int = 1) -> jax.Array:
    """Fraction of examples whose label is NOT in the top-k predictions."""
    gold = jnp.take_along_axis(
        logits, labels[..., None], axis=-1
    )
    # >= so ties score against the model: a collapsed constant-logit net must
    # not report 0% error (the label's own logit is excluded by the -1)
    rank = jnp.sum(logits >= gold, axis=-1) - 1
    return jnp.mean((rank >= k).astype(jnp.float32))
