"""Strategy-pluggable parameter/gradient exchanger — the heart of the rebuild.

Reference (unverified — SURVEY.md §2.1): ``theanompi/lib/exchanger.py``
(``BSP_Exchanger.exchange()`` summing worker grads/params each iteration) and
``theanompi/lib/exchanger_strategy.py`` with config-string-selected collective
implementations:

====================  =============================================  =====================
reference strategy    what it did (GPU/MPI era)                      TPU-native analogue
====================  =============================================  =====================
``ar``                CUDA-aware ``MPI.Allreduce`` on gpuarray bufs  ``psum``
``nccl32``            pygpu/NCCL ``all_reduce`` fp32                 ``psum``
``asa16``/``nccl16``  fp16-compressed exchange                       ``psum_bf16``
``asa32``             alltoall-sum-allgather ring                    ``ring``
``copper``/``16``     host-staged copy path                          ``ring_bf16``
====================  =============================================  =====================

Every strategy here is a *pure function applied inside ``shard_map``* over the
``data`` mesh axis; XLA lowers ``psum``/``ppermute`` to ICI collectives, so
the "CUDA-aware" zero-copy property of the reference is automatic.  The
``ring*`` strategies are the explicit reduce-scatter/all-gather formulation
(the shape of the reference's ``asa`` strategies) built from ``ppermute`` —
mostly valuable as the template for custom collective schedules (and reused by
ring attention), since XLA's own ``psum`` lowering is already ring-based.

Bucketed exchange (ISSUE 2)
---------------------------

The leaf-wise strategies above issue ONE collective per parameter tensor —
dozens per step for ResNet-50/transformer_lm, each paying per-message launch
latency.  The ``*_bucket`` strategies (plus ``ring_int8`` and ``zero1``,
which are bucket-native) instead flatten the floating leaves and pack them
into a small number of fixed-size fused buckets (default ~4 MiB, leaves
grouped by dtype, greedy fill — an oversized leaf gets its own bucket) before
the collective and unpack after, so a 100+-leaf model compiles to a handful
of ``all-reduce`` HLO ops (lint-tested in ``tests/test_lint_collectives.py``).
With ``overlap=True`` (the ``exch_overlap`` rule key) the bucketed strategies
additionally chain the per-bucket collectives in reverse layout order so they
issue *during* backward instead of trailing it — mechanism, bit-equality
contract, and audit story in :mod:`theanompi_tpu.parallel.overlap`.

- ``psum_bucket``/``psum_bf16_bucket`` — fused-bucket analogues of
  ``psum``/``psum_bf16`` (multi-axis capable, like their leaf-wise twins).
- ``ring_bucket``/``ring_bf16_bucket`` — the explicit ppermute ring over
  fused buckets.
- ``ring_int8`` — int8-quantized ring (the modern analogue of the
  reference's compressed ``asa16`` path): each hop ships an int8 payload
  plus ONE fp32 per-chunk scale, with stochastic rounding so the
  quantization error is zero-mean.  Like the reference's fp16 strategies,
  accumulation error grows ~O(n) with worker count; the final all-gather
  circulates each owner's quantized bytes verbatim, so every replica
  dequantizes identical values (replicas cannot drift).
- ``zero1`` — ZeRO-1-style sharded update: each grad bucket is
  reduce-scattered (mean), the optimizer update runs on only the local 1/n
  shard of params + opt_state (see :func:`theanompi_tpu.ops.opt.sharded_update`),
  and updated params are all-gathered.  Optimizer-state HBM and update
  FLOPs drop by n; params stay replicated for eval/checkpoint.  Because
  the exchange and the update fuse, the trainer calls
  :meth:`Exchanger.exchange_and_update` instead of ``exchange`` (the
  ``fuses_update`` plug point).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from theanompi_tpu.parallel.mesh import DATA_AXIS
from theanompi_tpu.parallel.overlap import fence as _fence
from theanompi_tpu.parallel.overlap import overlap_pred as _overlap_pred

# strategy name -> fn(x, axis_name, axis_size) -> mean-reduced x (leaf-wise)
STRATEGIES: dict[str, Callable] = {}

#: bucketed strategies — fused flat buckets instead of one collective/leaf
BUCKETED_STRATEGIES = (
    "psum_bucket",
    "psum_bf16_bucket",
    "ring_bucket",
    "ring_bf16_bucket",
    "ring_int8",
    "zero1",
)

#: strategies that may reduce over multiple mesh axes at once (plain psum
#: accepts an axis tuple; the ring/scatter schedules assume ONE ring)
_MULTI_AXIS_OK = ("psum", "psum_bf16", "none", "psum_bucket", "psum_bf16_bucket")

#: strategies that put float leaves on the wire in bf16 (2 bytes/elem)
_BF16_WIRE = ("psum_bf16", "ring_bf16", "psum_bf16_bucket", "ring_bf16_bucket")
#: strategies that put float leaves on the wire in int8 (1 byte/elem;
#: per-chunk fp32 scales excluded from accounting — see Exchanger.wire_bytes)
_INT8_WIRE = ("ring_int8",)

DEFAULT_BUCKET_BYTES = 4 * 2**20

#: fold_in tag callers use to derive the exchange rng stream (ring_int8
#: stochastic rounding) from their per-step key — distinct from dropout's
#: micro-batch folds, which use small ints
EXCHANGE_RNG_TAG = 0x45584348  # "EXCH"


def wire_itemsize(strategy: str, dtype) -> int:
    """Bytes per element a leaf of ``dtype`` occupies on the ICI wire.

    The telemetry layer cannot observe the collective (it is fused into one
    XLA program), so bytes are accounted *statically* from the strategy's
    wire dtype: the bf16 strategies compress floating leaves to 2 bytes and
    ``ring_int8`` to 1; everything else (including ``zero1``'s
    reduce-scatter + all-gather) ships the leaf dtype verbatim; ``none``
    ships nothing.
    """
    if strategy == "none":
        return 0
    itemsize = jnp.dtype(dtype).itemsize
    if jnp.issubdtype(dtype, jnp.floating):
        if strategy in _BF16_WIRE:
            return min(itemsize, 2)
        if strategy in _INT8_WIRE:
            return min(itemsize, 1)
    return itemsize


def collective_wire_bytes(buffer_bytes: int, axis_size: int) -> int:
    """Per-device bytes on the wire for one all-reduce of ``buffer_bytes``.

    Ring all-reduce (reduce-scatter + all-gather — both the explicit
    ``ring*`` strategies and XLA's own ``psum`` lowering) moves
    ``2*(n-1)/n`` of the buffer through each device; n=1 moves nothing.
    """
    if axis_size <= 1:
        return 0
    return int(2 * (axis_size - 1) * buffer_bytes // axis_size)


def register_strategy(name: str):
    def deco(fn):
        STRATEGIES[name] = fn
        return fn

    return deco


@register_strategy("none")
def _no_exchange(x: jax.Array, axis_name: str, axis_size: int) -> jax.Array:
    """No-op strategy: skip the collective entirely.

    Replicas diverge — NOT for training.  Exists for a differential comm
    measurement (step time with vs. without the exchange is the honest
    comm-share proxy when the collective is fused into one XLA program and
    invisible to host-side timers).
    """
    return x


@register_strategy("psum")
def _psum_mean(x: jax.Array, axis_name: str, axis_size: int) -> jax.Array:
    """Plain all-reduce mean (reference ``ar``/``nccl32``)."""
    return lax.psum(x, axis_name) / axis_size


@register_strategy("psum_bf16")
def _psum_bf16_mean(x: jax.Array, axis_name: str, axis_size: int) -> jax.Array:
    """bf16-compressed all-reduce (reference ``asa16``/``nccl16``).

    Halves ICI bytes.  Note the accumulation itself is bf16 (XLA reduces in
    the wire dtype), so rounding error grows ~O(n) with worker count exactly
    as the reference's fp16 strategies' did; only the final mean division is
    fp32.  Use plain ``psum`` when numerics matter more than bandwidth.
    """
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return _psum_mean(x, axis_name, axis_size)
    summed = lax.psum(x.astype(jnp.bfloat16), axis_name)
    return (summed.astype(jnp.float32) / axis_size).astype(x.dtype)


def _ring_allreduce(x: jax.Array, axis_name: str, n: int, wire_dtype=None) -> jax.Array:
    """Explicit ring all-reduce: reduce-scatter then all-gather via ppermute.

    Equivalent communication shape to the reference's ``asa32``/``asa16``
    (alltoall-sum-allgather) strategies.  2*(n-1) ppermute steps, each moving
    1/n of the buffer around the ring.  Chunk selection uses
    ``lax.dynamic_index_in_dim`` (a 1/n slice), NOT ``jnp.take`` — take
    lowers to a gather over the whole chunk array per hop, touching n× the
    bytes each step actually needs.
    """
    if n == 1:
        return x
    orig_shape, orig_dtype = x.shape, x.dtype
    flat = x.reshape(-1)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    chunks = flat.reshape(n, -1)
    if wire_dtype is not None and jnp.issubdtype(orig_dtype, jnp.floating):
        chunks = chunks.astype(wire_dtype)
    idx = lax.axis_index(axis_name)
    ring = [(i, (i + 1) % n) for i in range(n)]

    # Reduce-scatter: after step s, device i holds the partial sum of chunk
    # (i - s - 1) mod n over s+2 contributors; after n-1 steps, device i owns
    # the complete chunk (i + 1) mod n.
    for s in range(n - 1):
        send = lax.dynamic_index_in_dim(chunks, (idx - s) % n, 0, keepdims=False)
        recv = lax.ppermute(send, axis_name, ring)
        tgt = (idx - s - 1) % n
        chunks = lax.dynamic_update_index_in_dim(
            chunks, lax.dynamic_index_in_dim(chunks, tgt, 0, keepdims=False) + recv,
            tgt, 0,
        )
    # All-gather: circulate the completed chunks.
    for s in range(n - 1):
        send = lax.dynamic_index_in_dim(chunks, (idx + 1 - s) % n, 0,
                                        keepdims=False)
        recv = lax.ppermute(send, axis_name, ring)
        chunks = lax.dynamic_update_index_in_dim(chunks, recv, (idx - s) % n, 0)

    out = chunks.astype(jnp.float32) if wire_dtype is not None else chunks
    out = out.reshape(-1)[: flat.size - pad if pad else flat.size]
    return out.reshape(orig_shape).astype(orig_dtype)


@register_strategy("ring")
def _ring_mean(x, axis_name, axis_size):
    return _ring_allreduce(x, axis_name, axis_size) / axis_size


@register_strategy("ring_bf16")
def _ring_bf16_mean(x, axis_name, axis_size):
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return _ring_mean(x, axis_name, axis_size)
    out = _ring_allreduce(x, axis_name, axis_size, wire_dtype=jnp.bfloat16)
    return (out.astype(jnp.float32) / axis_size).astype(x.dtype)


# -- int8 quantized ring (the modern ``asa16``) ------------------------------
# The per-chunk-scale + stochastic-rounding primitive moved to
# ``ops/quant.py`` (ISSUE 6) so the serving path's weight quantization can
# share the exact wire format without importing this training-side module.
from theanompi_tpu.ops.quant import quantize_chunk as _quantize_chunk  # noqa: E402


def _ring_allreduce_int8(x: jax.Array, axis_name: str, n: int,
                         key: jax.Array) -> jax.Array:
    """Ring all-reduce with int8 + per-chunk-scale wire format (fp32 math).

    Reduce-scatter: each hop quantizes the outgoing fp32 partial sum,
    ships (int8, scale), and the receiver dequantizes into its fp32
    accumulator.  All-gather: each completed chunk is quantized ONCE by
    its owner and the payload circulates verbatim, so every replica
    dequantizes bit-identical values — replicas cannot drift.  Returns
    fp32 (callers divide by n and cast back).
    """
    if n == 1:
        return x.astype(jnp.float32)
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    chunks = flat.reshape(n, -1)
    idx = lax.axis_index(axis_name)
    ring = [(i, (i + 1) % n) for i in range(n)]

    for s in range(n - 1):
        send = lax.dynamic_index_in_dim(chunks, (idx - s) % n, 0, keepdims=False)
        q, scale = _quantize_chunk(send, jax.random.fold_in(key, s))
        recv = (lax.ppermute(q, axis_name, ring).astype(jnp.float32)
                * lax.ppermute(scale, axis_name, ring))
        tgt = (idx - s - 1) % n
        chunks = lax.dynamic_update_index_in_dim(
            chunks, lax.dynamic_index_in_dim(chunks, tgt, 0, keepdims=False) + recv,
            tgt, 0,
        )
    own = lax.dynamic_index_in_dim(chunks, (idx + 1) % n, 0, keepdims=False)
    q_own, s_own = _quantize_chunk(own, jax.random.fold_in(key, n - 1))
    qc = lax.dynamic_update_index_in_dim(
        jnp.zeros(chunks.shape, jnp.int8), q_own, (idx + 1) % n, 0)
    sc = lax.dynamic_update_index_in_dim(
        jnp.zeros((n,), jnp.float32), s_own, (idx + 1) % n, 0)
    for s in range(n - 1):
        send_q = lax.dynamic_index_in_dim(qc, (idx + 1 - s) % n, 0,
                                          keepdims=False)
        send_s = lax.dynamic_index_in_dim(sc, (idx + 1 - s) % n, 0,
                                          keepdims=False)
        qc = lax.dynamic_update_index_in_dim(
            qc, lax.ppermute(send_q, axis_name, ring), (idx - s) % n, 0)
        sc = lax.dynamic_update_index_in_dim(
            sc, lax.ppermute(send_s, axis_name, ring), (idx - s) % n, 0)
    out = qc.astype(jnp.float32) * sc[:, None]
    out = out.reshape(-1)[: flat.size - pad if pad else flat.size]
    return out.reshape(x.shape)


# -- bucket layout -----------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _Bucket:
    """One fused flat buffer: which leaves it packs and where."""

    dtype: object
    indices: tuple[int, ...]   # flat-leaf indices packed, in order
    sizes: tuple[int, ...]     # element count per packed leaf
    shapes: tuple[tuple, ...]  # original shape per packed leaf
    elems: int                 # payload elements (sum of sizes)
    padded: int                # elems rounded up to a multiple of n


def _leaf_meta(leaf):
    """(shape, dtype) for arrays, ShapeDtypeStructs, and bare scalars."""
    if hasattr(leaf, "dtype"):
        return tuple(getattr(leaf, "shape", ())), jnp.dtype(leaf.dtype)
    arr = jnp.asarray(leaf)
    return tuple(arr.shape), jnp.dtype(arr.dtype)


def _bucket_layout(leaves, bucket_bytes: int, n: int) -> list[_Bucket]:
    """Greedy dtype-grouped fused buckets over the inexact leaves.

    Deterministic in the leaf order, so the layout computed at trace time
    (inside ``shard_map``) and host-side (opt-state init, wire accounting)
    always agrees.  Leaves are never split: one larger than ``bucket_bytes``
    simply gets its own (oversized) bucket.  Each bucket is padded to a
    multiple of ``n`` so ring chunking and reduce-scatter divide evenly.
    """
    groups: dict = {}
    for i, leaf in enumerate(leaves):
        shape, dtype = _leaf_meta(leaf)
        if not jnp.issubdtype(dtype, jnp.inexact):
            continue
        groups.setdefault(dtype, []).append((i, shape, math.prod(shape)))
    buckets: list[_Bucket] = []
    for dtype, entries in groups.items():
        cap = max(1, int(bucket_bytes) // max(1, jnp.dtype(dtype).itemsize))
        cur: list = []
        cur_elems = 0
        for i, shape, size in entries:
            if cur and cur_elems + size > cap:
                buckets.append(_make_bucket(dtype, cur, cur_elems, n))
                cur, cur_elems = [], 0
            cur.append((i, shape, size))
            cur_elems += size
        if cur:
            buckets.append(_make_bucket(dtype, cur, cur_elems, n))
    return buckets


def _make_bucket(dtype, entries, elems, n) -> _Bucket:
    return _Bucket(
        dtype=dtype,
        indices=tuple(e[0] for e in entries),
        shapes=tuple(e[1] for e in entries),
        sizes=tuple(e[2] for e in entries),
        elems=elems,
        padded=elems + (-elems) % max(1, n),
    )


def _pack(leaves, bucket: _Bucket) -> jax.Array:
    parts = [jnp.asarray(leaves[i]).reshape(-1) for i in bucket.indices]
    if bucket.padded > bucket.elems:
        parts.append(jnp.zeros((bucket.padded - bucket.elems,), bucket.dtype))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def _unpack(buf: jax.Array, bucket: _Bucket) -> dict:
    """-> {flat-leaf index: reduced array} for the leaves ``bucket`` packs."""
    out, off = {}, 0
    for i, size, shape in zip(bucket.indices, bucket.sizes, bucket.shapes):
        out[i] = lax.slice(buf, (off,), (off + size,)).reshape(shape)
        off += size
    return out


def fused_pmean(tree, axis_name):
    """Mean-reduce every inexact leaf with ONE collective per dtype.

    The fused analogue of mapping ``lax.pmean`` leaf-by-leaf (trainer
    metrics / BN-state consensus): the same pack/unpack machinery as the
    bucketed exchange, with an unbounded bucket per dtype — a 16-leaf
    state tree costs one all-reduce instead of 16.  Non-float leaves
    (step counters) pass through unchanged.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out = list(leaves)
    # one bucket per dtype, no padding (n=1), no size cap
    for bucket in _bucket_layout(leaves, bucket_bytes=2**62, n=1):
        red = lax.pmean(_pack(leaves, bucket), axis_name)
        for i, arr in _unpack(red, bucket).items():
            out[i] = arr
    return jax.tree_util.tree_unflatten(treedef, out)


class Exchanger:
    """Averages a gradient/parameter pytree across the ``data`` axis.

    Reference: ``BSP_Exchanger`` (SURVEY.md §2.1) — there, a post-step host
    call dispatching to MPI/NCCL; here, a pure pytree transform invoked
    *inside* the compiled train step, so XLA overlaps the collective with
    remaining compute where the dependence structure allows.

    ``strategy`` is the plug point, preserved from the reference's
    config-string mechanism: one of ``STRATEGIES`` keys (leaf-wise) or
    ``BUCKETED_STRATEGIES`` (fused flat buckets — see module docstring).
    ``bucket_bytes`` caps the fused-bucket payload (default 4 MiB).  The
    axis size is derived *inside* the mapped context (``lax.axis_size``),
    so it can never disagree with the actual mesh.

    ``zero1`` fuses the exchange into the optimizer update
    (``fuses_update``): the trainer calls :meth:`exchange_and_update`
    and stores the optimizer state in this exchanger's sharded bucket
    layout (:meth:`zero1_init_opt_state` / :meth:`zero1_opt_state_specs`).
    """

    def __init__(self, strategy: str = "psum",
                 axis_name: str | tuple[str, ...] = DATA_AXIS,
                 bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                 overlap: bool = False):
        known = set(STRATEGIES) | set(BUCKETED_STRATEGIES)
        if strategy not in known:
            raise ValueError(
                f"unknown exchange strategy {strategy!r}; "
                f"available: {sorted(known)}"
            )
        if isinstance(axis_name, (tuple, list)) and len(axis_name) > 1:
            if strategy not in _MULTI_AXIS_OK:
                raise ValueError(
                    f"strategy {strategy!r} reduces over a single ring; "
                    f"multi-axis exchange ({axis_name}) needs one of "
                    f"{sorted(_MULTI_AXIS_OK)}"
                )
            axis_name = tuple(axis_name)
        elif isinstance(axis_name, (tuple, list)):
            axis_name = axis_name[0]
        if int(bucket_bytes) < 1:
            raise ValueError(f"bucket_bytes must be positive, got {bucket_bytes}")
        if overlap and strategy not in BUCKETED_STRATEGIES:
            raise ValueError(
                f"exch_overlap chains per-bucket collectives; strategy "
                f"{strategy!r} is not bucketed (one of {BUCKETED_STRATEGIES})"
            )
        self.strategy = strategy
        self.axis_name = axis_name
        self.bucket_bytes = int(bucket_bytes)
        self.overlap = bool(overlap)
        self._fn = STRATEGIES.get(strategy)

    # -- properties ----------------------------------------------------------
    @property
    def bucketed(self) -> bool:
        return self.strategy in BUCKETED_STRATEGIES

    @property
    def fuses_update(self) -> bool:
        """True when the strategy fuses exchange + optimizer update (zero1):
        the trainer must call :meth:`exchange_and_update`, not ``exchange``."""
        return self.strategy == "zero1"

    # -- mapped-context helpers ----------------------------------------------
    def _axes(self) -> tuple:
        return (self.axis_name if isinstance(self.axis_name, tuple)
                else (self.axis_name,))

    def _mapped_axis_size(self) -> int:
        try:
            n = 1
            for a in self._axes():
                n *= lax.axis_size(a)
            return n
        except NameError as e:
            raise ValueError(
                f"Exchanger.exchange must run inside shard_map over a mesh "
                f"binding axes {self._axes()!r}"
            ) from e

    def _chain_pred(self, step):
        """The fence predicate for the overlap chain, from the traced step
        scalar — see :mod:`theanompi_tpu.parallel.overlap`."""
        if step is None:
            raise ValueError(
                "exch_overlap needs the traced int32 step scalar to anchor "
                "the fence chain; pass step= to exchange()/exchange_and_update()"
            )
        return _overlap_pred(step)

    # -- exchange ------------------------------------------------------------
    def exchange(self, tree, rng=None, step=None):
        """Mean-reduce every floating leaf across the exchange axes.

        Call inside ``shard_map`` over a mesh that binds ``axis_name``
        (a single axis, or a tuple — e.g. ``("data", "seq")`` when gradients
        carry per-sequence-shard partial contributions too).
        Non-float leaves (step counters and other bookkeeping that may ride
        along in an optimizer-state pytree) pass through unchanged —
        mean-reducing them would silently promote ints to floats.

        ``rng`` seeds ``ring_int8``'s stochastic rounding (ignored by every
        other strategy); pass a fresh per-step key so the rounding noise
        decorrelates across steps — ``None`` falls back to a fixed key.

        ``step`` (the traced int32 step scalar) is required when
        ``overlap`` is on: buckets are walked in reverse layout order and
        each bucket's buffer is fenced on the previous bucket's reduction
        (see :mod:`theanompi_tpu.parallel.overlap`), so collectives issue
        during backward instead of trailing it.  The per-bucket rng fold
        uses the bucket *index*, not the walk order, so ``ring_int8``'s
        rounding noise — and therefore the result — is identical to the
        fused walk.
        """
        if self.fuses_update:
            raise ValueError(
                "zero1 fuses the exchange into the optimizer update; "
                "call exchange_and_update(grads, opt_state, params, lr, opt)"
            )
        n = self._mapped_axis_size()
        if n == 1:
            return tree

        if not self.bucketed:
            def reduce_leaf(x):
                if not jnp.issubdtype(jnp.asarray(x).dtype, jnp.inexact):
                    return x
                return self._fn(x, axis_name=self.axis_name, axis_size=n)

            return jax.tree.map(reduce_leaf, tree)

        leaves, treedef = jax.tree_util.tree_flatten(tree)
        out = list(leaves)
        buckets = _bucket_layout(leaves, self.bucket_bytes, n)
        order = range(len(buckets))
        pred, prev = None, None
        if self.overlap:
            pred = self._chain_pred(step)
            order = reversed(order)
        for bi in order:
            bucket = buckets[bi]
            key = None
            if self.strategy == "ring_int8":
                base = rng if rng is not None else jax.random.PRNGKey(0)
                key = jax.random.fold_in(base, bi)
            buf = _pack(leaves, bucket)
            if prev is not None:
                buf = _fence(buf, prev, pred)
            red = self._reduce_bucket(buf, n, key)
            if self.overlap:
                prev = red
            for i, arr in _unpack(red, bucket).items():
                out[i] = arr
        return jax.tree_util.tree_unflatten(treedef, out)

    def _reduce_bucket(self, buf: jax.Array, n: int, key) -> jax.Array:
        s = self.strategy
        if s == "psum_bucket":
            return lax.psum(buf, self.axis_name) / n
        if s == "psum_bf16_bucket":
            summed = lax.psum(buf.astype(jnp.bfloat16), self.axis_name)
            return (summed.astype(jnp.float32) / n).astype(buf.dtype)
        if s == "ring_bucket":
            return _ring_allreduce(buf, self.axis_name, n) / n
        if s == "ring_bf16_bucket":
            out = _ring_allreduce(buf, self.axis_name, n,
                                  wire_dtype=jnp.bfloat16)
            return (out.astype(jnp.float32) / n).astype(buf.dtype)
        if s == "ring_int8":
            out = _ring_allreduce_int8(buf, self.axis_name, n, key)
            return (out / n).astype(buf.dtype)
        raise AssertionError(f"not a bucketed reduce strategy: {s}")

    # -- zero1: fused exchange + sharded optimizer update --------------------
    def exchange_and_update(self, grads, opt_state, params, lr, opt, rng=None,
                            step=None):
        """ZeRO-1 step: reduce-scatter grad buckets (mean), update the local
        1/n shard of params with the (sharded) ``opt_state``, all-gather the
        updated params.  -> (new_params, new_opt_state).

        ``opt_state`` must be in this exchanger's bucket layout
        (:meth:`zero1_init_opt_state`), stored with
        :meth:`zero1_opt_state_specs` so each device holds exactly its
        shard.  Non-inexact param leaves pass through un-updated (same
        skip as ``exchange``; float params are the contract).  ``rng`` is
        accepted for signature parity with ``exchange`` and unused.

        With ``overlap`` on (``step`` required), all three stages are
        chained in reverse layout order — the backward-readiness order:
        each bucket's packed grads are fenced on the previous bucket's
        scatter result (reduce-scatters issue during backward), the
        shard-local updates consume scattered buckets as they arrive
        (``chain=`` on :func:`theanompi_tpu.ops.opt.sharded_update`), and
        each all-gather is fenced on the previous gather (gathers issue
        as their bucket's update lands).  All fences are value-preserving,
        so the result is bit-identical to the unfenced schedule.
        """
        from theanompi_tpu.ops.opt import sharded_update

        n = self._mapped_axis_size()
        axis = self.axis_name
        p_leaves, treedef = jax.tree_util.tree_flatten(params)
        g_leaves = jax.tree_util.tree_flatten(grads)[0]
        buckets = _bucket_layout(p_leaves, self.bucket_bytes, n)
        idx = lax.axis_index(axis) if n > 1 else 0
        overlap = self.overlap and n > 1
        order = list(range(len(buckets)))
        pred, chain = None, None
        if overlap:
            pred = self._chain_pred(step)
            order = order[::-1]
            chain = (order, lambda buf, prev: _fence(buf, prev, pred))
        g_shards: list = [None] * len(buckets)
        p_shards: list = [None] * len(buckets)
        prev = None
        for bi in order:
            bucket = buckets[bi]
            g = _pack(g_leaves, bucket)
            p = _pack(p_leaves, bucket)
            if n > 1:
                if prev is not None:
                    g = _fence(g, prev, pred)
                g = lax.psum_scatter(g.reshape(n, -1), axis,
                                     scatter_dimension=0, tiled=False) / n
                p = lax.dynamic_index_in_dim(p.reshape(n, -1), idx, 0,
                                             keepdims=False)
            if overlap:
                prev = g
            g_shards[bi] = g
            p_shards[bi] = p
        new_shards, new_opt_state = sharded_update(
            opt, g_shards, opt_state, p_shards, lr, axis_name=axis,
            chain=chain)
        out = list(p_leaves)
        prev = None
        for bi in order:
            bucket, shard = buckets[bi], new_shards[bi]
            if n > 1:
                if prev is not None:
                    shard = _fence(shard, prev, pred)
                full = lax.all_gather(shard, axis, axis=0, tiled=True)
            else:
                full = shard
            if overlap:
                prev = full
            for i, arr in _unpack(full, bucket).items():
                out[i] = arr
        return jax.tree_util.tree_unflatten(treedef, out), new_opt_state

    def zero1_layout(self, params, axis_size: int) -> list[_Bucket]:
        """The bucket layout for ``params`` at worker count ``axis_size`` —
        host-side twin of the trace-time layout (same greedy walk over the
        same leaf order, so they cannot disagree)."""
        leaves = jax.tree_util.tree_flatten(params)[0]
        return _bucket_layout(leaves, self.bucket_bytes, max(1, axis_size))

    def zero1_init_opt_state(self, optimizer, params, axis_size: int):
        """Optimizer state over flat GLOBAL ``(padded,)`` bucket buffers —
        place with :meth:`zero1_opt_state_specs` so each device stores only
        its ``1/n`` slice (the ZeRO-1 HBM saving)."""
        tmpl = [jnp.zeros((b.padded,), b.dtype)
                for b in self.zero1_layout(params, axis_size)]
        return optimizer.init(tmpl)

    def zero1_opt_state_specs(self, optimizer, params, axis_size: int):
        specs = [P(self.axis_name)
                 for _ in self.zero1_layout(params, axis_size)]
        return optimizer.init_specs(specs)

    # -- static accounting ---------------------------------------------------
    def wire_bytes(self, tree, axis_size: int) -> int:
        """Static per-device bytes-on-wire for ONE exchange of ``tree``.

        Counts exactly the payload :meth:`exchange` reduces (inexact leaves
        only) at the strategy's wire dtype, times the ring traffic factor
        ``2*(n-1)/n`` applied once to the total element count per dtype.
        ``zero1`` moves the same total: ``(n-1)/n`` of the grad buckets out
        (reduce-scatter) plus ``(n-1)/n`` of the param buckets back
        (all-gather), both at the leaf dtype.  Bucket padding and
        ``ring_int8``'s per-chunk fp32 scales are excluded (<0.1% at 4 MiB
        buckets) so the compression invariants stay EXACT: ``psum_bf16*``
        reports exactly ½ and ``ring_int8`` exactly ¼ of ``psum`` for the
        same tree.  ``tree`` may hold arrays or ``ShapeDtypeStruct``s.
        """
        if axis_size <= 1 or self.strategy == "none":
            return 0
        per_dtype: dict = {}
        for leaf in jax.tree.leaves(tree):
            shape, dtype = _leaf_meta(leaf)
            if not jnp.issubdtype(dtype, jnp.inexact):
                continue
            per_dtype[dtype] = per_dtype.get(dtype, 0) + math.prod(shape)
        total = 0
        for dtype, elems in per_dtype.items():
            wire_elems = 2 * (axis_size - 1) * elems // axis_size
            total += wire_elems * wire_itemsize(self.strategy, dtype)
        return total

    def bucket_summary(self, tree, axis_size: int) -> dict | None:
        """Bucket-count/byte summary for telemetry's one-time accounting
        event; None for leaf-wise strategies."""
        if not self.bucketed:
            return None
        buckets = self.zero1_layout(tree, axis_size)
        return {
            "n_buckets": len(buckets),
            "bucket_bytes": self.bucket_bytes,
            "padded_bytes": sum(
                b.padded * jnp.dtype(b.dtype).itemsize for b in buckets),
        }

    def __repr__(self):
        extra = ", overlap=True" if self.overlap else ""
        return (f"Exchanger(strategy={self.strategy!r}, "
                f"axis={self.axis_name!r}{extra})")
