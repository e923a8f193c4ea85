"""Shared int8 quantization primitives: per-chunk scale + stochastic rounding.

Extracted from ``parallel/exchanger.py`` (ISSUE 6) so the serving path can
reuse the exact wire format of the ``ring_int8`` exchange strategy without
importing the training-side exchanger (the serving lint forbids that edge):

- **per-chunk fp32 scale**: one ``max|x| / 127`` scale per fixed-size chunk
  of the flattened tensor — coarse enough to be free, fine enough that a
  single outlier only poisons its own chunk;
- **stochastic rounding**: ``floor(y + U[0,1))`` is an unbiased rounding of
  ``y``, so quantization error is zero-mean (for gradients that keeps the
  expected update exact; for weights it keeps the expected dequantized
  weight exact under the explicit PRNG key, making quantization a seeded,
  reproducible transform).

The exchanger's ring schedule quantizes per ring hop with these same
helpers; serving quantizes matmul weights once at load
(:mod:`theanompi_tpu.serving.quant`).

ISSUE 18 adds the serving-side consumers of the format, kept HERE so the
wire format and the kernel that eats it stay one module (and the kernels
layer of ``analysis/layers.py`` owns both):

- :class:`QuantizedTensor` — one quantized matmul weight as a pytree node
  (moved from ``serving/quant.py``, which re-exports it);
- :func:`int8_matmul` — a fused Pallas matmul that consumes the int8
  chunks DIRECTLY: the per-chunk fp32 scales ride the activation into the
  MXU dot (they vary along the contraction axis, so they must be applied
  before the accumulate), and the fp32 weight tensor the old
  dequantize-then-matmul materialized every step never exists;
- :func:`matmul_any` — the dispatch point the layer stack calls:
  ``x @ w`` for plain arrays, the fused kernel for supported
  :class:`QuantizedTensor` leaves, dequantize-then-matmul otherwise.

The chunked flat layout maps onto a 2D matmul without moving bytes: with
``W [Din, Dout]`` flattened row-major, either each chunk spans whole rows
(``chunk %% Dout == 0`` — one scale per row band, a single kernel band) or
each row spans whole chunks (``Dout %% chunk == 0`` — ``Dout // chunk``
column bands, per-row scales within each).  Both are metadata-only
reshapes of the wire payload, which is what keeps ``ring_int8``'s bytes
byte-identical.  Shapes satisfying neither (e.g. a 61-vocab test head)
fall back to dequantize-then-matmul via :func:`matmul_any`.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def quantize_chunk(x: jax.Array, key: jax.Array):
    """-> (int8 payload, fp32 scale) with per-chunk scale + stochastic
    rounding: ``E[dequantize(q)] == x`` because ``floor(y + U[0,1))`` is an
    unbiased rounding of ``y``.  The scale guard keeps all-zero chunks
    finite (0/eps -> exactly 0)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    y = x.astype(jnp.float32) / scale
    u = jax.random.uniform(key, y.shape)
    q = jnp.clip(jnp.floor(y + u), -127, 127).astype(jnp.int8)
    return q, scale


def quantize_chunked(x: jax.Array, key: jax.Array, chunk_elems: int):
    """Flatten ``x``, zero-pad to a multiple of ``chunk_elems``, quantize
    each chunk with its own scale; -> (q ``[n_chunks, chunk_elems]`` int8,
    scales ``[n_chunks]`` fp32).  ``vmap`` over chunks so every chunk gets
    an independent rounding stream from one key."""
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.size) % chunk_elems
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    chunks = flat.reshape(-1, chunk_elems)
    keys = jax.random.split(key, chunks.shape[0])
    return jax.vmap(quantize_chunk)(chunks, keys)


def dequantize_chunked(q: jax.Array, scales: jax.Array, shape, dtype):
    """Inverse of :func:`quantize_chunked`: drop the padding tail and
    restore ``shape``/``dtype``."""
    import numpy as np

    flat = (q.astype(jnp.float32) * scales[:, None]).reshape(-1)
    return flat[: int(np.prod(shape, dtype=np.int64))].reshape(shape).astype(dtype)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedTensor:
    """One quantized leaf: ``q [n_chunks, chunk]`` int8 + ``scales
    [n_chunks]`` fp32, with the original shape/dtype as static aux data."""

    q: jax.Array
    scales: jax.Array
    shape: tuple
    dtype: object

    def tree_flatten(self):
        return (self.q, self.scales), (self.shape, str(self.dtype))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux[0], jnp.dtype(aux[1]))

    def dequantize(self) -> jax.Array:
        return dequantize_chunked(self.q, self.scales, self.shape,
                                  self.dtype)

    @property
    def nbytes_quantized(self) -> int:
        return int(self.q.size + 4 * self.scales.size)


# ---------------------------------------------------------------------------
# fused int8 weight matmul (ISSUE 18)
# ---------------------------------------------------------------------------


def _int8_mm_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref):
    """One (column band, contraction tile) step: scale the activation by
    the band's per-row scales (fp32, on the VPU), one MXU dot against the
    raw int8 tile, accumulate in fp32 across contraction tiles."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        acc_ref[:, :] = jnp.zeros_like(acc_ref[:, :])

    xs = x_ref[:, :].astype(jnp.float32) * s_ref[0, :][None, :]
    qt = q_ref[:, :]
    if o_ref.dtype == jnp.bfloat16:
        # bf16 activations keep the MXU at its bf16 rate; fp32 runs exact
        xs, qt = xs.astype(jnp.bfloat16), qt.astype(jnp.bfloat16)
    else:
        qt = qt.astype(jnp.float32)
    acc_ref[:, :] += jax.lax.dot_general(
        xs, qt, dimension_numbers=((((1,), (0,)), ((), ()))),
        preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(1) - 1)
    def _():
        o_ref[:, :] = acc_ref[:, :].astype(o_ref.dtype)


#: contraction rows resident per grid step.  A whole ``[Din, band]`` int8
#: tile plus its bf16 copy overran the 16 MiB scoped VMEM at Din = 8192
#: (the dim-2048 FFN down-projection, found on the v5e); 2048 rows is the
#: largest tile the chip has compiled, and one step for every Din <= 2048.
_MAX_K_TILE = 2048


def _k_tile(din: int) -> int:
    """Largest 128-multiple <= ``_MAX_K_TILE`` that divides ``din`` (all
    of ``din`` when it is small or has no such divisor)."""
    if din <= _MAX_K_TILE:
        return din
    for tk in range(_MAX_K_TILE, 0, -128):
        if din % tk == 0:
            return tk
    return din


def _band_layout(qt: QuantizedTensor):
    """Metadata-only view of the chunked wire payload as ``(q2d [Din,
    Dout] int8, scales [bands, Din] fp32, bands)``; ``None`` when the
    chunking does not tile the 2D shape (see module docstring)."""
    if len(qt.shape) != 2:
        return None
    din, dout = (int(s) for s in qt.shape)
    chunk = int(qt.q.shape[1])
    if chunk % dout == 0:
        # row bands: each chunk covers chunk // Dout whole rows
        q2d = qt.q.reshape(-1, dout)[:din]
        srow = jnp.repeat(qt.scales, chunk // dout)[:din]
        return q2d, srow[None, :], 1
    if dout % chunk == 0:
        # column bands: each row is Dout // chunk consecutive chunks
        bands = dout // chunk
        return qt.q.reshape(din, dout), qt.scales.reshape(din, bands).T, bands
    return None


def int8_matmul_supported(shape, chunk_elems: int,
                          compiled: bool = False) -> bool:
    """Whether :func:`int8_matmul` can consume a ``[Din, Dout]`` weight
    quantized at ``chunk_elems``: the chunking must tile the 2D shape,
    and the COMPILED kernel additionally needs Mosaic-tileable bands
    (``interpret=True`` parity tests take any tiling shape)."""
    if len(shape) != 2:
        return False
    din, dout = (int(s) for s in shape)
    if chunk_elems % dout and dout % chunk_elems:
        return False
    if compiled:
        band_cols = dout if chunk_elems % dout == 0 else chunk_elems
        return din % 128 == 0 and band_cols % 128 == 0
    return True


def int8_matmul(x, qt: QuantizedTensor, interpret: bool | None = None):
    """``x @ dequantize(qt)`` without materializing the fp32 weight:
    ``x [..., Din]`` -> ``[..., Dout]`` in ``x.dtype``.

    Grid over (column bands, contraction tiles); per step the kernel
    holds an ``[M, tk]`` slice of the activation (decode batches are
    tiny), the band's raw ``[tk, band]`` int8 tile and its per-row
    scales, accumulating in fp32.  ``interpret=None`` auto-selects like
    the attention kernels.  Tolerance vs dequantize-then-matmul: the
    scale application associates ``(x * s) @ q`` instead of ``x @ (s *
    q)``, so results differ by normal fp rounding (~1e-7 relative, locked
    in tests), never by quantization error — both consume the same int8
    payload."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    layout = _band_layout(qt)
    if layout is None:
        raise ValueError(
            f"int8_matmul: chunking {qt.q.shape[1]} does not tile shape "
            f"{qt.shape}; gate with int8_matmul_supported()")
    q2d, scales, bands = layout
    din, dout = q2d.shape
    lead = x.shape[:-1]
    x2 = x.reshape(-1, din)
    m = x2.shape[0]
    m_pad = -(-m // 8) * 8  # sublane-align the batch; pad rows drop below
    if m_pad != m:
        x2 = jnp.concatenate(
            [x2, jnp.zeros((m_pad - m, din), x2.dtype)], axis=0)
    scales_bd = jnp.broadcast_to(scales[:, None, :], (bands, 8, din))
    cc = dout // bands
    tk = _k_tile(din)
    out = pl.pallas_call(
        _int8_mm_kernel,
        grid=(bands, din // tk),
        in_specs=[
            pl.BlockSpec((m_pad, tk), lambda b, k: (0, k)),
            pl.BlockSpec((tk, cc), lambda b, k: (k, b)),
            pl.BlockSpec((None, 8, tk), lambda b, k: (b, 0, k)),
        ],
        out_specs=pl.BlockSpec((m_pad, cc), lambda b, k: (0, b)),
        out_shape=jax.ShapeDtypeStruct((m_pad, dout), x.dtype),
        scratch_shapes=[pltpu.VMEM((m_pad, cc), jnp.float32)],
        interpret=interpret,
    )(x2, q2d, scales_bd)
    return out[:m].reshape(*lead, dout)


def matmul_any(x, w, interpret: bool | None = None):
    """The layer stack's matmul dispatch: plain ``x @ w`` for arrays, the
    fused int8 kernel for supported :class:`QuantizedTensor` leaves,
    dequantize-then-matmul for the rest.  A param tree that was fully
    dequantized upstream (the non-kernel serving path, and every training
    path) never reaches the isinstance branch, so this is free there."""
    if isinstance(w, QuantizedTensor):
        compiled = (jax.default_backend() == "tpu"
                    and interpret is not True)
        if int8_matmul_supported(w.shape, int(w.q.shape[1]),
                                 compiled=compiled):
            return int8_matmul(x, w, interpret)
        w = w.dequantize()
    return x @ w.astype(x.dtype)
