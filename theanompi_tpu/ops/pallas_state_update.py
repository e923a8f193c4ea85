"""Mamba-2 decode state update, in place in the whole pool (ISSUE 30).

One token for every slot of a batch moves each head's recurrent state
``S`` ``[P, N]`` one step and reads the token's output off it::

    S' = a S + (dt x) (x) B        y = S' C

as a Pallas TPU kernel that brings each slot's state through VMEM once:
read ``S``, store ``S'``, and reduce ``y`` from the ``S'`` still in VMEM.
The layer is all bytes (4 MB of float32 state a slot against a few KB of
``a``, ``dt x``, ``B``, ``C``), so the kernel's time is the time two passes
over the layer's states take in HBM.  XLA compiles the same lines as two
fusions that each recompute ``S'`` from ``S`` — one reduces ``y``, one
scatters ``S'`` — and so makes three passes.

**The pool goes in whole.**  The operand is the ``[L, B, H, P, N]`` pool of
every state layer with a static ``layer`` in the index maps, never
``pool[layer]``: a custom call's operand is a whole buffer, and XLA copies
a slice ahead of every call (ISSUE 26).  ``input_output_aliases`` hands the
same buffer back, so a call writes its layer's blocks and the other layers
stay where they are; with the pool donated to the program, the calls of a
step update one buffer in place.

**What a grid step moves.**  The grid is ``(slot, head tile)``; a tile is
whole groups of heads of one slot, contiguous in HBM.  Per head the state
takes the scalar ``a`` (SMEM, scalar prefetch) and the outer product of the
head's ``dt x`` — handed over with ``P`` on the sublanes, ``[B, T, P, ht]``,
so a head's column broadcasts along the lanes — with its group's ``B`` row;
that is the plain lines' arithmetic, product for product, and ``S'`` comes
out bit-equal to theirs.  ``y`` is one product a group on the idle MXU,
``C [8, N] . S'_group [R P, N]^T`` at the highest precision: its sum over
``N`` is float32 in another order than the plain lines' (1e-7 relative).

**The tile is 2 MB (64 heads of 64 x 128 float32).**  Measured on a v5e at
the served geometry (L 5, B 128, H 128, G 8, P 64, N 128; PERF.md section 6,
PR 30), ms a layer: the plain lines 2.454; a kernel that only copies each
tile in and out 1.803 / 1.718 / 1.671 / 1.653 at 16 / 32 / 64 / 128 heads a
tile — 650 GB/s, the rate this chip reads and writes at once; this kernel
1.659 / 1.653 / 1.656 at 32 / 64 / 128, so the arithmetic hides under the
copies.  The two forms of ``y`` on the VPU (a lane reduction a head, stored
by column or selected into a tile) took 1.780 and 1.944 at 128 heads.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the name the custom call carries in a trace (``DEVICE_SCOPES["kernels"]``)
SCOPE = "mamba_state_update"
#: one tile of state (module docstring); held four times: in, out, twice each
_TILE_BYTES = 2 * 1024 * 1024


def state_update_supported(head_dim: int, state: int, dtype) -> bool:
    """Shape gate for the COMPILED kernel: float32 state, ``state`` a whole
    number of 128 lanes and ``head_dim`` of 8 sublanes.  Callers take the
    plain lines when False; the interpreter (tests) runs any shape."""
    return (jnp.dtype(dtype) == jnp.float32 and state % 128 == 0
            and head_dim % 8 == 0)


def head_tile(heads: int, groups: int, head_dim: int, state: int) -> int:
    """Heads in a tile: the most whole groups that divide ``heads`` and fit
    the tile's bytes; one group where not even one fits."""
    r = heads // groups
    fits = [n * r for n in range(1, groups + 1) if groups % n == 0
            and n * r * head_dim * state * 4 <= _TILE_BYTES]
    return max(fits) if fits else r


def _kernel(a_ref, dtx_ref, b_ref, c_ref, s_ref, o_ref, y_ref, *, ht, r):
    slot, first = pl.program_id(0), pl.program_id(1) * ht
    p, n = s_ref.shape[1:]
    for g in range(ht // r):
        b = b_ref[g:g + 1, :]
        for h in range(g * r, (g + 1) * r):
            o_ref[h] = (a_ref[slot, first + h] * s_ref[h]
                        + dtx_ref[:, h:h + 1] * b)
        c = jnp.broadcast_to(c_ref[g:g + 1, :], (8, n))
        y = jax.lax.dot_general(
            c, o_ref[g * r:(g + 1) * r].reshape(r * p, n),
            (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        y_ref[g:g + 1, :] = y[:1]


def state_update(pool, layer: int, a, dtx, b, c, *,
                 interpret: bool | None = None):
    """State layer ``layer`` of ``pool`` ``[L, B, H, P, N]`` float32 one
    token on, for every slot: ``a`` ``[B, H]`` the decay ``exp(dt A)``,
    ``dtx`` ``[B, H, P]``, ``b`` and ``c`` ``[B, G, N]`` (head ``h`` reads
    group ``h // (H / G)``), all float32 -> (the pool with that layer's
    states replaced, the same buffer where ``pool`` is donated; ``y``
    ``[B, H, P]`` float32).  ``interpret=None``: compiled on a TPU (gate
    with :func:`state_update_supported`), the interpreter elsewhere."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n_layers, bsz, h, p, n = pool.shape
    g = b.shape[1]
    if (a.shape, dtx.shape, b.shape, c.shape) != (
            (bsz, h), (bsz, h, p), (bsz, g, n), (bsz, g, n)) or h % g:
        raise ValueError(f"state_update: pool {pool.shape}, a {a.shape}, "
                         f"dtx {dtx.shape}, b {b.shape}, c {c.shape} do not "
                         f"agree")
    if not 0 <= layer < n_layers:
        raise ValueError(f"state_update: layer {layer} of {n_layers}")
    if not interpret and not state_update_supported(p, n, pool.dtype):
        raise ValueError(
            f"state_update: unsupported P={p} N={n} ({pool.dtype}) for the "
            "compiled kernel; gate with state_update_supported()")
    r = h // g
    ht = head_tile(h, g, p, n)
    nt, gt = h // ht, ht // r
    tile = (None, None, ht, p, n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, nt),
        in_specs=[
            pl.BlockSpec((None, None, p, ht), lambda i, t, a: (i, t, 0, 0)),
            pl.BlockSpec((None, None, gt, n), lambda i, t, a: (i, t, 0, 0)),
            pl.BlockSpec((None, None, gt, n), lambda i, t, a: (i, t, 0, 0)),
            pl.BlockSpec(tile, lambda i, t, a: (layer, i, t, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec(tile, lambda i, t, a: (layer, i, t, 0, 0)),
            pl.BlockSpec((None, None, gt, r * p),
                         lambda i, t, a: (i, t, 0, 0)),
        ],
    )
    fn = pl.pallas_call(
        functools.partial(_kernel, ht=ht, r=r),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((bsz, nt, gt, r * p), jnp.float32)],
        # operand 4 (after the prefetched ``a``) is the pool: same buffer out
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=4 * ht * p * n * 4 + 16 * 1024 * 1024),
        interpret=interpret,
        name=SCOPE,
    )
    f32 = jnp.float32
    # a head's ``dt x`` as a column: ``P`` on the sublanes
    columns = dtx.astype(f32).reshape(bsz, nt, ht, p).transpose(0, 1, 3, 2)
    with jax.named_scope(SCOPE):  # the custom call's name in a trace
        pool, y = fn(a.astype(f32), columns,
                     b.astype(f32).reshape(bsz, nt, gt, n),
                     c.astype(f32).reshape(bsz, nt, gt, n), pool)
    return pool, y.reshape(bsz, h, p)
