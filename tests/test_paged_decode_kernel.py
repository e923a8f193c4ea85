"""ISSUE 18 parity locks: the serving decode fast path.

Three layers of lock, all CPU tier-1:

- **kernel vs fallback, to rounding** — the pallas paged-decode kernel
  (``interpret=True``) and the pure-JAX blockwise fallback compute the
  SAME online-softmax recurrence, so their outputs agree to a few fp32
  rounding steps across null-block padding, prefix-shared blocks (PR
  17's copy-on-write cache) and ragged per-slot positions.  (They were
  bit-identical under jaxlib 0.4.3x; on jax 0.9.0 the interpreter and
  XLA:CPU round the per-head reductions differently — 1.8e-7 apart — so
  the lock is a tolerance set from the dtype, not equality.)
- **fallback vs the PR 17 formula** — the fallback was restructured from
  one global softmax into the blockwise recurrence; the two are the same
  math up to the rounding association of the normalizer, pinned here
  against the VERBATIM old formula at ~1e-6.
- **int8 kernel vs dequantize-then-matmul** — same int8 payload, the
  only difference is scale association (``(x*s) @ q`` vs ``x @ (s*q)``),
  so the tolerance is plain fp32 rounding, never quantization error.
  Engine-level: kernel-on decode logits bit-equal to kernel-off
  (unquantized) and argmax-identical (quantized — the PR 9 lock's bar).

Engine tests ride the session ``serving_engine_factory`` fixture
(compile-light: each configuration's decode program compiles once per
tier-1 run).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from theanompi_tpu.ops import quant
from theanompi_tpu.ops.pallas_paged_attention import (
    paged_attend_decode,
    paged_decode_supported,
)
from theanompi_tpu.serving import BlockPool, blocks_for
from theanompi_tpu.serving.kv_cache import PagedKVCache

_NEG_INF = -1e30


# -- kernel vs fallback: to fp32 rounding -------------------------------------

#: a few rounding steps of fp32 at the outputs' O(1) magnitude; the two
#: paths differ only in how their reductions associate
_F32_TOL = dict(rtol=0, atol=4 * float(np.finfo(np.float32).eps) * 4)

def _pools(key, nblocks, bs, h, d, dtype=jnp.float32, layers=1):
    kk, kv = jax.random.split(key)
    shape = (layers, nblocks, bs, h, d)
    return (jax.random.normal(kk, shape, jnp.float32).astype(dtype),
            jax.random.normal(kv, shape, jnp.float32).astype(dtype))


#: (tables, positions): null-block padding, a prefix-SHARED block between
#: slots, an inactive null slot, ragged non-block-multiple positions and
#: completely full tables
TABLE_CASES = [
    ([[1, 2, 0, 0], [3, 4, 5, 0]], [5, 11]),
    ([[1, 2, 0, 0], [1, 3, 0, 0]], [7, 6]),
    ([[1, 0, 0, 0], [0, 0, 0, 0]], [2, 0]),
    ([[5, 4, 3, 2], [2, 3, 4, 5]], [15, 12]),
]


@pytest.mark.parametrize("tables,positions", TABLE_CASES)
@pytest.mark.parametrize("h,d", [(2, 16), (4, 8)])
def test_kernel_matches_fallback(tables, positions, h, d):
    bs, nblocks = 4, 6
    kp, vp = _pools(jax.random.PRNGKey(h * 100 + d), nblocks, bs, h, d)
    tbl = jnp.asarray(tables, jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(7), (len(tables), h, d),
                          jnp.float32)
    outs = {}
    for impl in ("kernel_interpret", "fallback"):
        cache = PagedKVCache(kp, vp, tbl, bs, decode_impl=impl)
        outs[impl] = np.asarray(cache.attend_decode(0, q, pos))
    assert np.isfinite(outs["fallback"]).all()
    np.testing.assert_allclose(outs["kernel_interpret"], outs["fallback"],
                               **_F32_TOL)


def test_kernel_matches_fallback_bf16():
    """Same lock in the serving cache's bf16 dtype: both paths upcast to
    fp32 for the recurrence and downcast once at the end, so they may
    land one bf16 rounding step apart."""
    bs, nblocks, h, d = 4, 6, 2, 16
    kp, vp = _pools(jax.random.PRNGKey(3), nblocks, bs, h, d,
                    dtype=jnp.bfloat16)
    tbl = jnp.asarray([[1, 2, 3, 0], [4, 1, 0, 0]], jnp.int32)
    pos = jnp.asarray([9, 4], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(8), (2, h, d),
                          jnp.float32).astype(jnp.bfloat16)
    outs = {}
    for impl in ("kernel_interpret", "fallback"):
        cache = PagedKVCache(kp, vp, tbl, bs, decode_impl=impl)
        outs[impl] = np.asarray(cache.attend_decode(0, q, pos)
                                .astype(jnp.float32))
    np.testing.assert_allclose(
        outs["kernel_interpret"], outs["fallback"], rtol=0,
        atol=float(jnp.finfo(jnp.bfloat16).eps) * 4)


@pytest.mark.parametrize("layer", [0, 2, 4])
def test_kernel_reads_its_own_layer_of_the_pool(layer):
    """ISSUE 26: the kernel takes the whole ``[L, ...]`` pools and finds
    its layer in the K/V index_map.  Every layer of this pool holds other
    values, so a wrong (or dropped) layer index reads O(1) away from the
    fallback at that layer — which ``L = 1`` pools cannot show."""
    n_layers, bs, nblocks, h, d = 5, 4, 6, 2, 16
    kp, vp = _pools(jax.random.PRNGKey(26), nblocks, bs, h, d,
                    layers=n_layers)
    tbl = jnp.asarray([[1, 2, 0, 0], [3, 4, 5, 0]], jnp.int32)
    pos = jnp.asarray([5, 11], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(27), (2, h, d), jnp.float32)
    outs = {}
    for impl in ("kernel_interpret", "fallback"):
        cache = PagedKVCache(kp, vp, tbl, bs, decode_impl=impl)
        outs[impl] = np.asarray(cache.attend_decode(layer, q, pos))
    np.testing.assert_allclose(outs["kernel_interpret"], outs["fallback"],
                               **_F32_TOL)
    # the lock has teeth: the same call one layer off is nowhere near
    other = np.asarray(PagedKVCache(kp, vp, tbl, bs, "kernel_interpret")
                       .attend_decode((layer + 1) % n_layers, q, pos))
    assert np.abs(other - outs["fallback"]).max() > 1e-2
    with pytest.raises(ValueError, match="outside the pool"):
        paged_attend_decode(kp, vp, n_layers, tbl, bs, q, pos,
                            interpret=True)


def test_decode_parity_at_a_tileable_geometry():
    """``decode_parity`` is the check chip_smoke.py runs compiled on the
    chip at the served geometry; here the same function under the
    interpreter at the smallest geometry the compiled gate admits."""
    from theanompi_tpu.serving.kv_cache import decode_parity

    res = decode_parity(16, 128, max_batch=4, max_context=64,
                        decode_impl="kernel_interpret")
    assert res["ok"] and res["finite"], res
    assert res["shape"] == [4, 16, 128] and res["dtype"] == "bfloat16"
    assert res["kv_heads"] == 16


@pytest.mark.parametrize("heads,kv_heads", [(48, 8), (32, 2)])
def test_decode_parity_of_a_grouped_pool(heads, kv_heads):
    """``kv_heads`` makes the pool a grouped one: the grouped kernel against
    the grouped gather, the served head counts at a short context."""
    from theanompi_tpu.serving.kv_cache import decode_parity

    res = decode_parity(heads, 128, kv_heads=kv_heads, max_batch=4,
                        max_context=128, decode_impl="kernel_interpret")
    assert res["ok"] and res["finite"], res
    assert res["shape"] == [4, heads, 128] and res["kv_heads"] == kv_heads


# -- fallback vs the PR 17 global softmax -------------------------------------

def _global_softmax_reference(cache, layer, q, positions):
    """VERBATIM PR 17 ``attend_decode`` (one softmax over the gathered
    context) — the formula the blockwise recurrence replaced."""
    scale = q.shape[-1] ** -0.5
    kb = jnp.take(cache.k[layer], cache.block_tables, axis=0)
    vb = jnp.take(cache.v[layer], cache.block_tables, axis=0)
    b = q.shape[0]
    t_max = cache.max_context
    kb = kb.reshape(b, t_max, *kb.shape[3:])
    vb = vb.reshape(b, t_max, *vb.shape[3:])
    qf = q.astype(jnp.float32) * scale
    s = jnp.einsum("bhd,bthd->bht", qf, kb.astype(jnp.float32))
    valid = jnp.arange(t_max)[None, :] <= positions[:, None]
    s = jnp.where(valid[:, None, :], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    ctx = jnp.einsum("bht,bthd->bhd", p, vb.astype(jnp.float32))
    return ctx.astype(q.dtype)


@pytest.mark.parametrize("tables,positions", TABLE_CASES)
def test_fallback_matches_the_pr17_global_softmax(tables, positions):
    bs, nblocks, h, d = 4, 6, 2, 16
    kp, vp = _pools(jax.random.PRNGKey(11), nblocks, bs, h, d)
    tbl = jnp.asarray(tables, jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(12), (len(tables), h, d),
                          jnp.float32)
    cache = PagedKVCache(kp, vp, tbl, bs, decode_impl="fallback")
    got = np.asarray(cache.attend_decode(0, q, pos))
    ref = np.asarray(_global_softmax_reference(cache, 0, q, pos))
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)


# -- shape gates --------------------------------------------------------------

def test_compiled_shape_gates_and_raise():
    assert paged_decode_supported(8, 128)
    assert not paged_decode_supported(2, 128)
    assert not paged_decode_supported(8, 64)
    assert paged_decode_supported(16, 128, jnp.bfloat16)
    assert not paged_decode_supported(8, 128, jnp.bfloat16)
    bs, h, d = 4, 2, 16
    kp, vp = _pools(jax.random.PRNGKey(0), 3, bs, h, d)
    with pytest.raises(ValueError, match="unsupported shape"):
        paged_attend_decode(kp, vp, 0,
                            jnp.asarray([[1, 2]], jnp.int32), bs,
                            jnp.zeros((1, h, d), jnp.float32),
                            jnp.asarray([3], jnp.int32), interpret=False)


# -- fused int8 matmul --------------------------------------------------------

def _qt(key, din, dout, chunk):
    w = jax.random.normal(key, (din, dout), jnp.float32)
    qq, ss = quant.quantize_chunked(w, jax.random.fold_in(key, 1), chunk)
    return w, quant.QuantizedTensor(qq, ss, (din, dout),
                                    jnp.dtype(jnp.float32))


@pytest.mark.parametrize("din,dout,chunk", [
    (32, 24, 24),    # case A: one row per chunk
    (32, 24, 48),    # case A: two rows per chunk
    (16, 48, 16),    # case B: three chunks per row
    (64, 32, 32),
])
def test_int8_matmul_matches_dequantize(din, dout, chunk):
    assert quant.int8_matmul_supported((din, dout), chunk)
    _, qt = _qt(jax.random.PRNGKey(din + dout), din, dout, chunk)
    x = jax.random.normal(jax.random.PRNGKey(2), (3, din), jnp.float32)
    got = np.asarray(quant.int8_matmul(x, qt, interpret=True))
    ref = np.asarray(x @ qt.dequantize())
    # same int8 payload; only the scale association differs -> fp rounding
    np.testing.assert_allclose(got, ref, rtol=1e-5,
                               atol=1e-5 * np.abs(ref).max())


def test_int8_matmul_leading_dims_and_m_padding():
    """x with extra leading dims and a row count that is not a multiple
    of the 8-row sublane pad: the kernel pads M internally and slices."""
    _, qt = _qt(jax.random.PRNGKey(5), 32, 24, 24)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 5, 32), jnp.float32)
    got = np.asarray(quant.int8_matmul(x, qt, interpret=True))
    ref = np.asarray(x @ qt.dequantize())
    assert got.shape == (2, 5, 24)
    np.testing.assert_allclose(got, ref, rtol=1e-5,
                               atol=1e-5 * np.abs(ref).max())


def test_int8_supported_gate_and_matmul_any_fallback():
    # the serving head's odd vocab never tiles -> dequantize path
    assert not quant.int8_matmul_supported((32, 61), 1024)
    assert not quant.int8_matmul_supported((32,), 32)
    # interpret takes any tiling; COMPILED needs Mosaic-tileable bands
    assert quant.int8_matmul_supported((32, 24), 24)
    assert not quant.int8_matmul_supported((32, 24), 24, compiled=True)
    assert quant.int8_matmul_supported((256, 128), 128, compiled=True)
    # matmul_any on an unsupported leaf == dequantize-then-matmul exactly
    _, qt = _qt(jax.random.PRNGKey(9), 32, 61, 1024)
    x = jax.random.normal(jax.random.PRNGKey(10), (3, 32), jnp.float32)
    np.testing.assert_array_equal(np.asarray(quant.matmul_any(x, qt)),
                                  np.asarray(x @ qt.dequantize()))
    # and on a plain array it is exactly x @ w
    w = jax.random.normal(jax.random.PRNGKey(13), (32, 8), jnp.float32)
    np.testing.assert_array_equal(np.asarray(quant.matmul_any(x, w)),
                                  np.asarray(x @ w))


# -- engine level -------------------------------------------------------------

def _drive(engine, prompt, n_decode=10):
    """Prefill + greedy decode on slot 0; -> [(token, logits)] per step."""
    pool = BlockPool(engine.num_blocks)
    row = pool.alloc(blocks_for(len(prompt), engine.block_size))
    tok, last = engine.prefill(row, prompt, 0.0, rid=1)
    b = engine.max_batch
    tables = np.zeros((b, engine.max_blocks_per_seq), np.int32)
    tables[0, :len(row)] = row
    lengths = np.zeros(b, np.int32)
    lengths[0] = len(prompt)
    tokens = np.zeros(b, np.int32)
    tokens[0] = tok
    temps = np.zeros(b, np.float32)
    rids = np.zeros(b, np.int32)
    rids[0] = 1
    outs = [(int(tok), np.asarray(last))]
    for _ in range(n_decode):
        if lengths[0] % engine.block_size == 0:
            tables[0, lengths[0] // engine.block_size] = pool.alloc(1)[0]
        nxt, logits = engine.decode(tables, lengths, tokens, temps, rids)
        outs.append((int(nxt[0]), np.asarray(logits[0])))
        lengths[0] += 1
        tokens[0] = int(nxt[0])
    return outs


PROMPT = [7, 3, 11, 42, 5, 60, 1, 19, 23, 2]


def test_engine_kernel_on_bit_equal_logits(serving_engine,
                                           serving_engine_factory):
    """decode_kernel="on" (interpreter on CPU) vs the fallback engine:
    every decode step's logits are BIT-identical — the whole decode
    program differs only in the attend dispatch, and the two attends are
    the same recurrence."""
    eng_on = serving_engine_factory(decode_kernel="on")
    assert serving_engine.decode_impl == "fallback"
    assert eng_on.decode_impl == "kernel_interpret"
    off = _drive(serving_engine, PROMPT)
    on = _drive(eng_on, PROMPT)
    assert [t for t, _ in on] == [t for t, _ in off]
    for (_, lo), (_, lf) in zip(on, off):
        np.testing.assert_array_equal(lo, lf)


def test_engine_kernel_quantized_argmax_agreement(serving_engine_factory):
    """The PR 9 bar under the fused int8 kernel: the kernel-on quantized
    engine greedy-decodes the SAME tokens as the kernel-off quantized
    engine (whose path the PR 9 argmax-agreement lock covers), with
    logits within fp32-rounding tolerance of each other."""
    eng_off = serving_engine_factory(quantize_int8=True)
    eng_on = serving_engine_factory(quantize_int8=True, decode_kernel="on")
    assert eng_on.quantized and eng_off.quantized
    off = _drive(eng_off, PROMPT)
    on = _drive(eng_on, PROMPT)
    assert [t for t, _ in on] == [t for t, _ in off]
    for (_, lo), (_, lf) in zip(on, off):
        np.testing.assert_allclose(lo, lf, rtol=1e-5, atol=1e-5)
