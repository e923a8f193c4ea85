"""Optimizer and loss unit tests against hand-computed values."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.ops import SGD, Adam, RMSProp
from theanompi_tpu.ops.losses import (
    sigmoid_binary_cross_entropy,
    softmax_cross_entropy,
    top_k_error,
)


def test_sgd_vanilla_matches_formula():
    opt = SGD()
    params = {"w": jnp.array([1.0, 2.0])}
    grads = {"w": jnp.array([0.5, -1.0])}
    st = opt.init(params)
    new, st = opt.update(grads, st, params, lr=0.1)
    np.testing.assert_allclose(np.asarray(new["w"]), [0.95, 2.1], rtol=1e-6)


def test_sgd_momentum_two_steps():
    opt = SGD(momentum=0.9)
    p = {"w": jnp.zeros(1)}
    g = {"w": jnp.ones(1)}
    st = opt.init(p)
    p, st = opt.update(g, st, p, lr=1.0)  # v=-1, p=-1
    np.testing.assert_allclose(np.asarray(p["w"]), [-1.0])
    p, st = opt.update(g, st, p, lr=1.0)  # v=-1.9, p=-2.9
    np.testing.assert_allclose(np.asarray(p["w"]), [-2.9], rtol=1e-6)


def test_sgd_nesterov_differs_from_classic():
    g = {"w": jnp.ones(1)}
    p0 = {"w": jnp.zeros(1)}
    classic = SGD(momentum=0.9)
    nest = SGD(momentum=0.9, nesterov=True)
    pc, _ = classic.update(g, classic.init(p0), p0, lr=1.0)
    pn, _ = nest.update(g, nest.init(p0), p0, lr=1.0)
    np.testing.assert_allclose(np.asarray(pn["w"]), [-1.9], rtol=1e-6)
    assert not np.allclose(np.asarray(pc["w"]), np.asarray(pn["w"]))


def test_weight_decay_shrinks_params():
    opt = SGD(weight_decay=0.1)
    p = {"w": jnp.array([10.0])}
    g = {"w": jnp.array([0.0])}
    new, _ = opt.update(g, opt.init(p), p, lr=0.5)
    np.testing.assert_allclose(np.asarray(new["w"]), [9.5])  # 10 - 0.5*0.1*10


@pytest.mark.parametrize("opt", [SGD(momentum=0.9), Adam(), RMSProp()])
def test_optimizers_descend_quadratic(opt):
    def loss(p):
        return jnp.sum((p["w"] - 3.0) ** 2)

    p = {"w": jnp.zeros(4)}
    st = opt.init(p)
    lr = 0.1 if not isinstance(opt, Adam) else 0.3
    for _ in range(60):
        g = jax.grad(loss)(p)
        p, st = opt.update(g, st, p, lr)
    assert float(loss(p)) < 0.05


def test_softmax_cross_entropy_matches_manual():
    logits = jnp.array([[2.0, 1.0, 0.0], [0.0, 0.0, 0.0]])
    labels = jnp.array([0, 2])
    got = float(softmax_cross_entropy(logits, labels))
    p0 = np.exp(2.0) / (np.exp(2.0) + np.exp(1.0) + 1.0)
    expect = (-np.log(p0) - np.log(1 / 3)) / 2
    np.testing.assert_allclose(got, expect, rtol=1e-6)
    # bf16 logits still give fp32-precision loss
    got16 = float(softmax_cross_entropy(logits.astype(jnp.bfloat16), labels))
    np.testing.assert_allclose(got16, expect, rtol=1e-2)


def test_bce_matches_manual():
    logits = jnp.array([0.0, 100.0, -100.0])
    targets = jnp.array([0.5, 1.0, 0.0])
    got = float(sigmoid_binary_cross_entropy(logits, targets))
    np.testing.assert_allclose(got, np.log(2.0) / 3, rtol=1e-5)


def test_top_k_error():
    logits = jnp.array([[3.0, 2.0, 1.0], [3.0, 2.0, 1.0]])
    labels = jnp.array([0, 2])
    assert float(top_k_error(logits, labels, k=1)) == 0.5
    assert float(top_k_error(logits, labels, k=3)) == 0.0


# -- fused LM-head cross entropy ----------------------------------------------


def _naive_lm_loss(h, w, b, y):
    logits = (h @ w.astype(h.dtype) + b.astype(h.dtype)).astype(jnp.float32)
    return softmax_cross_entropy(logits, y)


# (vocab, chunk, t): chunk=8 over t=16 -> 4 genuine chunks; t=13 -> n=26
# pads to 32 and masks; chunk=None/1024 -> single chunk (both regimes of
# the scan carry are exercised: cross-chunk dw/db/lse accumulation AND the
# degenerate one-chunk path)
@pytest.mark.parametrize("vocab,chunk,t", [(37, 8, 16), (37, 8, 13),
                                           (64, None, 16), (64, 1024, 16)])
def test_fused_lm_xent_matches_naive_fp32(vocab, chunk, t):
    """Loss, metrics, and ALL grads (h, w, b) must match the naive
    [N, V]-materializing path — fwd+bwd equivalence (VERDICT r2 #3)."""
    from theanompi_tpu.ops.losses import fused_lm_xent

    r = np.random.RandomState(0)
    bsz, d = 2, 12
    h = jnp.asarray(r.randn(bsz, t, d).astype(np.float32))
    w = jnp.asarray(r.randn(d, vocab).astype(np.float32) * 0.2)
    b = jnp.asarray(r.randn(vocab).astype(np.float32) * 0.1)
    y = jnp.asarray(r.randint(0, vocab, size=(bsz, t)))

    def fused(h, w, b):
        return fused_lm_xent(h, w, b, y, chunk_tokens=chunk)[0]

    def naive(h, w, b):
        return _naive_lm_loss(h, w, b, y)

    lf, gf = jax.value_and_grad(fused, argnums=(0, 1, 2))(h, w, b)
    ln, gn = jax.value_and_grad(naive, argnums=(0, 1, 2))(h, w, b)
    np.testing.assert_allclose(float(lf), float(ln), rtol=1e-5)
    for a, bb, name in zip(gf, gn, ("dh", "dw", "db")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=1e-4, atol=1e-6, err_msg=name)

    # error metrics ride the same pass and must equal top_k_error
    logits = h @ w + b
    _, e1, e5 = fused_lm_xent(h, w, b, y, chunk_tokens=chunk)
    np.testing.assert_allclose(float(e1), float(top_k_error(logits, y, k=1)),
                               rtol=1e-6)
    np.testing.assert_allclose(float(e5), float(top_k_error(logits, y, k=5)),
                               rtol=1e-6)


def test_fused_lm_xent_bf16_close_to_naive():
    """bf16 inputs: the fused path accumulates scores in fp32 on the MXU, so
    it may only be MORE accurate than the naive bf16-logit path; assert
    agreement at bf16 tolerance."""
    from theanompi_tpu.ops.losses import fused_lm_xent

    r = np.random.RandomState(1)
    h = jnp.asarray(r.randn(2, 8, 16).astype(np.float32)).astype(jnp.bfloat16)
    w = jnp.asarray((r.randn(16, 96) * 0.2).astype(np.float32)).astype(jnp.bfloat16)
    b = jnp.zeros((96,), jnp.bfloat16)
    y = jnp.asarray(r.randint(0, 96, size=(2, 8)))
    lf = float(fused_lm_xent(h, w, b, y)[0])
    ln = float(_naive_lm_loss(h, w, b, y))
    assert abs(lf - ln) / max(abs(ln), 1e-6) < 2e-2


def test_fused_lm_xent_no_bias():
    from theanompi_tpu.ops.losses import fused_lm_xent

    r = np.random.RandomState(2)
    h = jnp.asarray(r.randn(1, 8, 8).astype(np.float32))
    w = jnp.asarray(r.randn(8, 32).astype(np.float32))
    y = jnp.asarray(r.randint(0, 32, size=(1, 8)))
    lf = float(fused_lm_xent(h, w, None, y)[0])
    ln = float(_naive_lm_loss(h, w, jnp.zeros((32,)), y))
    np.testing.assert_allclose(lf, ln, rtol=1e-5)


@pytest.mark.parametrize("unroll", [1, 2])
def test_fused_lm_xent_vocab_parallel_matches_unsharded(unroll):
    """Megatron parallel CE: the vocab-sharded fused loss (head
    P(None, model)) must reproduce the unsharded fused loss — value,
    metrics, and all grads, including the psum-pinned h-cotangent.
    ``unroll=2`` proves the r5 scan-unroll knob composes with the
    collective-assembled softmax (the reference here stays rolled, so
    this is a cross-unroll equality, stronger than same-vs-same)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from theanompi_tpu.ops.losses import fused_lm_xent, fused_lm_xent_vp
    from theanompi_tpu.parallel.mesh import MODEL_AXIS, make_mesh, shard_map

    r = np.random.RandomState(0)
    bsz, t, d, v = 2, 10, 12, 64  # n=20 tokens: pads inside an 8-chunk
    h = jnp.asarray(r.randn(bsz, t, d).astype(np.float32))
    w = jnp.asarray(r.randn(d, v).astype(np.float32) * 0.2)
    b = jnp.asarray(r.randn(v).astype(np.float32) * 0.1)
    y = jnp.asarray(r.randint(0, v, size=(bsz, t)))

    def ref(h, w, b):
        loss, e1, e5 = fused_lm_xent(h, w, b, y, chunk_tokens=8)
        return loss, (e1, e5)

    (lr_, (e1r, e5r)), gr = jax.value_and_grad(ref, argnums=(0, 1, 2),
                                               has_aux=True)(h, w, b)

    mesh = make_mesh(n_data=1, n_model=4)

    def vp(h, w, b):
        loss, e1, e5 = fused_lm_xent_vp(h, w, b, y, MODEL_AXIS,
                                        chunk_tokens=8, unroll=unroll)
        return loss, (e1, e5)

    f = jax.jit(shard_map(
        jax.value_and_grad(vp, argnums=(0, 1, 2), has_aux=True), mesh,
        in_specs=(P(), P(None, MODEL_AXIS), P(MODEL_AXIS)),
        out_specs=((P(), (P(), P())), (P(), P(None, MODEL_AXIS), P(MODEL_AXIS))),
    ))
    hw = jax.device_put(w, NamedSharding(mesh, P(None, MODEL_AXIS)))
    hb = jax.device_put(b, NamedSharding(mesh, P(MODEL_AXIS)))
    (lv, (e1v, e5v)), gv = f(h, hw, hb)

    np.testing.assert_allclose(float(lv), float(lr_), rtol=1e-5)
    np.testing.assert_allclose(float(e1v), float(e1r), rtol=1e-6)
    np.testing.assert_allclose(float(e5v), float(e5r), rtol=1e-6)
    for a, bb, name in zip(gv, gr, ("dh", "dw", "db")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=1e-4, atol=1e-6, err_msg=name)


def test_fused_lm_xent_unroll_exact_match():
    """unroll>1 is a scheduling hint, not a numerics change: loss, metrics,
    and all grads must be bit-comparable to the unroll=1 scan (r5 knob for
    the fused-loss scans' while-self-time share).  Also
    covers the non-divisible case (4 chunks, unroll=3)."""
    from theanompi_tpu.ops.losses import fused_lm_xent

    r = np.random.RandomState(1)
    bsz, t, d, vocab = 2, 16, 12, 37
    h = jnp.asarray(r.randn(bsz, t, d).astype(np.float32))
    w = jnp.asarray(r.randn(d, vocab).astype(np.float32) * 0.2)
    b = jnp.asarray(r.randn(vocab).astype(np.float32) * 0.1)
    y = jnp.asarray(r.randint(0, vocab, size=(bsz, t)))

    def run(unroll):
        def f(h, w, b):
            out = fused_lm_xent(h, w, b, y, chunk_tokens=8, unroll=unroll)
            return out[0], (out[1], out[2])

        (loss, errs), grads = jax.value_and_grad(
            f, argnums=(0, 1, 2), has_aux=True)(h, w, b)
        return loss, errs, grads

    l1, e1, g1 = run(1)
    for u in (3, 4):
        lu, eu, gu = run(u)
        np.testing.assert_allclose(float(lu), float(l1), rtol=1e-6)
        for a, bb in zip(eu, e1):
            np.testing.assert_allclose(float(a), float(bb), rtol=1e-6)
        for a, bb, name in zip(gu, g1, ("dh", "dw", "db")):
            np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                       rtol=1e-5, atol=1e-7, err_msg=name)
