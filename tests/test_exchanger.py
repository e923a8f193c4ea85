"""Exchanger strategy tests: every strategy must compute the cross-replica mean.

Reference parity target (SURVEY.md §2.1): BSP_Exchanger.exchange() averaging
worker gradients; strategies ar/asa32/asa16/nccl32/nccl16 → psum/ring/…bf16.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from theanompi_tpu.parallel.mesh import shard_map
from jax.sharding import PartitionSpec as P

from theanompi_tpu.parallel.exchanger import (
    BUCKETED_STRATEGIES,
    STRATEGIES,
    Exchanger,
    _bucket_layout,
    fused_pmean,
)
from theanompi_tpu.parallel.mesh import DATA_AXIS

#: every strategy whose plain exchange() computes a mean (zero1 fuses the
#: exchange into the optimizer update — covered by the train-step matrix;
#: 'none' deliberately skips the mean, see test_none_strategy_skips_exchange)
MEAN_STRATEGIES = sorted(
    (set(STRATEGIES) | set(BUCKETED_STRATEGIES)) - {"none", "zero1"}
)

#: documented numeric tolerance per wire format: fp32 strategies are
#: float-round-off; bf16 accumulates ~O(n) wire-dtype rounding; int8's
#: per-chunk-scale stochastic rounding error is zero-mean with worst case
#: ~hops x max|partial|/127/n (measured ~5e-3 on the randn config below)
TOL = {"bf16": 1e-2, "int8": 5e-2, "fp32": 1e-6}


def _tol(strategy: str) -> float:
    if "int8" in strategy:
        return TOL["int8"]
    return TOL["bf16"] if "bf16" in strategy else TOL["fp32"]


def _run_exchange(mesh, strategy, per_device_vals):
    """per_device_vals: [n, ...] array; returns exchanged per-device output."""
    n = mesh.shape[DATA_AXIS]
    ex = Exchanger(strategy=strategy)

    def f(x):
        return jax.tree.map(lambda a: a[0], ex.exchange({"g": x}))["g"][None]

    out = shard_map(
        f, mesh=mesh, in_specs=P(DATA_AXIS), out_specs=P(DATA_AXIS),
        check=False,
    )(per_device_vals)
    return np.asarray(out)


@pytest.mark.parametrize("strategy", MEAN_STRATEGIES)
def test_strategy_computes_mean(mesh4, strategy):
    """Every strategy computes the cross-replica mean — AND leaves every
    replica bit-identical (for ring_int8 that is a designed property: the
    all-gather circulates each owner's quantized payload verbatim, so
    dequantization cannot drift across devices)."""
    rng = np.random.RandomState(0)
    vals = rng.randn(4, 3, 5).astype(np.float32)
    out = _run_exchange(mesh4, strategy, jnp.asarray(vals))
    expect = vals.mean(axis=0)
    tol = _tol(strategy)
    for i in range(4):
        np.testing.assert_allclose(out[i], expect, rtol=tol, atol=tol)
    for i in range(1, 4):
        np.testing.assert_array_equal(out[i], out[0])


def test_none_strategy_skips_exchange(mesh8):
    """'none' must leave per-worker grads unreduced (replicas diverge)."""
    vals = np.arange(8 * 3, dtype=np.float32).reshape(8, 3)
    out = _run_exchange(mesh8, "none", jnp.asarray(vals))
    np.testing.assert_array_equal(out, vals)  # untouched, NOT the mean


@pytest.mark.parametrize("strategy", ["ring", "psum", "ring_int8"])
def test_strategy_ragged_sizes(mesh4, strategy):
    # sizes not divisible by n exercise the ring/bucket padding paths
    rng = np.random.RandomState(1)
    vals = rng.randn(4, 13).astype(np.float32)  # 13 not divisible by 4
    out = _run_exchange(mesh4, strategy, jnp.asarray(vals))
    tol = max(_tol(strategy), 1e-5)
    for i in range(4):
        np.testing.assert_allclose(out[i], vals.mean(axis=0), rtol=tol, atol=tol)


def test_exchange_identity_on_single_device_mesh():
    from theanompi_tpu.parallel.mesh import make_mesh

    mesh1 = make_mesh(n_data=1, devices=jax.devices()[:1])
    ex = Exchanger()

    def f(t):
        out = ex.exchange(jax.tree.map(lambda a: a[0], t))
        return jax.tree.map(lambda a: a[None], out)

    tree = {"a": jnp.ones((1, 2)), "b": [jnp.zeros((1, 3))]}
    out = shard_map(f, mesh1, P(DATA_AXIS), P(DATA_AXIS), check=False)(tree)
    np.testing.assert_array_equal(np.asarray(out["a"]), np.ones((1, 2)))


def test_exchange_outside_mapped_context_raises():
    ex = Exchanger()
    with pytest.raises(ValueError, match="inside shard_map"):
        ex.exchange({"a": jnp.ones((2,))})


def test_int_leaves_pass_through_unreduced(mesh8):
    # opt-state pytrees may carry int step counters; exchange must not
    # mean-reduce them into floats
    ex = Exchanger(strategy="psum")

    def f(t):
        local = jax.tree.map(lambda a: a[0], t)
        return jax.tree.map(lambda a: a[None], ex.exchange(local))

    out = shard_map(
        f, mesh=mesh8, in_specs=P(DATA_AXIS), out_specs=P(DATA_AXIS), check=False
    )({"w": jnp.ones((8, 2)), "step": jnp.full((8, 1), 7, jnp.int32)})
    assert out["step"].dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out["step"]).ravel(), 7)


def test_unknown_strategy_raises():
    with pytest.raises(ValueError):
        Exchanger(strategy="warp_drive")


def test_bf16_strategy_halves_error_not_correctness(mesh8):
    # all-equal inputs: bf16 path must be exact
    vals = jnp.full((8, 4), 3.0, jnp.float32)
    out = _run_exchange(mesh8, "psum_bf16", vals)
    np.testing.assert_allclose(out, 3.0)


# -- bucket layout (ISSUE 2 tentpole) ----------------------------------------

def test_bucket_layout_greedy_dtype_grouped():
    """Layout unit: dtype grouping, greedy fill, oversized-leaf bucket,
    int passthrough, padding to a multiple of n — all host-side."""
    leaves = [
        np.zeros((100,), np.float32),   # 400 B
        np.zeros((100,), np.float32),   # 400 B -> same bucket (800 <= 1024)
        np.zeros((100,), np.float32),   # would overflow -> new bucket
        np.zeros((1000,), np.float32),  # oversized (4000 B > 1024): own bucket
        jnp.zeros((10,), jnp.bfloat16),  # separate dtype group
        np.zeros((), np.int32),         # non-inexact: not bucketed at all
    ]
    buckets = _bucket_layout(leaves, bucket_bytes=1024, n=8)
    by_dtype = {}
    for b in buckets:
        by_dtype.setdefault(str(np.dtype(b.dtype)), []).append(b)
    f32 = by_dtype["float32"]
    assert [list(b.indices) for b in f32] == [[0, 1], [2], [3]]
    assert all(b.padded % 8 == 0 and b.padded >= b.elems for b in buckets)
    assert [list(b.indices) for b in by_dtype["bfloat16"]] == [[4]]
    assert not any(5 in b.indices for b in buckets)


def test_bucketed_exchange_splits_on_bucket_size(mesh4):
    """A small bucket_bytes must split the tree into several fused buckets
    and still compute the exact mean."""
    rng = np.random.RandomState(5)
    host = {f"w{i}": rng.randn(4, 11).astype(np.float32) for i in range(6)}
    ex = Exchanger(strategy="psum_bucket", bucket_bytes=2 * 11 * 4)  # 2 leaves

    def f(t):
        local = jax.tree.map(lambda a: a[0], t)
        return jax.tree.map(lambda a: a[None], ex.exchange(local))

    out = shard_map(f, mesh4, P(DATA_AXIS), P(DATA_AXIS), check=False)(
        jax.tree.map(jnp.asarray, host))
    layout = _bucket_layout(
        [jax.ShapeDtypeStruct((11,), np.float32)] * 6, 2 * 11 * 4, 4)
    assert len(layout) == 3
    for k, v in host.items():
        np.testing.assert_allclose(np.asarray(out[k])[0], v.mean(axis=0),
                                   rtol=1e-6, atol=1e-6)


def test_fused_pmean_matches_leafwise(mesh4):
    """fused_pmean == per-leaf lax.pmean for floats; ints pass through."""
    rng = np.random.RandomState(6)
    host = {"a": rng.randn(4, 4).astype(np.float32),
            "b": rng.randn(4, 3, 2).astype(np.float32),
            "c": rng.randn(4).astype(np.float32),
            "n": np.full((4, 1), 3, np.int32)}

    def f(t):
        local = jax.tree.map(lambda a: a[0], t)
        return jax.tree.map(lambda a: a[None], fused_pmean(local, DATA_AXIS))

    out = shard_map(f, mesh4, P(DATA_AXIS), P(DATA_AXIS), check=False)(
        jax.tree.map(jnp.asarray, host))
    for k in ("a", "b", "c"):
        np.testing.assert_allclose(np.asarray(out[k])[0],
                                   host[k].mean(axis=0), rtol=1e-6, atol=1e-6)
    assert out["n"].dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out["n"]).ravel(), 3)


# -- wire-byte invariants (ISSUE 2 satellite) --------------------------------

@pytest.mark.parametrize("strategy,num,den", [
    ("psum", 1, 1),
    ("psum_bucket", 1, 1),
    ("ring", 1, 1),
    ("ring_bucket", 1, 1),
    ("zero1", 1, 1),          # reduce-scatter grads + all-gather params
    ("psum_bf16", 1, 2),      # bf16 wire: exactly half
    ("psum_bf16_bucket", 1, 2),
    ("ring_bf16", 1, 2),
    ("ring_bf16_bucket", 1, 2),
    ("ring_int8", 1, 4),      # int8 wire: exactly a quarter
])
def test_wire_bytes_compression_invariants(strategy, num, den):
    """The EXACT byte ratios vs psum the accounting contract documents —
    including under element counts the ring factor floors (the 33-element
    leaf) and with an int leaf that must not be counted at all."""
    tree = {"w": np.zeros((64, 32), np.float32),
            "b": np.zeros((33,), np.float32),
            "step": np.zeros((), np.int32)}
    base = Exchanger("psum").wire_bytes(tree, 8)
    got = Exchanger(strategy).wire_bytes(tree, 8)
    assert base > 0 and got * den == base * num
    # single worker: nothing on the wire for any strategy
    assert Exchanger(strategy).wire_bytes(tree, 1) == 0


def test_zero1_wire_bytes_matches_scatter_gather_arithmetic():
    """zero1 = (n-1)/n of the grad buckets out + (n-1)/n of the param
    buckets back, both fp32 — the sum IS psum's 2(n-1)/n ring total."""
    n = 8
    tree = {"w": np.zeros((1000,), np.float32)}
    elems = 1000
    scatter = (n - 1) * elems // n * 4
    gather = (n - 1) * elems // n * 4
    assert Exchanger("zero1").wire_bytes(tree, n) == scatter + gather


# -- full-train-step strategy equivalence (ISSUE 2 acceptance) ---------------
# The two-step runs live in conftest's session-scoped ``exchange_run``
# fixture (ISSUE 12 satellite, ROADMAP item 4): the fused-vs-overlapped
# bit-equality locks in test_overlap.py share these baselines instead of
# retraining them per module.


@pytest.fixture(scope="module")
def psum_two_step_params(mesh4, exchange_run):
    return exchange_run(mesh4, "psum")[1]


@pytest.mark.parametrize("strategy", ["psum_bucket", "ring_int8"])
def test_train_step_matches_psum(mesh4, exchange_run, psum_two_step_params,
                                 strategy):
    """Acceptance: the new strategies' full BSP train step matches psum
    numerics on the 4-device CPU mesh within the documented tolerance
    (fp32 bucket layouts are reduction-order-identical — near-bit-exact;
    int8 carries its wire-format rounding).  The bf16/ring bucket variants'
    numerics are covered at exchange level by the mean matrix above —
    their train-step plumbing is identical to psum_bucket's."""
    _, got = exchange_run(mesh4, strategy)
    tol = _tol(strategy)
    for a, b in zip(jax.tree.leaves(got),
                    jax.tree.leaves(psum_two_step_params)):
        np.testing.assert_allclose(a, b, rtol=tol, atol=tol)


# -- zero1 specifics (one shared training run) -------------------------------

@pytest.fixture(scope="module")
def zero1_run(mesh4, exchange_run):
    return exchange_run(mesh4, "zero1")


def test_zero1_train_step_matches_psum(zero1_run, psum_two_step_params):
    """Acceptance: reduce-scatter mean + shard-local update + all-gather
    reproduces the psum-exchange step (same elementwise update math)."""
    _, got = zero1_run
    for a, b in zip(jax.tree.leaves(got),
                    jax.tree.leaves(psum_two_step_params)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_zero1_opt_state_sharded_one_nth(zero1_run):
    """The ZeRO-1 claim itself: each device stores exactly 1/n of every
    momentum buffer (flat bucket layout, sharded over data)."""
    t, _ = zero1_run
    vels = t.opt_state["velocity"]
    assert isinstance(vels, list) and vels
    for vel in vels:
        assert vel.shape[0] % 4 == 0
        shards = vel.addressable_shards
        assert len({s.device for s in shards}) == 4
        for s in shards:
            assert s.data.shape[0] == vel.shape[0] // 4


def test_zero1_replicas_stay_in_sync(zero1_run):
    """All-gathered params must be identical on every device after steps."""
    t, _ = zero1_run
    leaf = jax.tree.leaves(t.params)[0]
    shards = [np.asarray(s.data) for s in leaf.addressable_shards]
    for s in shards[1:]:
        np.testing.assert_array_equal(s, shards[0])


def test_zero1_plain_exchange_raises():
    ex = Exchanger(strategy="zero1")
    with pytest.raises(ValueError, match="exchange_and_update"):
        ex.exchange({"a": jnp.ones((2,))})


def test_zero1_rejects_sharded_params(mesh4x2):
    """zero1 + tensor parallelism is refused up front, not silently wrong."""
    from theanompi_tpu.models.transformer_lm import TransformerLM
    from theanompi_tpu.parallel.bsp import BSPTrainer
    from theanompi_tpu.utils.recorder import Recorder

    model = TransformerLM({
        "batch_size": 2, "seq_len": 8, "vocab": 64, "dim": 16, "heads": 2,
        "n_layers": 1, "dropout": 0.0, "n_train": 16, "n_val": 8,
        "precision": "fp32", "verbose": False, "attn_impl": "blockwise",
    })
    t = BSPTrainer(model, mesh=mesh4x2, exch_strategy="zero1",
                   recorder=Recorder(verbose=False))
    with pytest.raises(ValueError, match="replicated"):
        t.compile_iter_fns()


def test_single_ring_strategies_reject_multi_axis():
    for strategy in ("ring", "ring_bucket", "ring_int8", "zero1"):
        with pytest.raises(ValueError, match="single ring"):
            Exchanger(strategy=strategy, axis_name=("data", "seq"))
    # the psum family (leaf-wise AND bucketed) accepts axis tuples
    for strategy in ("psum", "psum_bucket", "psum_bf16_bucket"):
        Exchanger(strategy=strategy, axis_name=("data", "seq"))


def test_exchanger_inside_jit_grad_pipeline(mesh8):
    """End-to-end shape: per-device grads -> exchange -> identical updates."""
    n = 8
    ex = Exchanger(strategy="psum")

    def per_device_loss(w, x):
        return jnp.sum((x @ w) ** 2)

    def step(w, x):
        g = jax.grad(per_device_loss)(w[0], x)
        g = ex.exchange(g)
        return (w[0] - 0.1 * g)[None]

    rng = np.random.RandomState(2)
    w = jnp.asarray(np.tile(rng.randn(1, 4, 2).astype(np.float32), (8, 1, 1)))
    x = jnp.asarray(rng.randn(8 * 3, 4).astype(np.float32))

    f = jax.jit(
        shard_map(
            step, mesh=mesh8,
            in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=P(DATA_AXIS),
            check=False,
        )
    )
    w_new = np.asarray(f(w, x.reshape(8, 3, 4).reshape(24, 4)))
    # every replica must hold the same updated params
    for i in range(1, 8):
        np.testing.assert_allclose(w_new[i], w_new[0], rtol=1e-6, atol=1e-6)
