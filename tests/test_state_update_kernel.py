"""The decode step's recurrent-state update
(``ops/pallas_state_update.py``, ISSUE 30) under the Pallas interpreter at a
tiny gated shape: against ``Mamba2.decode``'s plain lines, in place in the
whole pool, through two steps of the recurrence; the gate and the resolver;
what an engine reports; and the served geometry compiled for a v5e."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.ops import mamba2
from theanompi_tpu.ops import pallas_state_update as psu
from theanompi_tpu.ops.mamba2 import Mamba2

#: state layers, slots, heads, groups, head_dim, state: a shape the gate admits
L_, B_, H_, G_, P_, N_ = 2, 3, 16, 2, 8, 128
DIM = 32


def _operands(seed=0, heads=H_, groups=G_, p=P_, n=N_, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    pool = jax.random.normal(ks[0], (L_, B_, heads, p, n), dtype)
    a = jax.random.uniform(ks[1], (B_, heads), jnp.float32, 0.3, 1.0)
    dtx = 0.1 * jax.random.normal(ks[2], (B_, heads, p), jnp.float32)
    b = jax.random.normal(ks[3], (B_, groups, n), jnp.float32)
    c = jax.random.normal(ks[4], (B_, groups, n), jnp.float32)
    return pool, a, dtx, b, c


def _plain(pool, layer, a, dtx, b, c):
    """``Mamba2.decode``'s plain lines on the kernel's operands."""
    _, bsz, h, p, n = pool.shape
    g = b.shape[1]
    s = pool[layer].reshape(bsz, g, h // g, p, n)
    s = (a.reshape(bsz, g, -1)[..., None, None] * s
         + dtx.reshape(bsz, g, -1, p)[..., None] * b[:, :, None, None, :])
    y = jnp.sum(s * c[:, :, None, None, :], axis=-1)
    return s.reshape(bsz, h, p, n), y.reshape(bsz, h, p)


@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("heads,groups", [(16, 2), (16, 1), (8, 8)])
def test_the_kernel_is_the_plain_lines_in_place(layer, heads, groups):
    """``S'`` and ``y`` to float32 rounding, and the layers the call was not
    given come back bit-unchanged."""
    pool, a, dtx, b, c = _operands(layer, heads, groups)
    want_s, want_y = _plain(pool, layer, a, dtx, b, c)
    got, y = psu.state_update(pool, layer, a, dtx, b, c, interpret=True)
    np.testing.assert_allclose(got[layer], want_s, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=2e-5)
    np.testing.assert_array_equal(got[1 - layer], pool[1 - layer])


def test_a_tile_is_whole_groups_that_fit():
    # the served geometry: 4 of the 8 groups, 64 heads, 2 MB
    assert psu.head_tile(128, 8, 64, 128) == 64
    assert psu.head_tile(16, 2, 8, 128) == 16          # everything fits
    assert psu.head_tile(128, 1, 64, 128) == 128       # one group, too large
    assert psu.head_tile(96, 6, 64, 128) == 48         # 3 groups divide 6


@pytest.mark.parametrize("p,n,dtype,ok", [
    (8, 128, "float32", True), (64, 256, "float32", True),
    (8, 128, "bfloat16", False), (8, 64, "float32", False),
    (4, 128, "float32", False)])
def test_the_gate(p, n, dtype, ok):
    """float32 state, ``N`` whole 128-lane tiles, ``P`` whole 8-sublane
    tiles; a pin moves the platform test, never the gate; the compiled
    kernel refuses what the gate refuses."""
    assert psu.state_update_supported(p, n, dtype) is ok
    assert mamba2.resolve_state_update(p, n, dtype) == "plain"  # a CPU host
    with mamba2.pin_state_update("kernel_interpret"):
        assert mamba2.resolve_state_update(p, n, dtype) == (
            "kernel_interpret" if ok else "plain")
    assert mamba2.resolve_state_update(p, n, dtype) == "plain"
    if not ok:
        pool, a, dtx, b, c = _operands(0, p=p, n=n, dtype=jnp.dtype(dtype))
        with pytest.raises(ValueError, match="gate with"):
            psu.state_update(pool, 0, a, dtx, b, c, interpret=False)


def test_operands_that_do_not_agree_are_refused():
    pool, a, dtx, b, c = _operands()
    with pytest.raises(ValueError, match="do not agree"):
        psu.state_update(pool, 0, a[:, :8], dtx, b, c, interpret=True)
    with pytest.raises(ValueError, match="layer 2 of 2"):
        psu.state_update(pool, 2, a, dtx, b, c, interpret=True)
    with pytest.raises(ValueError, match="pin_state_update"):
        with mamba2.pin_state_update("on"):
            pass


# -- through the layer ---------------------------------------------------------

@pytest.fixture(scope="module")
def layer():
    mixer = Mamba2(DIM, H_, P_, N_, G_, chunk=4)
    params, _, _ = mixer.init(jax.random.PRNGKey(3), (DIM,))
    return mixer, params


def _pools(mixer):
    return {name: jnp.zeros((L_, B_, *shape), dt)
            for name, (shape, dt) in mixer.state_shapes().items()}


@pytest.mark.parametrize("which", [0, 1])
def test_decode_through_the_kernel_is_decode_through_the_plain_lines(
        layer, which):
    mixer, params = layer
    assert mixer.state_update_impl() == "plain"
    u = jax.random.normal(jax.random.PRNGKey(4), (B_, DIM))
    pools = jax.tree.map(
        lambda x: jax.random.normal(jax.random.PRNGKey(5), x.shape, x.dtype),
        _pools(mixer))
    want, want_pools = mixer.decode(params, u, pools, which)
    with mamba2.pin_state_update("kernel_interpret"):
        assert mixer.state_update_impl() == "kernel_interpret"
        got, got_pools = mixer.decode(params, u, pools, which)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got_pools["ssm"], want_pools["ssm"],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(got_pools["conv"], want_pools["conv"])
    # only this layer moved, by either path
    for p in (got_pools, want_pools):
        for name in p:
            np.testing.assert_array_equal(p[name][1 - which],
                                          pools[name][1 - which])
            assert not np.array_equal(p[name][which], pools[name][which])


@pytest.mark.parametrize("impl", ["plain", "kernel_interpret"])
def test_two_decode_steps_leave_the_state_prefill_leaves(layer, impl):
    """Slot 1 takes two tokens from an empty state; ``Mamba2.prefill`` of
    the same two tokens leaves the same state.  Slots 0 and 2 ride along
    as inactive slots do (position 0, whatever their state holds) and stay
    finite."""
    mixer, params = layer
    u = jax.random.normal(jax.random.PRNGKey(6), (2, DIM))
    _, want = mixer.prefill(params, u, jnp.int32(2))
    pools = _pools(mixer)
    pools["ssm"] = pools["ssm"].at[:, 0].set(3.0)   # a previous owner's state
    with mamba2.pin_state_update(impl):
        for t in range(2):
            batch = jnp.zeros((B_, DIM)).at[1].set(u[t])
            out, pools = mixer.decode(params, batch, pools, 1)
            assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(pools["ssm"][1, 1], want["ssm"],
                               rtol=1e-5, atol=1e-6)
    # the convolution's window is kept in bf16
    np.testing.assert_allclose(np.asarray(pools["conv"][1, 1], np.float32),
                               np.asarray(want["conv"], np.float32),
                               rtol=1e-2, atol=1e-2)
    assert all(np.isfinite(np.asarray(p, np.float32)).all()
               for p in pools.values())
    np.testing.assert_array_equal(pools["ssm"][0], _pools(mixer)["ssm"][0]
                                  .at[0].set(3.0))


@pytest.mark.parametrize("change,kept", [
    ({}, True), ({"state_size": 64}, False), ({"mamba_head_dim": 4}, False)])
def test_a_shape_the_gate_refuses_takes_the_plain_path(change, kept):
    """Pinned as a TPU host would resolve: the gated shape traces the
    kernel's custom call, the refused ones trace none."""
    from theanompi_tpu.models.hybrid_lm import HybridLM

    cfg = {"pattern": "M*", "dim": 32, "vocab": 50, "seq_len": 16,
           "mamba_heads": 8, "mamba_head_dim": 8, "state_size": 128,
           "n_groups": 2, "chunk_size": 4, **change}
    model = HybridLM(cfg)
    mixer = model._mixers["mamba"]
    params, _, _ = mixer.init(jax.random.PRNGKey(0), (32,))
    pools = {name: jnp.zeros((1, 2, *shape), dt)
             for name, (shape, dt) in mixer.state_shapes().items()}
    with mamba2.pin_state_update("kernel_interpret"):
        assert model.resolved_paths()["state_update"] == (
            "kernel_interpret" if kept else "plain")
        jaxpr = str(jax.make_jaxpr(
            lambda p, u, s: mixer.decode(p, u, s, 0))(
                params, jnp.zeros((2, 32)), pools))
    assert ("pallas_call" in jaxpr) is kept


# -- what an engine reports -----------------------------------------------------

def test_an_engine_on_a_cpu_reports_plain_and_tags_its_decode_spans():
    from theanompi_tpu.models.hybrid_lm import HybridLM
    from theanompi_tpu.serving.engine import InferenceEngine
    from theanompi_tpu.serving.scheduler import Request, Scheduler
    from theanompi_tpu.telemetry import spans
    from theanompi_tpu.telemetry.metrics import SERVE_STATE_UPDATE_TAGS

    model = HybridLM({"pattern": "MM*", "dim": 32, "vocab": 61, "seq_len": 32,
                      "mamba_heads": 8, "mamba_head_dim": 8,
                      "state_size": 128, "n_groups": 2, "chunk_size": 8})
    assert model.resolved_paths()["state_update"] == "plain"
    eng = InferenceEngine(model, model.init_params(jax.random.PRNGKey(0))[0],
                          block_size=4, max_batch=2)
    assert eng.state_update_impl == "plain"
    assert eng.resolved_paths()["state_update"] == "plain"
    sched = Scheduler(eng)
    # the ring is the process's: read what this scheduler recorded alone
    seq0 = max((r.seq for r in spans.snapshot()), default=-1)
    sched.submit(Request(rid=30, prompt=[1, 2, 3], max_new_tokens=3))
    while not sched.idle:
        sched.step()
    mine = [r for r in spans.snapshot() if r.seq > seq0]
    ours = [r for r in mine
            if r.name == "serve.decode" and r.tags["requests"] == [30]]
    assert len(ours) == 2
    for r in ours:  # two state layers, none through the kernel off the chip
        assert [r.tags[t] for t in SERVE_STATE_UPDATE_TAGS] == [2, 0]
    assert not any(t in r.tags for r in mine
                   if r.name == "serve.prefill" for t in SERVE_STATE_UPDATE_TAGS)


def test_a_model_without_a_state_layer_gains_no_tag_and_no_path():
    from theanompi_tpu.models.hybrid_lm import HybridLM
    from theanompi_tpu.serving.engine import InferenceEngine

    model = HybridLM({"pattern": "*E", "dim": 32, "vocab": 50, "seq_len": 16})
    assert "state_update" not in model.resolved_paths()
    eng = InferenceEngine(model, model.init_params(jax.random.PRNGKey(0))[0],
                          block_size=8, max_batch=2)
    assert eng.state_update_impl is None and eng._state_tags == {}
    assert "state_update" not in eng.resolved_paths()


def test_the_engine_decodes_the_same_tokens_through_the_kernel():
    """The whole served path: prefill writes a slot's state, decode steps
    move it through the kernel (the interpreter here), and the tokens are
    those of the plain lines."""
    from theanompi_tpu.models.hybrid_lm import HybridLM
    from theanompi_tpu.serving.engine import InferenceEngine
    from theanompi_tpu.serving.scheduler import Request, Scheduler

    cfg = {"pattern": "MM*", "dim": 32, "vocab": 61, "seq_len": 32,
           "mamba_heads": 8, "mamba_head_dim": 8, "state_size": 128,
           "n_groups": 2, "chunk_size": 8}
    got = {}
    for impl in ("plain", "kernel_interpret"):
        with mamba2.pin_state_update(impl):
            model = HybridLM(cfg)
            eng = InferenceEngine(
                model, model.init_params(jax.random.PRNGKey(1))[0],
                block_size=4, max_batch=2)
            assert eng.state_update_impl == impl
            sched = Scheduler(eng)
            for rid, prompt in enumerate([[1, 2, 3, 4, 5], [7, 8]]):
                sched.submit(Request(rid=rid, prompt=prompt, max_new_tokens=4))
            done = {}
            while not sched.idle:
                for r in sched.step():
                    done[r.rid] = list(r.generated)
        got[impl] = done
    assert got["plain"] == got["kernel_interpret"]
    assert sorted(got["plain"]) == [0, 1]


# -- compiled for the chip, without the chip -------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("n_layers,bsz,h,g,p,n", [
    (5, 128, 128, 8, 64, 128),    # nemotron3-super-ep4 as served
    (2, 3, 16, 2, 8, 128),        # the smallest state the gate admits
    (1, 4, 24, 3, 16, 256),       # groups that are no power of two
])
def test_the_served_geometry_compiles_for_a_v5e(one_chip, n_layers, bsz, h, g,
                                                p, n):
    """What the interpreter cannot show: Mosaic takes the tile, the column
    broadcast and the VMEM the call asks for, and the pool goes in and out
    as one buffer."""
    from jax.experimental.compilation_cache import compilation_cache

    def shape(*s):
        return jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(
            lambda pool, a, dtx, b, c: psu.state_update(
                pool, n_layers - 1, a, dtx, b, c, interpret=False),
            donate_argnums=0).lower(
                shape(n_layers, bsz, h, p, n), shape(bsz, h),
                shape(bsz, h, p), shape(bsz, g, n), shape(bsz, g, n)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    # in place: nothing the size of the pool, or of a layer of it, is made
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < bsz * h * p * n * 4
    assert mem.alias_size_in_bytes >= n_layers * bsz * h * p * n * 4
