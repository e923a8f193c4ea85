"""The grouped-product kernel (``ops/pallas_grouped_matmul.py``) against
``jax.lax.ragged_dot`` through the Pallas interpreter on the CPU: the
schedule, the groups' edges, rows past the last group, the shape gate and
the row tile, and — compiled for a described v5e, without the chip — the
two products of the served expert layer at their real widths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from theanompi_tpu.ops import pallas_grouped_matmul as gmm

K, N = 64, 128

#: name -> (group sizes, M, row tile)
CASES = {
    "empty_groups_between": ([3, 0, 5, 0, 0, 9, 0], 32, 16),
    "every_group_empty": ([0, 0, 0, 0], 32, 16),
    "one_group_longer_than_two_tiles": ([2, 41, 3], 64, 16),
    "rows_past_the_last_group": ([4, 7, 1], 64, 16),
    "a_group_ends_on_a_tile_boundary": ([16, 16, 5, 11], 64, 16),
    "tile_of_8_in_float32_only": ([5, 3, 9, 0, 6], 24, 8),
    # 128 slots x top-22 over 512 experts with 128 held, at 1/8: 16 x 22
    # assignments over 64 experts, 16 held
    "decode_scaled_down": ([6, 4, 7, 5, 3, 8, 6, 5, 4, 6, 7, 5, 6, 4, 5, 7],
                           352, 16),
    "decode_scaled_down_in_the_tile_served": (
        [6, 4, 7, 5, 3, 8, 6, 5, 4, 6, 7, 5, 6, 4, 5, 7], 352, 128),
    # a 512-token prefill bucket at 1/8: 64 x 22 over 64 experts
    "prefill_bucket_scaled_down": ([30, 12, 25, 19, 28, 22, 17, 31, 24, 20,
                                    26, 15, 23, 27, 21, 18], 1408, 32),
}


def _operands(sizes, m, dtype, seed=0):
    rng = np.random.default_rng(seed)
    rows = jnp.asarray(rng.normal(size=(m, K)), dtype)
    w = jnp.asarray(rng.normal(size=(len(sizes), K, N)) / np.sqrt(K), dtype)
    return rows, w, jnp.asarray(sizes, jnp.int32)


@pytest.mark.parametrize("case,dtype", [
    (case, dtype) for case in sorted(CASES) for dtype in ("float32", "bfloat16")
    if dtype == "float32" or CASES[case][2] >= 16])  # a bf16 tile: 16 sublanes
def test_kernel_is_ragged_dot_over_the_groups(case, dtype):
    sizes, m, tm = CASES[case]
    rows, w, gs = _operands(sizes, m, jnp.dtype(dtype))
    held = sum(sizes)
    for out in (jnp.dtype(dtype), jnp.float32):
        got = gmm.grouped_matmul(rows, w, gs, tm=tm, out_dtype=out,
                                 interpret=True)
        want = lax.ragged_dot(rows, w, gs, preferred_element_type=out)
        assert got.shape == (m, N) and got.dtype == out
        if out == jnp.float32:
            # float32 accumulation on both sides: a few rounding steps
            np.testing.assert_allclose(got[:held], want[:held], atol=2e-5,
                                       rtol=1e-5)
        else:
            # rounded once to bf16: at most one step of the result apart
            a = np.asarray(got[:held], np.float32)
            b = np.asarray(want[:held], np.float32)
            assert np.all(np.abs(a - b) <= 2.0 ** -7 * np.abs(b) + 1e-6)


def test_the_schedule_visits_each_group_tile_once_in_row_order():
    sizes, m, tm = [3, 0, 5, 40, 0, 2], 64, 16
    off, gid, tid, n = (np.asarray(x) for x in gmm.group_visits(
        jnp.asarray(sizes, jnp.int32), m, tm))
    assert off.tolist() == [0, 3, 3, 8, 48, 48, 50]
    assert int(n) == 6 and len(gid) == m // tm + len(sizes) - 1
    # groups 0 and 2 share tile 0, group 3 spans tiles 0-2, group 5 starts
    # tile 3; the empty groups 1 and 4 are nowhere
    assert list(zip(gid[:6], tid[:6])) == [(0, 0), (2, 0), (3, 0), (3, 1),
                                           (3, 2), (5, 3)]
    # slots past the last visit repeat it (the grid never runs them)
    assert set(zip(gid[6:], tid[6:])) == {(5, 3)}
    # no group at all: no visit, and indices that are still in range
    off, gid, tid, n = (np.asarray(x) for x in gmm.group_visits(
        jnp.zeros((4,), jnp.int32), 32, 16))
    assert int(n) == 0 and gid.max() < 4 and tid.max() < 2 and tid.min() >= 0


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_nan_past_the_last_group_reaches_nothing_that_is_kept(dtype):
    """Rows past the last group are never visited: a NaN planted in them
    (or left in them by the interpreter, which fills an unwritten output
    with NaN) stays out of every row a group owns, through both products
    of the layer."""
    sizes, m, tm = [4, 7, 1], 64, 16
    held = sum(sizes)
    rows, w1, gs = _operands(sizes, m, jnp.dtype(dtype))
    w2 = jnp.swapaxes(w1, 1, 2)
    clean = gmm.grouped_matmul(rows, w1, gs, tm=tm, interpret=True)
    rows = rows.at[held:].set(jnp.nan)
    h = gmm.grouped_matmul(rows, w1, gs, tm=tm, interpret=True)
    np.testing.assert_array_equal(np.asarray(h[:held], np.float32),
                                  np.asarray(clean[:held], np.float32))
    assert not np.isfinite(np.asarray(h[held:], np.float32)).any()
    y = gmm.grouped_matmul(h, w2, gs, tm=tm, out_dtype=jnp.float32,
                           interpret=True)
    assert np.isfinite(np.asarray(y[:held])).all()
    kept = jnp.where((jnp.arange(m) < held)[:, None], y, 0.0)
    assert np.isfinite(np.asarray(kept)).all()


def test_the_expert_layer_discards_unwritten_rows_by_selection():
    """``DroplessMoE`` with 3 of 4 experts absent: three quarters of the
    sorted assignments lie past the last group, come back NaN from the
    interpreter, and none of it reaches the layer's output."""
    from theanompi_tpu.ops.moe import DroplessMoE

    layer = DroplessMoE(32, 16, 4, K, N, 64, 2.5, (4, 8),
                        products="kernel_interpret")
    p = layer.init(jax.random.PRNGKey(1), (32,))[0]
    u = jax.random.normal(jax.random.PRNGKey(2), (24, 32), jnp.float32)
    got, stats = layer.apply_tokens(p, u)
    want, _ = DroplessMoE(32, 16, 4, K, N, 64, 2.5, (4, 8)).apply_tokens(p, u)
    assert 0 < int(stats["local_hits"]) < 24 * 4
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-5)
    with pytest.raises(ValueError, match="products"):
        DroplessMoE(32, 16, 4, K, N, 64, products="gmm").apply_tokens(p, u)


@pytest.mark.parametrize("k,n,dtype,admitted", [
    (1024, 2688, "bfloat16", True),    # latent -> expert width, as served
    (2688, 1024, "bfloat16", True),    # and back
    (128, 128, "bfloat16", True),
    (1024, 2688, "float32", False),    # only bf16 was compiled and measured
    (64, 128, "bfloat16", False),      # K not a whole number of lanes
    (1024, 2700, "bfloat16", False),   # N neither
    (65536, 128, "bfloat16", False),   # no weight tile fits twice in VMEM
])
def test_shape_gate(k, n, dtype, admitted):
    assert gmm.grouped_matmul_supported(k, n, jnp.dtype(dtype)) is admitted


def test_the_compiled_path_refuses_what_the_gate_refuses():
    rows, w, gs = _operands([3, 5], 16, jnp.float32)
    with pytest.raises(ValueError, match="grouped_matmul_supported"):
        gmm.grouped_matmul(rows, w, gs, tm=8, interpret=False)
    with pytest.raises(ValueError, match="dtype"):
        gmm.grouped_matmul(rows.astype(jnp.bfloat16), w, gs, tm=16,
                           interpret=True)
    with pytest.raises(ValueError, match="do not agree"):
        gmm.grouped_matmul(rows, w, gs[:1], tm=8, interpret=True)


@pytest.mark.parametrize("rows_a_group,m,n_experts", [
    (2.75, 64 * 22, 512),      # the 64-token prefill bucket
    (5.5, 128 * 22, 512),      # decode: 128 slots
    (44, 1024 * 22, 512),
    (176, 4096 * 22, 512),     # the longest prefill bucket
])
def test_the_row_tile_is_128_whatever_a_group_is_expected_to_hold(
        rows_a_group, m, n_experts):
    """ISSUE 28 asked for 16 rows at 2.75 and 5.5 a group up to 128 at 176;
    on the chip 128 was never slower and cut fewer groups in two (the
    kernel's docstring has the readings), so the tile reads nothing from
    ``m / n_experts``."""
    assert m / n_experts == rows_a_group
    assert gmm.row_tile(m, jnp.bfloat16) == gmm.row_tile(m, jnp.float32) == 128


@pytest.mark.parametrize("m,dtype,tile", [
    (24, "bfloat16", 32), (24, "float32", 24), (88, "bfloat16", 96),
    (128, "bfloat16", 128), (129, "float32", 128)])
def test_the_row_tile_holds_no_more_rows_than_there_are(m, dtype, tile):
    assert gmm.row_tile(m, jnp.dtype(dtype)) == tile


def test_the_weight_tile_is_the_widest_that_fits_twice():
    assert gmm._n_tile(1024, 2688, 2) == 2688 and gmm._n_tile(2688, 1024, 2) == 1024
    assert gmm._n_tile(8192, 2688, 2) == 384     # 2688 = 128 x 3 x 7
    assert gmm._n_tile(65536, 128, 2) is None


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


@pytest.mark.parametrize("m,k,n,out", [
    (128 * 22, 1024, 2688, "bfloat16"),    # decode, first product
    (128 * 22, 2688, 1024, "float32"),     # decode, second product
    (4096 * 22, 1024, 2688, "bfloat16"),   # the longest prefill bucket
    (4 * 22, 128, 256, "bfloat16"),        # fewer rows than one tile of 128
    # experts at model width, gate and up in one product (ISSUE 33): 256
    # experts, weight tiles of 4 MB and 2 MB
    (32 * 8, 2048, 1024, "bfloat16"),      # decode, first product
    (32 * 8, 512, 2048, "float32"),        # decode, second product
    (16384 * 8, 2048, 1024, "bfloat16"),   # the longest prefill bucket
])
def test_the_served_widths_compile_for_a_v5e(one_chip, m, k, n, out):
    """What the interpreter cannot show: Mosaic takes the tiles, the
    dynamic number of visits and the VMEM the call asks for."""
    from jax.experimental.compilation_cache import compilation_cache

    e, bf16 = (256 if 2048 in (k, n) else 128), jnp.bfloat16
    tm = gmm.row_tile(m, bf16)

    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(lambda r, w, g: gmm.grouped_matmul(
            r, w, g, tm=tm, out_dtype=jnp.dtype(out), interpret=False)).lower(
                shape((m, k), bf16), shape((e, k, n), bf16),
                shape((e,), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    # the weights go in as they are: nothing the size of the stack is made
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
