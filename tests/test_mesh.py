"""Mesh runtime tests (fake 8-device CPU mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.parallel.mesh import (
    BF16,
    DATA_AXIS,
    FP32,
    Precision,
    data_sharded,
    make_mesh,
    mesh_axis_size,
    replica_rng,
    replicated,
)


def test_device_count():
    assert jax.device_count() == 8


def test_make_mesh_shapes():
    m = make_mesh(n_data=8)
    assert mesh_axis_size(m, DATA_AXIS) == 8
    m = make_mesh(n_data=4, n_model=2)
    assert m.shape["data"] == 4 and m.shape["model"] == 2
    m = make_mesh()  # auto: all devices on data
    assert m.shape["data"] == 8


def test_make_mesh_errors():
    with pytest.raises(ValueError):
        make_mesh(n_data=16)
    with pytest.raises(ValueError):
        make_mesh(n_model=3)  # 8 % 3 != 0


def test_single_device_mesh():
    m = make_mesh(n_data=1, devices=jax.devices()[:1])
    assert m.shape["data"] == 1


def test_data_sharding_placement(mesh8):
    x = jnp.arange(16.0).reshape(16, 1)
    xs = jax.device_put(x, data_sharded(mesh8, ndim=2))
    assert len(xs.sharding.device_set) == 8
    np.testing.assert_array_equal(np.asarray(xs), np.asarray(x))
    w = jax.device_put(jnp.ones((4,)), replicated(mesh8))
    assert w.sharding.is_fully_replicated


def test_precision_policy_casts():
    tree = {"w": jnp.ones((2,), jnp.float32), "i": jnp.ones((2,), jnp.int32)}
    c = BF16.cast_to_compute(tree)
    assert c["w"].dtype == jnp.bfloat16
    assert c["i"].dtype == jnp.int32  # non-float leaves untouched
    back = BF16.cast_to_param(c)
    assert back["w"].dtype == jnp.float32
    assert FP32.compute_dtype == jnp.float32
    assert Precision(compute_dtype=jnp.float16).compute_dtype == jnp.float16


def test_replica_rng_distinct(mesh8):
    from theanompi_tpu.parallel.mesh import shard_map
    from jax.sharding import PartitionSpec as P

    def f(key):
        k = replica_rng(key[0])
        return jax.random.uniform(k, (1,))

    out = shard_map(
        f, mesh=mesh8, in_specs=P(DATA_AXIS), out_specs=P(DATA_AXIS),
        check=False,
    )(jnp.stack([jax.random.PRNGKey(0)] * 8))
    vals = np.asarray(out)
    assert len(np.unique(vals)) == 8  # every replica drew a different number


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(tmp_path):
    """ISSUE 21 (e): where JAX_COMPILATION_CACHE_DIR is set the cache is
    there and the code sets no other directory; unset, it is the one fixed
    git-ignored path in the checkout.  In children: the policy flips
    process-wide jax config."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("import jax\n"
            "from theanompi_tpu.parallel.mesh import (DEFAULT_COMPILE_CACHE,"
            " setup_compile_cache)\n"
            "print(setup_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "print(DEFAULT_COMPILE_CACHE)\n")

    def run(**env_over):
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env.update(PYTHONPATH=repo, JAX_PLATFORMS="cpu", **env_over)
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             cwd=str(tmp_path), capture_output=True,
                             text=True, timeout=120, check=True)
        return out.stdout.split()

    outside = str(tmp_path / "cc")
    assert run(JAX_COMPILATION_CACHE_DIR=outside)[:2] == [outside, outside]
    used, configured, default = run()
    assert used == configured == default == os.path.join(repo, ".jax_cache")
    ignored = open(os.path.join(repo, ".gitignore")).read().split()
    assert ".jax_cache/" in ignored
