"""Native C augmentation helper: bit-exact vs the numpy reference.

The C path is a host-runtime optimization; the numpy per-image loop
remains the source of truth.  Both loader call sites draw the rng BEFORE
choosing a path, so enabling/disabling the native library never changes
training data.
"""

import numpy as np
import pytest

from theanompi_tpu import native


def _numpy_ref(src, out_h, out_w, ys, xs, flips):
    n = src.shape[0]
    res = np.empty((n, out_h, out_w, src.shape[3]), src.dtype)
    for i in range(n):
        img = src[i, ys[i]: ys[i] + out_h, xs[i]: xs[i] + out_w]
        res[i] = img[:, ::-1] if flips[i] else img
    return res


@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
def test_crop_mirror_batch_matches_numpy(dtype):
    if native.lib() is None:
        pytest.skip("no C compiler available")
    rng = np.random.RandomState(0)
    src = (rng.rand(16, 40, 40, 3) * 255).astype(dtype)
    ys = rng.randint(0, 9, 16)
    xs = rng.randint(0, 9, 16)
    flips = rng.rand(16) < 0.5
    got = native.crop_mirror_batch(src, 32, 32, ys, xs, flips)
    assert got is not None
    np.testing.assert_array_equal(got, _numpy_ref(src, 32, 32, ys, xs, flips))


def test_loader_paths_identical_with_and_without_native(monkeypatch):
    """pad_crop_mirror / random_crop_mirror must produce the same batches
    whether or not the native library loads (same rng draw order)."""
    from theanompi_tpu.models.data.cifar10 import pad_crop_mirror
    from theanompi_tpu.models.data.imagenet import random_crop_mirror

    rng = np.random.RandomState(3)
    x32 = rng.rand(8, 32, 32, 3).astype(np.float32)
    x48 = (rng.rand(8, 48, 48, 3) * 255).astype(np.uint8)

    with_native = (pad_crop_mirror(x32, np.random.RandomState(7)),
                   random_crop_mirror(x48, 40, np.random.RandomState(7)))
    monkeypatch.setattr(native, "crop_mirror_batch",
                        lambda *a, **k: None)  # force numpy fallback
    without = (pad_crop_mirror(x32, np.random.RandomState(7)),
               random_crop_mirror(x48, 40, np.random.RandomState(7)))
    for a, b in zip(with_native, without):
        np.testing.assert_array_equal(a, b)


def test_native_build_is_cached(tmp_path):
    if native.lib() is None:
        pytest.skip("no C compiler available")
    import os

    # named after the committed source's hash: a stale binary built from
    # another augment.c can never be the one loaded
    assert os.path.exists(native._so_path())
    assert native.crop_impl() == "c"
    # second call must not rebuild (same handle)
    assert native.lib() is native.lib()
