"""Device mesh runtime: discovery, mesh construction, precision policy, RNG.

This is the TPU-native analogue of the reference's process bootstrap
(reference, unverified — SURVEY.md §2.1: ``theanompi/lib/base.py`` class
``MPI_GPU_process``: binds one GPU per OS process via ``theano.gpuarray``,
builds an ``MPI.COMM_WORLD`` plus an intra-node NCCL clique).  On TPU there is
no per-device process and no explicit communicator object: a single controller
builds a :class:`jax.sharding.Mesh` over the chips, and XLA lowers collective
ops over its named axes to ICI/DCN traffic.  "Binding a device" becomes
"naming a mesh axis"; the NCCL clique becomes the mesh itself.

Axes convention:

- ``data``  — data parallelism (the reference's only parallelism; one worker
  per reference GPU maps to one slice along this axis),
- ``pipe``  — pipeline parallelism over stacked homogeneous blocks
  (see :mod:`theanompi_tpu.parallel.pipeline`),
- ``model`` — tensor parallelism (beyond reference capability, here from day
  one so shardings compose),
- ``seq``   — sequence/context parallelism for ring attention
  (see :mod:`theanompi_tpu.parallel.ring_attention`).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
PIPE_AXIS = "pipe"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"

def force_host_devices(n: int) -> None:
    """Force ``n`` virtual CPU devices.  Must run before the first backend init.

    The test-suite analogue of the reference's multi-GPU cluster: the reference
    could only be tested on a real CUDA+MPI cluster (SURVEY.md §4); we fake an
    ``n``-chip mesh on host CPU so every collective path is unit-testable.

    From a fresh shell the plain environment does the same
    (``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=N``);
    this is for callers that have already imported jax (this module does),
    where the platform must go through ``jax.config``.  An existing
    device-count flag is replaced, not silently kept.
    """
    import re

    flags = os.environ.get("XLA_FLAGS", "")
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags)
    os.environ["XLA_FLAGS"] = (
        flags.strip() + f" --xla_force_host_platform_device_count={n}"
    ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")


#: the checkout's own compile cache (git-ignored), used when the
#: environment names none.  Fixed on purpose: the path is part of jax's
#: cache key, so a temp/pid/timestamp directory would never hit.
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def setup_compile_cache() -> str:
    """Place JAX's persistent compilation cache; -> its directory.

    One rule for every entry point (launcher, tmserve, chip_smoke):
    where ``JAX_COMPILATION_CACHE_DIR`` is set, jax has already read it and
    this sets no other directory; where it is not, the cache lives in
    :data:`DEFAULT_COMPILE_CACHE`.  Call before the first compile — jax
    decides once per process whether a cache is in use.

    On an accelerator every program is cached, however small: a restart,
    resume or sweep child then compiles nothing it has compiled before,
    which :class:`CompileStats` makes checkable (requests == hits).  The
    CPU backend keeps jax's own thresholds (programs that took >= 1 s):
    XLA:CPU writes a ~3 KB error line per executable it loads from a cache
    ("machine type ... doesn't match"), which at one per helper jit floods
    stderr — enough to fill an unread pipe and block the process.
    """
    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not directory:
        directory = DEFAULT_COMPILE_CACHE
        os.makedirs(directory, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", directory)
    if jax.default_backend() != "cpu":
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return directory


class CompileStats:
    """Counts this process's compiles through :mod:`jax.monitoring`.

    ``requests`` are compilations that consulted the persistent cache,
    ``hits`` those it served; ``requests - hits`` programs were compiled
    anew.  ``compile_s`` is the time spent compiling or loading them.
    Entry points print :meth:`line` so a run states its own set-up cost
    and whether a second run on the same cache compiled anything.
    """

    def __init__(self):
        self.requests = self.hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def close(self) -> None:
        """Stop counting (jax keeps listeners for the life of the
        process; an in-process caller — a test — unregisters its own)."""
        jax.monitoring.unregister_event_listener(self._on_event)
        jax.monitoring.unregister_event_duration_listener(self._on_duration)

    def as_dict(self) -> dict:
        return {"requests": self.requests, "hits": self.hits,
                "compiled": self.requests - self.hits,
                "compile_s": round(self.compile_s, 2)}

    def line(self) -> str:
        return " ".join(f"{k}={v}" for k, v in self.as_dict().items())


def device_summary() -> dict:
    """``{"platform", "kind", "count"}`` as jax reports the backend —
    every entry point prints it, so a run that fell back to the CPU says
    so in its own output."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def shard_report(tree) -> dict:
    """Which devices hold shards of ``tree`` and what each has allocated:
    ``{"devices": n, "bytes_in_use": [...]}`` (None per device where the
    backend keeps no memory statistics — the CPU)."""
    holders = {s.device for leaf in jax.tree.leaves(tree)
               for s in getattr(leaf, "addressable_shards", ())}
    return {"devices": len(holders),
            "bytes_in_use": [(d.memory_stats() or {}).get("bytes_in_use")
                             for d in sorted(holders, key=lambda d: d.id)]}


def make_mesh(
    n_data: int | None = None,
    n_model: int = 1,
    n_seq: int = 1,
    n_pipe: int = 1,
    devices: Sequence[Any] | None = None,
) -> Mesh:
    """Build a ``(data, pipe, model, seq)`` mesh over the available devices.

    ``n_data=None`` consumes all devices left over after the other axes.
    A mesh of total size 1 is valid and is the single-worker ("CPU Theano
    mode", BASELINE.md config 1) case.
    """
    if devices is None:
        devices = jax.devices()
    total = len(devices)
    rest = n_model * n_seq * n_pipe
    if n_data is None:
        if total % rest != 0:
            raise ValueError(
                f"{total} devices not divisible by pipe*model*seq={rest}"
            )
        n_data = total // rest
    need = n_data * rest
    if need > total:
        raise ValueError(f"need {need} devices, have {total}")
    arr = np.asarray(devices[:need], dtype=object).reshape(
        n_data, n_pipe, n_model, n_seq
    )
    return Mesh(arr, (DATA_AXIS, PIPE_AXIS, MODEL_AXIS, SEQ_AXIS))


def mesh_axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis]


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def data_sharded(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    """Sharding that splits the leading (batch) dim over the ``data`` axis."""
    return NamedSharding(mesh, P(DATA_AXIS, *([None] * (ndim - 1))))


@dataclasses.dataclass(frozen=True)
class Precision:
    """Mixed-precision policy: fp32 params, bf16 compute, fp32 outputs.

    The reference's analogue is its fp16 exchange strategies (``asa16``,
    ``nccl16`` — SURVEY.md §2.1, exchanger strategies) plus Theano's
    ``floatX``.  On TPU the MXU natively consumes bf16, so compute-in-bf16 is
    the default rather than a compression trick; the exchange-compression
    analogue lives in :mod:`theanompi_tpu.parallel.exchanger` (``psum_bf16``).
    """

    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    output_dtype: Any = jnp.float32

    def cast_to_compute(self, tree, is_leaf=None):
        """``is_leaf`` lets callers fence off opaque pytree nodes the
        policy must pass through whole — the serving fast path's
        ``QuantizedTensor`` leaves carry fp32 scales that must NOT cast
        to bf16 (this layer stays import-free, so the fence is generic)."""
        return jax.tree.map(self._cast(self.compute_dtype), tree,
                            is_leaf=is_leaf)

    def cast_to_param(self, tree):
        return jax.tree.map(self._cast(self.param_dtype), tree)

    def cast_to_output(self, tree):
        return jax.tree.map(self._cast(self.output_dtype), tree)

    @staticmethod
    def _cast(dtype):
        def cast(x):
            # result_type (not isinstance) so numpy arrays and Python floats
            # in a host-initialized params pytree are cast too, instead of
            # silently passing through the policy.  Leaves with no array
            # interpretation (an is_leaf-fenced QuantizedTensor) pass
            # through untouched.
            try:
                if jnp.issubdtype(jnp.result_type(x), jnp.floating):
                    return jnp.asarray(x, dtype)
            except TypeError:  # lint: swallow-ok — non-array leaf (QuantizedTensor), policy passes it through
                pass
            return x

        return cast


#: Full precision everywhere — for CPU tests and numerical-parity checks.
FP32 = Precision(compute_dtype=jnp.float32)
#: TPU default.
BF16 = Precision()


def shard_map(f, mesh: Mesh, in_specs, out_specs, check: bool = False):
    """Thin wrapper over :func:`jax.shard_map` pinning this repo's defaults.

    ``check=False`` disables varying-manual-axes checking: the ring strategies
    (:mod:`theanompi_tpu.parallel.exchanger`) produce replicated outputs via
    ``ppermute`` chains the checker cannot prove replicated.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def replica_rng(key: jax.Array, axis_name=DATA_AXIS) -> jax.Array:
    """Derive a distinct PRNG key per replica along one or more mesh axes.

    Call only inside ``shard_map``/collective context.  Replaces the
    reference's per-process numpy seeding (each MPI rank seeded separately;
    SURVEY.md §2.1 base.py) with a deterministic fold of the replica index.
    Pass a tuple (e.g. ``("data", "seq")``) when activations are sharded over
    several axes and per-shard randomness (dropout) must differ on each.
    """
    axes = axis_name if isinstance(axis_name, tuple) else (axis_name,)
    for a in axes:
        key = jax.random.fold_in(key, jax.lax.axis_index(a))
    return key
