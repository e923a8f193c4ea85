"""Test bootstrap: fake an 8-chip mesh on host CPU.

The reference could only be tested on a real CUDA+MPI cluster (SURVEY.md §4 —
manual mpirun scripts, no CI).  We instead force 8 virtual CPU devices so
every collective path (psum, ppermute rings, shardings) runs in unit tests
with no TPU attached (the same as running under ``JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8``; force_host_devices
also replaces a device-count flag the environment already carries).
"""


from theanompi_tpu.parallel.mesh import force_host_devices  # noqa: E402

force_host_devices(8)

import jax  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def subproc_compile_cache(tmp_path_factory):
    """Shared persistent compile cache for subprocess-spawning tests
    (resilience e2e, runbook supervision): the first child pays the XLA
    compile, every later child with the same program loads it — resumed
    children included."""
    return str(tmp_path_factory.mktemp("subproc-ccache"))


@pytest.fixture(scope="session", autouse=True)
def _session_compile_cache_env(subproc_compile_cache):
    """Tier-1 velocity (ISSUE 17 satellite): export the session compile
    cache as ``JAX_COMPILATION_CACHE_DIR`` — the one name every entry
    point honours — so every launcher/tmserve subprocess shares the one
    warm XLA cache.  In-process ``launcher.main([...])`` / ``serve()``
    calls are untouched: jax read its configuration when this process
    imported it, before the variable was set, and ``setup_compile_cache``
    sets no directory where the variable is present — so the test process
    itself runs without a persistent cache (and never writes the
    checkout's ``.jax_cache``)."""
    import os

    prev = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = subproc_compile_cache
    yield
    if prev is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = prev


@pytest.fixture(scope="session")
def mesh8():
    from theanompi_tpu.parallel.mesh import make_mesh

    return make_mesh(n_data=8)


@pytest.fixture(scope="session")
def mesh4():
    """4 data-parallel devices — the ISSUE 2 acceptance mesh; ring-strategy
    compiles unroll 2(n-1) hops, so exchange tests that don't need 8 workers
    run here at less than half the XLA compile cost."""
    from theanompi_tpu.parallel.mesh import make_mesh

    return make_mesh(n_data=4, devices=jax.devices()[:4])


@pytest.fixture(scope="session")
def mesh4x2():
    from theanompi_tpu.parallel.mesh import make_mesh

    return make_mesh(n_data=4, n_model=2)


# -- trained-model session fixtures (ISSUE 11 satellite) ----------------------
# Several files used to train their own tiny model per module; at session
# scope the training cost is paid once for the whole tier-1 run.

#: the serving test config (test_serving imports this as its TINY — one
#: source of truth, so the fixture and the per-test references can't drift)
SERVING_TINY = {
    "batch_size": 2, "n_train": 64, "n_val": 32, "seq_len": 32,
    "vocab": 61, "dim": 32, "heads": 2, "n_layers": 2,
    "dropout": 0.0, "n_epochs": 1, "precision": "fp32",
}


@pytest.fixture(scope="session")
def dense_model():
    """A tiny TransformerLM lightly trained on the synthetic bigram stream
    (40 plain-SGD steps, one jit) — serving tests run against weights with
    real structure: at random init the logits are near-tied and int8
    argmax agreement measures coin flips, not quantization quality.
    Session-scoped and treated as READ-ONLY by every consumer."""
    from theanompi_tpu.models.transformer_lm import TransformerLM

    model = TransformerLM(dict(SERVING_TINY))
    params, state = model.init_params(jax.random.PRNGKey(0))
    batches = list(model.data.train_batches(8, 0, seed=0))

    @jax.jit
    def step(p, batch):
        g = jax.grad(
            lambda p: model.loss_fn(p, state, batch, None, False)[0])(p)
        return jax.tree.map(lambda a, b: a - 0.5 * b, p, g)

    for i in range(40):
        params = step(params, batches[i % len(batches)])
    return model, params, state


@pytest.fixture(scope="session")
def serving_engine_factory(dense_model):
    """Memoizing InferenceEngine factory over the session ``dense_model``
    (ISSUE 18 satellite — tier-1 velocity): engines are keyed on their
    construction kwargs, so every test asking for the same configuration
    shares ONE engine and its compiled decode/prefill programs for the
    whole tier-1 run.  Defaults are the canonical serving geometry
    (``block_size=4, max_batch=2, seed=0``).

    Shared engines are READ-ONLY above the pools: tests may prefill /
    decode through them freely (pool contents are scratch — the position
    masks make stale blocks invisible, the same property eviction relies
    on) but must NOT ``swap_params`` or monkeypatch them.  Tests that
    mutate weights (the rollout suite) pass ``shared=False`` for a
    private engine with the same canonical construction."""
    from theanompi_tpu.serving.engine import InferenceEngine

    model, params, _state = dense_model
    cache: dict = {}

    def make(shared=True, **kw):
        kw.setdefault("block_size", 4)
        kw.setdefault("max_batch", 2)
        kw.setdefault("seed", 0)
        if not shared:
            return InferenceEngine(model, params, **kw)
        key = tuple(sorted(kw.items()))
        if key not in cache:
            cache[key] = InferenceEngine(model, params, **kw)
        return cache[key]

    return make


@pytest.fixture(scope="session")
def serving_engine(serving_engine_factory):
    """The canonical shared serving engine (see
    :func:`serving_engine_factory` for the READ-ONLY contract)."""
    return serving_engine_factory()


#: the checkpoint-integrity trainer config (test_checkpoint_integrity
#: imports this as its TINY — same one-source-of-truth contract)
WRN_TINY = {"depth": 10, "widen": 1, "batch_size": 8, "image_size": 8,
            "n_train": 32, "n_val": 16, "n_epochs": 2, "precision": "fp32",
            "augment": False, "verbose": False, "lr": 0.05}


def make_wrn_trainer(mesh, checkpoint_dir, n_epochs=2, **kw):
    """A compiled, initialized tiny-WRN BSP trainer over ``mesh`` — the
    shared builder behind :func:`trained_wrn_ckpt` and the checkpoint
    tests' resuming trainers (identical construction => identical resume
    fingerprint)."""
    from theanompi_tpu.models.wide_resnet import WideResNet
    from theanompi_tpu.parallel.bsp import BSPTrainer
    from theanompi_tpu.utils.recorder import Recorder

    t = BSPTrainer(
        WideResNet({**WRN_TINY, "n_epochs": n_epochs}), mesh=mesh,
        exch_strategy="psum",
        recorder=Recorder(verbose=False, print_freq=4),
        checkpoint_dir=checkpoint_dir, **kw,
    )
    t.compile_iter_fns()
    t.init_state()
    return t


# -- exchange strategy-equivalence runs (ISSUE 12 satellite) ------------------

#: the exchange-equivalence trainer config (test_exchanger / test_overlap
#: build their shared runs from this — one source of truth)
EXCHANGE_TINY = {
    "depth": 10, "widen": 1, "batch_size": 2, "image_size": 8,
    "n_train": 32, "n_val": 16, "n_epochs": 1, "precision": "fp32",
    "augment": False, "verbose": False,
}


@pytest.fixture(scope="session")
def exchange_run():
    """Memoized two-step tiny-WRN training runs keyed by exchange config.

    ``run(mesh, strategy, bucket_mb=4.0, overlap=False)`` ->
    ``(trainer, params_as_numpy)``.  The strategy-equivalence matrix in
    test_exchanger.py and the fused-vs-overlapped bit-equality locks in
    test_overlap.py both compare runs against shared baselines; memoizing
    at session scope trains each distinct configuration exactly once for
    the whole tier-1 run (ROADMAP item 4 — the XLA compiles dominate).
    Consumers treat the trainer AND the params as READ-ONLY.
    """
    import numpy as np

    cache: dict = {}

    def run(mesh, strategy, bucket_mb=4.0, overlap=False):
        key = (id(mesh), strategy, float(bucket_mb), bool(overlap))
        if key not in cache:
            from theanompi_tpu.models.wide_resnet import WideResNet
            from theanompi_tpu.parallel.bsp import BSPTrainer
            from theanompi_tpu.utils.recorder import Recorder

            model = WideResNet(dict(EXCHANGE_TINY))
            t = BSPTrainer(model, mesh=mesh, exch_strategy=strategy,
                           exch_bucket_mb=bucket_mb, exch_overlap=overlap,
                           recorder=Recorder(verbose=False,
                                             print_freq=10**9))
            t.compile_iter_fns()
            t.init_state()
            for batch in list(model.data.train_batches(
                    t.global_batch, 0, seed=0))[:2]:
                t.train_iter(batch, lr=0.05)
            cache[key] = (t, jax.tree.map(np.asarray, t.params))
        return cache[key]

    return run


@pytest.fixture(scope="session")
def trained_wrn_ckpt(tmp_path_factory, mesh4):
    """A completed 2-epoch tiny-WRN training run's checkpoint directory
    (epochs 0 and 1 published, clean-shutdown handshake done).  Tests
    that corrupt or resume MUST ``shutil.copytree`` it into their own
    tmp_path first — the session copy is read-only."""
    d = str(tmp_path_factory.mktemp("wrn-trained") / "ck")
    t = make_wrn_trainer(mesh4, d)
    t.run()
    assert not t.checkpointer.was_unclean()
    return d
