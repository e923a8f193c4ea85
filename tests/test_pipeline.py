"""Pipeline parallelism: the GPipe collective-permute schedule over 'pipe'.

The invariant: a dp2 x pp4 pipelined run must track the single-device run
of the SAME stacked model through multiple train steps (forward AND the
cross-pipe gradient path — embeddings/head cotangents exist only on the
injection/collection stages and must be psum-repaired, exactly the bug
class ADVICE.md round 1 found for tensor parallelism).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from theanompi_tpu.models.transformer_lm import PipelineTransformerLM
from theanompi_tpu.parallel.bsp import BSPTrainer
from theanompi_tpu.parallel.mesh import make_mesh

CFG = {"batch_size": 8, "n_train": 64, "n_val": 32, "seq_len": 16,
       "vocab": 32, "dim": 32, "heads": 4, "n_layers": 4, "dropout": 0.0,
       "n_micro": 4,
       "l2": 1e-4, "n_epochs": 1, "precision": "fp32"}


def _run_steps(mesh, cfg, steps=3):
    model = PipelineTransformerLM(cfg)
    t = BSPTrainer(model, mesh=mesh)
    t.compile_iter_fns()
    t.init_state()
    batches = list(model.data.train_batches(t.global_batch, 0, seed=0))
    costs = [
        float(t.train_iter(batches[i % len(batches)], lr=1e-2)["cost"])
        for i in range(steps)
    ]
    return t, costs


def test_pipeline_params_are_stacked_and_sharded():
    model = PipelineTransformerLM(dict(CFG))
    params, _ = model.init_params(jax.random.PRNGKey(0))
    for leaf in jax.tree.leaves(params["blocks"]):
        assert leaf.shape[0] == CFG["n_layers"]
    specs = model.param_specs(params)
    from jax.sharding import PartitionSpec as P

    # every stacked leaf leads with pipe; TP-ruled leaves keep their
    # Megatron spec behind it (dp x pp x tp composition)
    for s in jax.tree.leaves(specs["blocks"], is_leaf=lambda x: isinstance(x, P)):
        assert s[0] == "pipe"
    assert specs["blocks"]["attn"]["q"]["w"] == P("pipe", None, "model")
    assert specs["blocks"]["attn"]["o"]["w"] == P("pipe", "model", None)
    assert specs["blocks"]["up"]["w"] == P("pipe", None, "model")
    assert specs["blocks"]["ln1"]["scale"] == P("pipe")
    assert all(s == P() for s in jax.tree.leaves(specs["head"]))


def test_pp4_matches_single_device():
    """dp2 x pp4 must track the unsharded model through 3 train steps.

    Per-batch costs see the forward; steps 2-3 see the updated params, so a
    wrong cross-pipe gradient (embed/head psum, stage routing) shows up as
    loss divergence."""
    mesh1 = make_mesh(n_data=1, devices=jax.devices()[:1])
    t1, c1 = _run_steps(mesh1, dict(CFG))

    mesh_pp = make_mesh(n_data=2, n_pipe=4)
    # same GLOBAL batch: data axis splits it in two
    cfg = {**CFG, "batch_size": CFG["batch_size"] // 2}
    t2, c2 = _run_steps(mesh_pp, cfg)

    np.testing.assert_allclose(c1, c2, rtol=2e-4, atol=2e-5)
    # a replicated (head) leaf must end identical too
    a = np.asarray(jax.tree.leaves(t1.params["head"])[0])
    b = np.asarray(jax.tree.leaves(t2.params["head"])[0])
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_pp8_trains_and_validates():
    """All 8 devices as pipeline stages (dp=1, pp=8): runs + learns-ish."""
    mesh = make_mesh(n_data=1, n_pipe=8)
    cfg = {**CFG, "n_layers": 8, "n_epochs": 2}
    model = PipelineTransformerLM(cfg)
    t = BSPTrainer(model, mesh=mesh)
    rec = t.run()
    costs = rec.val_history["cost"]
    assert len(costs) == 2 and all(np.isfinite(costs)), costs


def test_pp2_tp2_matches_single_device():
    """dp1 x pp2 x tp2: the full composition must track the unsharded model
    through 3 train steps (VERDICT r2 #4).  Steps 2-3 run on updated
    params, so any mis-composed collective (double-counted TP psum under
    the pipe schedule, missing pipe-pin on a replicated leaf) diverges."""
    mesh1 = make_mesh(n_data=1, devices=jax.devices()[:1])
    t1, c1 = _run_steps(mesh1, dict(CFG))

    mesh = make_mesh(n_data=1, n_pipe=2, n_model=2, devices=jax.devices()[:4])
    t2, c2 = _run_steps(mesh, dict(CFG))
    np.testing.assert_allclose(c1, c2, rtol=2e-4, atol=2e-5)
    a = np.asarray(jax.tree.leaves(t1.params["head"])[0])
    b = np.asarray(jax.tree.leaves(t2.params["head"])[0])
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
    # a TP'd stacked weight is actually SHARDED (device_set size alone is
    # vacuous: replicated arrays also span all devices)
    qw = t2.params["blocks"]["attn"]["q"]["w"]
    assert not qw.sharding.is_fully_replicated


def test_dp2_pp2_tp2_trains():
    """All three axes at once on the 8-device mesh: finite loss, val runs."""
    mesh = make_mesh(n_data=2, n_pipe=2, n_model=2)
    model = PipelineTransformerLM({**CFG, "n_epochs": 1})
    t = BSPTrainer(model, mesh=mesh)
    rec = t.run()
    costs = rec.val_history["cost"]
    assert len(costs) == 1 and all(np.isfinite(costs)), costs


def test_pp2_sp2_matches_single_device():
    """dp1 x pp2 x sp2: ring attention's KV laps inside the GPipe schedule
    (VERDICT r3 #5 — the last refusal on the parallelism surface).  Multi-
    step equivalence: steps 2-3 run on updated params, so a wrong hop
    order / mis-pinned cotangent on either ring diverges the loss."""
    mesh1 = make_mesh(n_data=1, devices=jax.devices()[:1])
    t1, c1 = _run_steps(mesh1, dict(CFG))

    cfg = {**CFG, "seq_parallel": True}
    mesh = make_mesh(n_data=1, n_pipe=2, n_seq=2, devices=jax.devices()[:4])
    t2, c2 = _run_steps(mesh, cfg)
    np.testing.assert_allclose(c1, c2, rtol=2e-4, atol=2e-5)
    a = np.asarray(jax.tree.leaves(t1.params["head"])[0])
    b = np.asarray(jax.tree.leaves(t2.params["head"])[0])
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_dp2_pp2_sp2_trains():
    """All of data x pipe x seq on the 8-device mesh: finite loss, val runs."""
    mesh = make_mesh(n_data=2, n_pipe=2, n_seq=2)
    model = PipelineTransformerLM(
        {**CFG, "seq_parallel": True, "n_epochs": 1})
    t = BSPTrainer(model, mesh=mesh)
    rec = t.run()
    costs = rec.val_history["cost"]
    assert len(costs) == 1 and all(np.isfinite(costs)), costs


def test_pipeline_rejects_indivisible_microbatch():
    from theanompi_tpu.parallel.mesh import shard_map
    from theanompi_tpu.parallel.pipeline import pipeline_apply
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(n_data=1, n_pipe=8)

    def f(x):
        return pipeline_apply(lambda p, a, t: a, None, x, n_micro=3)

    with pytest.raises(ValueError, match="not divisible"):
        jax.jit(shard_map(f, mesh, in_specs=P(), out_specs=P()))(
            jnp.ones((8, 4))
        )


def test_pp2_tp2_with_fused_vocab_parallel_loss():
    """The full stack at once: GPipe over `pipe`, Megatron splits + the
    vocab-parallel fused loss over `model` — must track the single-device
    fused run through 3 steps, with the head actually vocab-sharded."""
    cfg = {**CFG, "fused_loss": True}
    mesh1 = make_mesh(n_data=1, devices=jax.devices()[:1])
    t1, c1 = _run_steps(mesh1, dict(cfg))

    mesh = make_mesh(n_data=1, n_pipe=2, n_model=2, devices=jax.devices()[:4])
    t2, c2 = _run_steps(mesh, dict(cfg))
    np.testing.assert_allclose(c1, c2, rtol=2e-4, atol=2e-5)
    hw = t2.params["head"]["w"]
    assert not hw.sharding.is_fully_replicated  # vocab actually sharded
    np.testing.assert_allclose(
        np.asarray(t1.params["head"]["w"]), np.asarray(hw),
        rtol=2e-4, atol=2e-5,
    )


def test_scan_unroll_matches_rolled():
    """layers_unroll/loss_unroll are scheduling hints: multi-step training
    must track the rolled (unroll=1) run on identical inits (r5 knobs for
    the scans' while-self-time share).

    loss_unroll is exercised on the base TransformerLM (its only scans are
    the fused-loss chunk scans); layers_unroll on PipelineTransformerLM —
    the ONLY model with a stacked-layer scan (the base trunk is a
    Python-loop Sequential, where the knob is inert by design).
    """
    from theanompi_tpu.models.transformer_lm import TransformerLM

    mesh = make_mesh(n_data=1, devices=jax.devices()[:1])
    base = {"batch_size": 4, "n_train": 32, "n_val": 16, "seq_len": 16,
            "vocab": 4096, "dim": 32, "heads": 4, "n_layers": 4,
            "dropout": 0.0, "n_epochs": 1, "precision": "fp32",
            "fused_loss": True}

    def run(model_cls, extra):
        model = model_cls({**base, **extra})
        t = BSPTrainer(model, mesh=mesh)
        t.compile_iter_fns()
        t.init_state()
        batches = list(model.data.train_batches(t.global_batch, 0, seed=0))
        return [
            float(t.train_iter(batches[i % len(batches)], lr=1e-2)["cost"])
            for i in range(3)
        ]

    rolled = run(TransformerLM, {})
    unrolled = run(TransformerLM, {"loss_unroll": 2})
    np.testing.assert_allclose(unrolled, rolled, rtol=1e-5)

    pp_cfg = {"n_micro": 2}
    pp_rolled = run(PipelineTransformerLM, pp_cfg)
    pp_unrolled = run(PipelineTransformerLM,
                      {**pp_cfg, "layers_unroll": 4, "loss_unroll": 2})
    np.testing.assert_allclose(pp_unrolled, pp_rolled, rtol=1e-5)
