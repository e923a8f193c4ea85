"""Async rules under the full resilience stack (ISSUE 20): the stacked
reshard planner, the rule-typed fingerprint contract, the async_staleness
health detector, the straggler/gossip-drop fault sites, wire-byte
exactness against the ISSUE 2 per-dtype contract, and the resume matrix
(verified-chain round-trip, cadence mid-epoch crash, elastic mesh8->4).

Planner and detector units run on handcrafted manifests / synthetic
events (milliseconds); the training matrix reuses the tiny wide_resnet
config every resilience e2e shares so subprocess children hit one
compile-cache entry.
"""

import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from theanompi_tpu import EASGD, GOSGD
from theanompi_tpu.resilience import FaultInjected, FaultPlan
from theanompi_tpu.resilience.faults import SITES, FaultPlanError
from theanompi_tpu.telemetry.health import (
    SEV_CRITICAL,
    SEV_OK,
    SEV_WARN,
    HealthConfig,
    HealthMonitor,
)
from theanompi_tpu.telemetry.metrics import (
    ASYNC_GAUGES,
    ASYNC_INSTANTS,
    EXCHANGE_COUNTS,
)
from theanompi_tpu.utils import checkpoint as ck_mod
from theanompi_tpu.utils.checkpoint import (
    Checkpointer,
    CheckpointReshardableMismatch,
    CheckpointReshardError,
    build_manifest,
    plan_reshard,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_CFG = {"depth": 10, "widen": 1, "batch_size": 4, "image_size": 8,
            "n_train": 32, "n_val": 16, "n_epochs": 2, "precision": "fp32"}
TINY_ARGS = ["--set", "depth=10", "--set", "widen=1", "--set", "batch_size=4",
             "--set", "image_size=8", "--set", "n_train=32",
             "--set", "n_val=16", "--set", "precision='fp32'"]


# -- planner units (handcrafted manifests, no training) ----------------------

def _fp(n, exchange, **over):
    fp = {"mesh": {"data": n, "pipe": 1, "model": 1, "seq": 1},
          "exchange": exchange, "n_subb": 1,
          "model": "WideResNet", "model_config_sha": "abc123"}
    fp.update(over)
    return fp


def _easgd_fp(n, **over):
    return _fp(n, "EASGDTrainer", rule="easgd", tau=2, alpha="auto", **over)


def _gosgd_fp(n, **over):
    return _fp(n, "GOSGDTrainer", rule="gosgd", p_push="auto", **over)


def _stacked_flat(n):
    """Stacked per-worker trees with recognizable per-replica payloads."""
    return {
        "params::conv/w": np.arange(n * 3, dtype=np.float32).reshape(n, 3),
        "state::bn/mean": np.arange(n * 2, dtype=np.float32).reshape(n, 2),
        "opt_state::velocity/conv/w":
            np.arange(n * 3, dtype=np.float32).reshape(n, 3) * 10.0,
    }


def _easgd_manifest(n=8, **fpover):
    flat = dict(_stacked_flat(n))
    flat["center::conv/w"] = np.array([7.0, 8.0, 9.0], np.float32)
    return build_manifest(1, 4, flat, _easgd_fp(n, **fpover)), flat


def _gosgd_manifest(n=8, weights=None, **fpover):
    flat = dict(_stacked_flat(n))
    flat["weights::"] = (np.full((n,), 1.0 / n, np.float32)
                         if weights is None else np.asarray(weights))
    return build_manifest(1, 4, flat, _gosgd_fp(n, **fpover)), flat


def test_plan_easgd_shrink_keeps_first_center_passthrough():
    man, flat = _easgd_manifest(8)
    plan = plan_reshard(man, _easgd_fp(4))
    assert (plan.old_n, plan.new_n) == (8, 4)
    assert plan.stacked == "easgd" and plan.buckets is None
    assert plan.lr_scale == pytest.approx(1.0)  # carried, NOT rescaled
    assert plan.summary()["stacked"] == "easgd"
    assert "keep the first 4" in plan.describe()
    out = plan.transform_arrays(flat)
    for key in ("params::conv/w", "state::bn/mean",
                "opt_state::velocity/conv/w"):
        np.testing.assert_array_equal(out[key], flat[key][:4], err_msg=key)
    # the center is replicated and n-independent: restored untouched
    np.testing.assert_array_equal(out["center::conv/w"],
                                  flat["center::conv/w"])
    assert any("LR carried unrescaled" in w for w in plan.warnings)


def test_plan_easgd_grow_clones_cyclically():
    man, flat = _easgd_manifest(4)
    plan = plan_reshard(man, _easgd_fp(8))
    out = plan.transform_arrays(flat)
    idx = np.arange(8) % 4
    np.testing.assert_array_equal(out["params::conv/w"],
                                  flat["params::conv/w"][idx])
    np.testing.assert_array_equal(out["opt_state::velocity/conv/w"],
                                  flat["opt_state::velocity/conv/w"][idx])
    assert any("cloned" in w for w in plan.warnings)


def test_plan_gosgd_weights_renormalized():
    w8 = np.array([.30, .20, .10, .10, .10, .10, .05, .05], np.float32)
    man, flat = _gosgd_manifest(8, weights=w8)
    plan = plan_reshard(man, _gosgd_fp(4))
    assert plan.stacked == "gosgd"
    out = plan.transform_arrays(flat)
    got = out["weights::"]
    assert got.shape == (4,) and got.dtype == np.float32
    np.testing.assert_allclose(got, w8[:4] / w8[:4].sum(), rtol=1e-6)
    assert got.sum() == pytest.approx(1.0, abs=1e-6)  # conservation
    # grow direction: cyclic index map then renormalize
    man4, flat4 = _gosgd_manifest(4, weights=np.array([.4, .3, .2, .1],
                                                      np.float32))
    up = plan_reshard(man4, _gosgd_fp(6))
    w = up.transform_arrays(flat4)["weights::"]
    ref = np.array([.4, .3, .2, .1, .4, .3])
    np.testing.assert_allclose(w, ref / ref.sum(), rtol=1e-6)


def test_plan_async_same_n_is_identity():
    man, flat = _easgd_manifest(8)
    plan = plan_reshard(man, _easgd_fp(8))
    assert plan.stacked == "easgd"
    assert plan.transform_arrays(flat) is flat  # identity, no copy


def test_plan_async_lr_scale_carries_composed_factor():
    """A lineage that picked up x0.5 as BSP before converting would carry
    it; the async plan composes the carried factor and never applies the
    linear-scaling rule on top."""
    flat = dict(_stacked_flat(8))
    flat["center::conv/w"] = np.zeros((3,), np.float32)
    man = build_manifest(1, 4, flat, _easgd_fp(8), lr_scale=0.5)
    plan = plan_reshard(man, _easgd_fp(4))
    assert plan.lr_scale == pytest.approx(0.5)


def test_plan_async_refusals():
    # rule tag promises "center" but the checkpoint doesn't carry it
    man = build_manifest(1, 4, _stacked_flat(8), _easgd_fp(8))
    with pytest.raises(CheckpointReshardError, match="promises the extra"):
        plan_reshard(man, _easgd_fp(4))
    # extras without a recognized rule tag stay a refusal (unknown layout)
    flat = dict(_stacked_flat(8))
    flat["center::conv/w"] = np.zeros((3,), np.float32)
    man = build_manifest(1, 4, flat, _fp(8, "EASGDTrainer"))
    with pytest.raises(CheckpointReshardError, match="no recognized"):
        plan_reshard(man, _fp(4, "EASGDTrainer"))
    # stacked re-layout is rule-specific: no cross-trainer-class reshard
    man, _ = _easgd_manifest(8)
    with pytest.raises(CheckpointReshardError, match="one trainer class"):
        plan_reshard(man, _fp(4, "LocalSGDTrainer",
                              rule="easgd", tau=2, alpha="auto"))


def test_plan_async_transform_refuses_unstacked_leaves():
    man, flat = _easgd_manifest(8)
    plan = plan_reshard(man, _easgd_fp(4))
    bad = dict(flat)
    bad["params::conv/w"] = np.zeros((3,), np.float32)  # no worker axis
    with pytest.raises(CheckpointReshardError, match="layout tag"):
        plan.transform_arrays(bad)
    man, flat = _gosgd_manifest(8)
    plan = plan_reshard(man, _gosgd_fp(4))
    bad = dict(flat)
    bad["weights::"] = np.full((3,), 1 / 3, np.float32)
    with pytest.raises(CheckpointReshardError, match="consensus weights"):
        plan.transform_arrays(bad)


# -- fault grammar -----------------------------------------------------------

def test_async_fault_sites_parse_and_fire_once():
    assert SITES["easgd"] == ("worker_slow",)
    assert SITES["gosgd"] == ("gossip_drop",)
    plan = FaultPlan.parse("easgd:worker_slow@2, gosgd:gossip_drop@0")
    assert plan.fire("easgd", 1, "worker_slow") is None  # wrong ordinal
    assert plan.fire("gosgd", 2, "worker_slow") is None  # wrong action
    assert plan.fire("easgd", 2, "worker_slow") == "worker_slow"
    assert plan.fire("easgd", 2, "worker_slow") is None  # one-shot
    assert plan.fire("gosgd", 0, "gossip_drop") == "gossip_drop"
    with pytest.raises(FaultPlanError, match="valid"):
        FaultPlan.parse("easgd:kill@1")
    with pytest.raises(FaultPlanError, match="valid"):
        FaultPlan.parse("gosgd:worker_slow@1")


def test_async_telemetry_names_registered():
    """The emitting modules bind these spellings by index — a drift here
    is a tmlint finding AND a silently dead health detector."""
    assert ASYNC_INSTANTS == ("easgd.exchange", "gosgd.round")
    assert ASYNC_GAUGES == ("easgd.staleness", "easgd.center_drift",
                            "gosgd.staleness_max", "gosgd.staleness_mean")
    assert EXCHANGE_COUNTS == ("exchange.wire_bytes",)


# -- async_staleness detector units (synthetic events) -----------------------

def _mon(tmp_path, **cfg):
    return HealthMonitor(str(tmp_path), HealthConfig(**cfg),
                         clock=lambda: 0.0)


def _round(mon, name="easgd.exchange", step=0, **fields):
    mon.observe({"kind": "instant", "name": name, "step": step, **fields},
                now=0.0)


def _verdict(mon, detector="async_staleness"):
    for v in mon.verdicts():
        if v["detector"] == detector:
            return v
    return None


def test_async_staleness_warn_needs_sustained_rounds(tmp_path):
    mon = _mon(tmp_path)
    _round(mon, step=4, staleness=4, expected=4, stretch=1.0)
    assert _verdict(mon)["severity"] == SEV_OK
    # one bad round is noise, not a verdict flip
    _round(mon, step=16, staleness=12, expected=4, stretch=1.0)
    assert _verdict(mon)["severity"] == SEV_OK
    assert _verdict(mon)["fields"]["bad_rounds"] == 1
    _round(mon, step=28, staleness=12, expected=4, stretch=1.0)
    v = _verdict(mon)
    assert v["severity"] == SEV_WARN
    assert "straggler being absorbed" in v["reason"]
    assert v["fields"]["bad_rounds"] == 2
    # a healthy round resets the streak
    _round(mon, step=32, staleness=4, expected=4, stretch=1.0)
    v = _verdict(mon)
    assert v["severity"] == SEV_OK and v["fields"]["bad_rounds"] == 0


def test_async_stretch_alone_warns(tmp_path):
    mon = _mon(tmp_path)
    for step in (4, 8):
        _round(mon, step=step, staleness=4, expected=4, stretch=3.0)
    v = _verdict(mon)
    assert v["severity"] == SEV_WARN and "stretched" in v["reason"]


def test_async_drift_critical_is_immediate(tmp_path):
    mon = _mon(tmp_path)
    _round(mon, step=4, staleness=4, expected=4, stretch=1.0, drift=6.0)
    v = _verdict(mon)
    assert v["severity"] == SEV_CRITICAL
    assert v["fields"]["critical_at"] == pytest.approx(5.0)
    # sub-threshold drift rides along as a field, not a verdict
    mon2 = _mon(tmp_path)
    _round(mon2, step=4, staleness=4, expected=4, stretch=1.0, drift=0.02)
    v2 = _verdict(mon2)
    assert v2["severity"] == SEV_OK
    assert v2["fields"]["drift"] == pytest.approx(0.02)


def test_gosgd_round_feeds_same_detector(tmp_path):
    mon = _mon(tmp_path, async_min_rounds=1)
    _round(mon, name="gosgd.round", step=9, staleness=20, expected=4.0)
    v = _verdict(mon)
    assert v["severity"] == SEV_WARN and "gosgd.round" in v["reason"]


# -- wire-byte exactness (ISSUE 2 per-dtype contract audit) ------------------

def _easgd(devices, n_epochs, ck=None, **cfg):
    rule = EASGD(config={"verbose": False, "scale_lr": False, "tau": 2,
                         **({"checkpoint_dir": ck} if ck else {}), **cfg})
    rule.init(devices=devices, modelfile="theanompi_tpu.models.wide_resnet",
              modelclass="WideResNet",
              model_config={**TINY_CFG, "n_epochs": n_epochs})
    return rule


def _gosgd(devices, n_epochs, ck=None, **cfg):
    rule = GOSGD(config={"verbose": False,
                         **({"checkpoint_dir": ck} if ck else {}), **cfg})
    rule.init(devices=devices, modelfile="theanompi_tpu.models.wide_resnet",
              modelclass="WideResNet",
              model_config={**TINY_CFG, "n_epochs": n_epochs})
    return rule


def test_easgd_wire_bytes_match_dtype_contract():
    """The elastic psum ships ``p - c`` in each leaf's OWN dtype: the
    static accounting must equal ring traffic over the float center
    leaves at their verbatim itemsize — recomputed here from first
    principles, not via the audited helpers."""
    from theanompi_tpu.parallel.exchanger import wire_itemsize

    assert wire_itemsize("elastic", jnp.float32) == 4
    assert wire_itemsize("elastic", jnp.bfloat16) == 2  # verbatim, no cast
    rule = _easgd(4, 1)
    t = rule.trainer
    total = sum(int(leaf.size) * np.dtype(leaf.dtype).itemsize
                for leaf in jax.tree.leaves(t.center)
                if jnp.issubdtype(leaf.dtype, jnp.inexact))
    assert total > 0
    assert t._periodic_wire_bytes() == int(2 * (4 - 1) * total // 4)


def test_gosgd_hop_bytes_are_fp32_wire():
    """gossip_merge casts every outgoing leaf to fp32 on the wire, so a
    hop moves 4 bytes per float element of ONE worker's tree plus the
    4-byte consensus-weight scalar — independently recomputed."""
    rule = _gosgd(4, 1)
    t = rule.trainer
    elems = sum(int(leaf.size) // 4 for leaf in jax.tree.leaves(t.params)
                if jnp.issubdtype(leaf.dtype, jnp.inexact))
    assert elems > 0
    assert t._gossip_hop_bytes() == 4 * (elems + 1)


# -- resume matrix (in-process, tiny wide_resnet) ----------------------------

def _assert_ckpt_equal(path_a, path_b):
    with np.load(path_a) as a, np.load(path_b) as b:
        assert set(a.files) == set(b.files)
        for k in a.files:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)


@pytest.mark.faultinject
def test_easgd_crash_resume_bit_equal(tmp_path):
    """EASGD through the PR 5 verified chain: a crash one step into
    epoch 1 resumes from the epoch-0 boundary save — params, center and
    opt state round-trip bit-exactly, and the finished lineage matches an
    uninterrupted run; the manifest carries the rule-typed fingerprint."""
    clean_ck = str(tmp_path / "ck_clean")
    _easgd(4, 2, clean_ck).wait()

    ck = str(tmp_path / "ck")
    rule = _easgd(4, 2, ck, fault_plan="step:raise@3")
    with pytest.raises(FaultInjected):
        rule.wait()
    assert rule.trainer.try_resume()
    assert rule.trainer.epoch == 1
    rule.wait()
    _assert_ckpt_equal(os.path.join(clean_ck, "ckpt_e0001.npz"),
                       os.path.join(ck, "ckpt_e0001.npz"))
    with np.load(os.path.join(ck, "ckpt_e0001.npz")) as z:
        assert any(k.startswith("center::") for k in z.files)
    fp = json.load(open(os.path.join(
        ck, "ckpt_e0001.manifest.json")))["fingerprint"]
    assert fp["rule"] == "easgd" and fp["tau"] == 2
    assert fp["alpha"] == "auto" and fp["exchange"] == "EASGDTrainer"


@pytest.mark.faultinject
def test_easgd_cadence_midepoch_crash_resume_bit_equal(tmp_path):
    """Cadence saves + a crash INSIDE epoch 1: resume re-enters the epoch
    at the data cursor (not its start) and still finishes bit-equal to
    the uninterrupted run — the (center, weights, cursor) contract holds
    mid-epoch, not just at boundaries."""
    clean_ck = str(tmp_path / "ck_clean")
    _easgd(4, 2, clean_ck).wait()

    ck = str(tmp_path / "ck")
    rule = _easgd(4, 2, ck, fault_plan="step:raise@3",
                  checkpoint_every_n_iters=1, checkpoint_async=False)
    with pytest.raises(FaultInjected):
        rule.wait()  # 2 steps/epoch: dies ONE step into epoch 1, whose
        # cadence save (iteration 3, completed=False) is the latest
    assert rule.trainer.try_resume()
    assert rule.trainer.epoch == 1  # re-entered, not restarted
    assert rule.trainer.iteration == 3  # the cadence save's mid-epoch cursor
    rule.wait()
    _assert_ckpt_equal(os.path.join(clean_ck, "ckpt_e0001.npz"),
                       os.path.join(ck, "ckpt_e0001.npz"))


@pytest.mark.faultinject
def test_gosgd_crash_resume_bit_equal(tmp_path):
    """GOSGD resume replays the gossip draws it would have made (stateless
    (seed, iteration) derivation): the resumed lineage is bit-equal with
    NO extra RNG state in the checkpoint; consensus mass stays 1."""
    cfg = {"p_push": 0.9}
    clean_ck = str(tmp_path / "ck_clean")
    _gosgd(4, 2, clean_ck, **cfg).wait()

    ck = str(tmp_path / "ck")
    rule = _gosgd(4, 2, ck, fault_plan="step:raise@3", **cfg)
    with pytest.raises(FaultInjected):
        rule.wait()
    assert rule.trainer.try_resume()
    rule.wait()
    _assert_ckpt_equal(os.path.join(clean_ck, "ckpt_e0001.npz"),
                       os.path.join(ck, "ckpt_e0001.npz"))
    w = np.asarray(rule.trainer.weights)
    assert w.sum() == pytest.approx(1.0, abs=1e-5)
    fp = json.load(open(os.path.join(
        ck, "ckpt_e0001.manifest.json")))["fingerprint"]
    assert fp["rule"] == "gosgd" and fp["p_push"] == 0.9


@pytest.mark.faultinject
def test_easgd_elastic_mesh8_to_4(tmp_path):
    """The tentpole acceptance unit: an EASGD mesh8 lineage resumes onto
    mesh4 as a TYPED stacked plan — first 4 worker replicas kept, center
    restored bit-exactly, LR carried unrescaled — and trains on; a blind
    (non-reshard) consumer still refuses with the actionable mismatch."""
    ck = str(tmp_path / "ck")
    _easgd(8, 1, ck, tau=1).wait()
    with np.load(os.path.join(ck, "ckpt_e0000.npz")) as z:
        saved = {k: z[k] for k in z.files
                 if k.startswith(("params::", "center::"))}

    down = _easgd(4, 2, ck, tau=1, resume_reshard=True)
    blind = Checkpointer(ck, fingerprint=down.trainer._run_fingerprint(),
                         sweep_debris=False)
    with pytest.raises(CheckpointReshardableMismatch, match="mesh"):
        blind.verify_epoch(0)

    t = down.trainer
    assert t.epoch == 1  # epoch 0 resumed, not restarted
    assert t.lr_scale == pytest.approx(1.0)  # carried, not linear-scaled
    plan = t.checkpointer.last_reshard_plan
    assert plan is not None and plan.stacked == "easgd"
    assert (plan.old_n, plan.new_n) == (8, 4)
    # live params are the first 4 rows of the saved stacked trees; the
    # center re-placed bit-exactly
    for path, leaf in jax.tree_util.tree_flatten_with_path(t.params)[0]:
        key = "params::" + ck_mod._leaf_key(path)
        np.testing.assert_array_equal(np.asarray(leaf), saved[key][:4],
                                      err_msg=key)
    for path, leaf in jax.tree_util.tree_flatten_with_path(t.center)[0]:
        key = "center::" + ck_mod._leaf_key(path)
        np.testing.assert_array_equal(np.asarray(leaf), saved[key],
                                      err_msg=key)
    down.wait()
    assert t.epoch == 2
    assert t.recorder.val_history["epoch"] == [0, 1]  # continuous curve
    events = json.load(open(os.path.join(ck, "resilience.json")))["events"]
    names = [e["name"] for e in events]
    assert "reshard.plan" in names and "reshard.apply" in names
    man = json.load(open(os.path.join(ck, "ckpt_e0001.manifest.json")))
    assert man["fingerprint"]["mesh"]["data"] == 4
    assert man["fingerprint"]["rule"] == "easgd"


@pytest.mark.faultinject
def test_easgd_worker_slow_degrades_throughput_not_trajectory(
        tmp_path, monkeypatch, capfd):
    """A straggler stall before the synchronous exchange costs wall time
    only: the faulted run's params are bit-equal to the unfaulted one."""
    monkeypatch.setenv("THEANOMPI_EASGD_SLOW_S", "0.01")
    ref = _easgd(4, 1, tau=1)
    ref.wait()
    slow = _easgd(4, 1, tau=1, fault_plan="easgd:worker_slow@1")
    slow.wait()
    assert "injected EASGD straggler" in capfd.readouterr().err
    for a, b in zip(jax.tree.leaves(ref.trainer.params),
                    jax.tree.leaves(slow.trainer.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.faultinject
def test_gosgd_gossip_drop_conserves_consensus(tmp_path, capfd):
    """A dropped gossip round skips the collective but consumes its
    draws: the run completes, weights still sum to 1, later rounds keep
    the uninterrupted schedule (the round counter advanced)."""
    rule = _gosgd(4, 1, p_push=1.0, fault_plan="gosgd:gossip_drop@0")
    rule.wait()
    assert "injected gossip drop" in capfd.readouterr().err
    t = rule.trainer
    assert t._round_count >= 2  # rounds kept flowing after the drop
    assert np.asarray(t.weights).sum() == pytest.approx(1.0, abs=1e-5)


# -- supervised SIGKILL e2e (subprocess) -------------------------------------

def _adaptive_timeout(base: float) -> float:
    try:
        load = os.getloadavg()[0]
    except (OSError, AttributeError):
        return base
    per_core = load / max(os.cpu_count() or 1, 1)
    return base * min(4.0, max(1.0, per_core))


def _child_env(**extra):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "JAX_THREEFRY_PARTITIONABLE": "true",
        "PYTHONPATH": REPO,
    })
    env.pop("THEANOMPI_FAULT_PLAN", None)
    env.update(extra)
    return env


@pytest.mark.faultinject
def test_easgd_supervised_sigkill_cadence_resume_bit_equal(
        tmp_path, subproc_compile_cache):
    """The ISSUE 20 resume-matrix e2e: a supervised EASGD run with
    cadence saves SIGKILLed mid-epoch-1 restarts, auto-resumes through
    the verified chain, and finishes bit-equal to an uninterrupted
    in-process run at the same seed."""
    clean_ck = str(tmp_path / "ck_clean")
    _easgd(4, 2, clean_ck).wait()

    ck = str(tmp_path / "ck")
    p = subprocess.run(
        [sys.executable, "-m", "theanompi_tpu.launcher",
         "--rule", "EASGD", "--devices", "4",
         "--modelfile", "theanompi_tpu.models.wide_resnet",
         "--modelclass", "WideResNet", *TINY_ARGS,
         "--set", "n_epochs=2", "--quiet",
         "--rule-set", "tau=2", "--rule-set", "scale_lr=False",
         "--rule-set", "checkpoint_every_n_iters=1",
         "--rule-set", "checkpoint_async=False",
         "--checkpoint-dir", ck,
         "--supervise", "--max-restarts", "3", "--backoff-base", "0.1"],
        env=_child_env(THEANOMPI_FAULT_PLAN="step:kill@3@1"),
        cwd=REPO, capture_output=True, text=True,
        timeout=_adaptive_timeout(480))
    assert p.returncode == 0, p.stderr[-2000:]
    art = json.load(open(os.path.join(ck, "resilience.json")))
    assert [a["cause"] for a in art["attempts"]] == ["crash", "clean"]
    assert art["attempts"][0]["exit_code"] == -signal.SIGKILL
    _assert_ckpt_equal(os.path.join(clean_ck, "ckpt_e0001.npz"),
                       os.path.join(ck, "ckpt_e0001.npz"))
