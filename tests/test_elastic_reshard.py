"""Elastic resharded resume (ISSUE 8): the fingerprint gate, the
manifest-only reshard planner, zero1 flat-bucket re-layout, the elastic
supervisor, the scrubber dry-run CLI, and the acceptance e2e — a
supervised run SIGKILLed on mesh8 resumes under ``--elastic`` onto mesh4
and back onto mesh8 with a continuous loss curve.

Planner units run on handcrafted manifests (milliseconds, no training);
the training matrix reuses the tiny wide_resnet config every resilience
e2e shares so subprocess children hit one compile-cache entry.
"""

import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

import jax

from theanompi_tpu.resilience import (
    EXIT_RESHARD,
    FaultPlan,
    Supervisor,
    classify_exit,
)
from theanompi_tpu.utils import checkpoint as ck_mod
from theanompi_tpu.utils.checkpoint import (
    CheckpointFingerprintError,
    CheckpointReshardError,
    CheckpointReshardableMismatch,
    Checkpointer,
    build_manifest,
    check_fingerprint,
    plan_reshard,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_CFG = {"depth": 10, "widen": 1, "batch_size": 4, "image_size": 8,
            "n_train": 32, "n_val": 16, "n_epochs": 1, "precision": "fp32"}
TINY_ARGS = ["--set", "depth=10", "--set", "widen=1", "--set", "batch_size=4",
             "--set", "image_size=8", "--set", "n_train=32",
             "--set", "n_val=16", "--set", "precision='fp32'"]


def _fp(n=8, strategy="psum", **over):
    fp = {"mesh": {"data": n, "pipe": 1, "model": 1, "seq": 1},
          "exchange": strategy, "n_subb": 1,
          "model": "WideResNet", "model_config_sha": "abc123"}
    fp.update(over)
    return fp


def _zero1_manifest(n=8, lr_scale=1.0):
    """A handcrafted zero1 manifest: params 5+4=9 payload elems in one
    bucket, padded to 16 at n=8 / 12 at n=4."""
    flat = {
        "params::conv/w": np.zeros((5,), np.float32),
        "params::fc/w": np.zeros((4,), np.float32),
        "state::bn/mean": np.zeros((2,), np.float32),
        "opt_state::velocity/0": np.zeros((9 + (-9) % n,), np.float32),
    }
    return build_manifest(3, 7, flat, _fp(n, "zero1"), lr_scale=lr_scale)


# -- planner units -----------------------------------------------------------

def test_plan_reshard_zero1_relayout():
    plan = plan_reshard(_zero1_manifest(8), _fp(4, "zero1"))
    assert (plan.old_n, plan.new_n) == (8, 4)
    assert plan.lr_scale == pytest.approx(0.5)
    assert plan.buckets == [(9, 16, 12)]
    # transform: payload preserved, old padding stripped, new padding zero
    arr = np.arange(16, dtype=np.float32)
    out = plan.transform_arrays({"opt_state::velocity/0": arr})
    np.testing.assert_array_equal(
        out["opt_state::velocity/0"],
        np.concatenate([np.arange(9), np.zeros(3)]).astype(np.float32))
    # growing direction too
    up = plan_reshard(_zero1_manifest(4), _fp(8, "zero1"))
    assert up.buckets == [(9, 12, 16)]
    assert up.lr_scale == pytest.approx(2.0)


def test_plan_reshard_composes_carried_lr_scale():
    """mesh8 -> mesh4 stamps x0.5; resharding that checkpoint back to 8
    must net exactly 1.0 against the originally tuned LR."""
    plan = plan_reshard(_zero1_manifest(4, lr_scale=0.5), _fp(8, "zero1"))
    assert plan.lr_scale == pytest.approx(1.0)


def test_plan_reshard_non_zero1_is_passthrough():
    flat = {"params::w": np.zeros((4,), np.float32),
            "opt_state::velocity/w": np.zeros((4,), np.float32)}
    man = build_manifest(0, 1, flat, _fp(8, "psum_bucket"))
    plan = plan_reshard(man, _fp(2, "psum_bucket"))
    assert plan.buckets is None and plan.lr_scale == pytest.approx(0.25)
    arrays = {"opt_state::velocity/w": np.arange(4.0)}
    assert plan.transform_arrays(arrays) is arrays  # identity, no copy


@pytest.mark.parametrize("target,match", [
    (_fp(4, "zero1", mesh={"data": 2, "model": 2}), "non-data axes"),
    (_fp(4, "psum"), "layout changes"),
    (_fp(4, "zero1", model_config_sha="zzz"), "model-identity"),
])
def test_plan_reshard_refusals(target, match):
    with pytest.raises(CheckpointReshardError, match=match):
        plan_reshard(_zero1_manifest(8), target)


def test_plan_reshard_refuses_tp_checkpoint():
    man = _zero1_manifest(8)
    man["fingerprint"]["mesh"] = {"data": 4, "model": 2}
    with pytest.raises(CheckpointReshardError, match="non-data axes"):
        plan_reshard(man, _fp(4, "zero1"))


def test_plan_reshard_refuses_rule_extras():
    flat = {"params::w": np.zeros((4,), np.float32),
            "extras::center/w": np.zeros((4,), np.float32)}
    man = build_manifest(0, 1, flat, _fp(8, "psum"))
    with pytest.raises(CheckpointReshardError, match="rule extras"):
        plan_reshard(man, _fp(4, "psum"))


def test_plan_reshard_refuses_without_fingerprint():
    man = build_manifest(0, 1, {"params::w": np.zeros((2,), np.float32)},
                         None)
    with pytest.raises(CheckpointReshardError, match="no run fingerprint"):
        plan_reshard(man, _fp(4, "psum"))


def test_plan_reshard_refuses_bucket_padding_mismatch():
    """A stored shard whose length disagrees with the recomputed layout
    (exch_bucket_mb changed between runs) must refuse, never truncate."""
    man = _zero1_manifest(8)
    man["leaves"]["opt_state::velocity/0"]["shape"] = [24]
    with pytest.raises(CheckpointReshardError, match="bucket"):
        plan_reshard(man, _fp(4, "zero1"))


def test_check_fingerprint_reshardable_vs_fatal():
    """Mismatch errors name the differing keys and are typed: topology
    keys -> CheckpointReshardableMismatch, model identity -> fatal."""
    man = {"fingerprint": _fp(8, "psum")}
    with pytest.raises(CheckpointReshardableMismatch) as ei:
        check_fingerprint(man, _fp(4, "psum_bucket"), "/x/ckpt_e0000.npz")
    msg = str(ei.value)
    assert "mesh" in msg and "exchange" in msg
    assert "--resume-reshard" in msg and "RESHARDABLE" in msg

    with pytest.raises(CheckpointFingerprintError) as ei:
        check_fingerprint(man, _fp(8, "psum", model_config_sha="zzz"),
                          "/x/ckpt_e0000.npz")
    assert not isinstance(ei.value, CheckpointReshardableMismatch)
    assert "model_config_sha" in str(ei.value)
    assert "NOT reshardable" in str(ei.value)


def test_reshard_fault_site_grammar():
    plan = FaultPlan.parse("reshard:fail@2")
    assert plan.fire("reshard", 1) is None
    assert plan.fire("reshard", 2) == "fail"
    assert plan.fire("reshard", 2) is None  # one-shot


def test_classify_exit_reshard_is_distinct():
    assert classify_exit(EXIT_RESHARD) == "reshard"


def test_reshard_telemetry_names_registered():
    from theanompi_tpu.telemetry.metrics import RESHARD_INSTANTS

    assert set(RESHARD_INSTANTS) == {"reshard.plan", "reshard.apply"}


# -- supervisor elastic mode (python -c children, milliseconds) --------------

def _script_child(tmp_path, body: str) -> list:
    return [sys.executable, "-c", body.replace("STATE", repr(str(tmp_path)))]


def test_supervisor_elastic_rewrites_devices_and_resumes_reshard(tmp_path):
    """Attempt 2 must carry the probed --devices value plus the reshard
    resume args, and the attempt record must log the device count."""
    body = """
import os, sys
marker = os.path.join(STATE, "n")
n = int(open(marker).read()) if os.path.exists(marker) else 0
open(marker, "w").write(str(n + 1))
if n == 0:
    sys.exit(70)  # crash: the "pod lost chips" event
ok = ("--devices" in sys.argv
      and sys.argv[sys.argv.index("--devices") + 1] == "4"
      and "--resume-reshard" in sys.argv and "--resume" in sys.argv)
sys.exit(0 if ok else 71)
"""
    probes = iter([4])
    sup = Supervisor(
        _script_child(tmp_path, body) + ["--devices", "8"],
        max_restarts=2, backoff_base=0.0, jitter=0.0,
        resilience_path=str(tmp_path / "r.json"),
        sleep=lambda s: None, elastic=True,
        resume_args=("--resume", "--resume-reshard"),
        device_probe=lambda: next(probes))
    assert sup.run() == 0
    art = json.load(open(tmp_path / "r.json"))
    assert [a["cause"] for a in art["attempts"]] == ["crash", "clean"]
    assert "devices" not in art["attempts"][0]  # first attempt: as asked
    assert art["attempts"][1]["devices"] == 4


def test_supervisor_elastic_probe_failure_keeps_topology(tmp_path):
    """An unknowable device count must not block the restart — the child
    runs with the previous topology unchanged."""
    body = """
import os, sys
marker = os.path.join(STATE, "n")
n = int(open(marker).read()) if os.path.exists(marker) else 0
open(marker, "w").write(str(n + 1))
if n == 0:
    sys.exit(70)
ok = sys.argv[sys.argv.index("--devices") + 1] == "8"
sys.exit(0 if ok else 71)
"""

    def broken_probe():
        raise OSError("probe exploded")

    sup = Supervisor(
        _script_child(tmp_path, body) + ["--devices", "8"],
        max_restarts=2, backoff_base=0.0, jitter=0.0,
        resilience_path=str(tmp_path / "r.json"),
        sleep=lambda s: None, elastic=True, device_probe=broken_probe)
    assert sup.run() == 0


def test_supervisor_reshard_exit_is_fatal(tmp_path):
    """reshard fails -> classified fatal, no restart loop (the faults
    satellite's contract)."""
    body = f"""
import os, sys
marker = os.path.join(STATE, "n")
n = int(open(marker).read()) if os.path.exists(marker) else 0
open(marker, "w").write(str(n + 1))
sys.exit(70 if n == 0 else {EXIT_RESHARD})
"""
    sup = Supervisor(
        _script_child(tmp_path, body), max_restarts=5,
        backoff_base=0.0, jitter=0.0,
        resilience_path=str(tmp_path / "r.json"),
        sleep=lambda s: None, elastic=True, device_probe=lambda: 4)
    assert sup.run() == EXIT_RESHARD
    art = json.load(open(tmp_path / "r.json"))
    assert [a["cause"] for a in art["attempts"]] == ["crash", "reshard"]
    assert art["attempts"][1]["reshard"] == "failed"
    assert art["restarts"] == 1  # the reshard failure did NOT restart


def test_launcher_elastic_flag_implies_supervision():
    """--elastic parses, is stripped from the child argv, and the child
    flags include the reshard resume pair."""
    from theanompi_tpu import launcher

    args = launcher.build_parser().parse_args(
        ["--elastic", "--devices", "8"])
    assert args.elastic and not args.supervise  # main() promotes it
    stripped = launcher._strip_supervision_args(
        ["--elastic", "--supervise", "--max-restarts", "3",
         "--devices", "8"])
    assert stripped == ["--devices", "8"]


# -- scrubber CLI dry run ----------------------------------------------------

def _write_zero1_dir(tmp_path, n=8, strategy="zero1"):
    d = str(tmp_path / "ckpt")
    ck = Checkpointer(d, fingerprint=_fp(n, strategy))
    flat_trees = {
        "params": {"conv": {"w": np.zeros((5,), np.float32)},
                   "fc": {"w": np.zeros((4,), np.float32)}},
        "opt_state": {"velocity": [np.zeros((9 + (-9) % n,), np.float32)]},
    }
    ck.save(0, 3, flat_trees)
    ck.mark_clean()
    return d


def test_reshard_plan_cli_is_manifest_only(tmp_path, capsys):
    """--reshard-plan --to-devices N prints the planned re-layout without
    reading a checkpoint byte: a truncated .npz (live-writer torn state)
    must not stop the dry run."""
    d = _write_zero1_dir(tmp_path)
    # destroy the archive — only the manifest may be consulted
    npz = os.path.join(d, "ckpt_e0000.npz")
    with open(npz, "r+b") as f:
        f.truncate(4)
    rc = ck_mod.main(["--reshard-plan", d, "--to-devices", "4"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "reshard plan: 8 -> 4 workers" in out
    assert "bucket 0: payload 9 elems" in out
    assert "LR x0.5" in out
    assert "plannable" in out


def test_reshard_plan_cli_refusal_exit_code(tmp_path, capsys):
    d = _write_zero1_dir(tmp_path)
    # zero1 -> psum is a layout-family change: refused, exit 79
    rc = ck_mod.main(["--reshard-plan", d, "--to-devices", "4",
                      "--strategy", "psum"])
    assert rc == EXIT_RESHARD
    assert "REFUSED" in capsys.readouterr().out


def test_reshard_plan_cli_usage_errors(tmp_path):
    d = _write_zero1_dir(tmp_path)
    with pytest.raises(SystemExit) as ei:
        ck_mod.main(["--reshard-plan", d])  # missing --to-devices
    assert ei.value.code == 2
    with pytest.raises(SystemExit) as ei:
        ck_mod.main(["--verify", d, "--reshard-plan", d,
                     "--to-devices", "4"])  # mutually exclusive
    assert ei.value.code == 2


def test_reshard_gate_outranks_resume_force(tmp_path):
    """resume_force must not silently defeat resume_reshard: with both
    set, a topology-only mismatch is REPLANNED (strictly safer than
    force's blind restore of old-n shards into new-n templates), while a
    model-identity mismatch still honors the force override."""
    d = _write_zero1_dir(tmp_path)  # mesh8 zero1
    t4 = {"params": {"conv": {"w": np.zeros((5,), np.float32)},
                     "fc": {"w": np.zeros((4,), np.float32)}},
          "opt_state": {"velocity": [np.zeros((12,), np.float32)]}}
    ck = Checkpointer(d, fingerprint=_fp(4, "zero1"), reshard=True,
                      resume_force=True, sweep_debris=False)
    ep, _, restored = ck.load_latest_verified(t4)
    assert ck.last_reshard_plan is not None  # resharded, NOT blind-forced
    assert restored["opt_state"]["velocity"][0].shape == (12,)

    # fatal (model-identity) mismatch + force: the documented blind
    # override still works, and no plan is invented for it
    t8 = {"params": t4["params"],
          "opt_state": {"velocity": [np.zeros((16,), np.float32)]}}
    ck2 = Checkpointer(d, reshard=True, resume_force=True,
                       sweep_debris=False,
                       fingerprint=_fp(8, "zero1", model_config_sha="zzz"))
    ep, _, _ = ck2.load_latest_verified(t8)
    assert ep == 0 and ck2.last_reshard_plan is None


def test_lr_scale_survives_verify_none(tmp_path):
    """The legacy no-verify resume path must still carry a resharded
    lineage's cumulative LR factor (best-effort manifest read)."""
    d = str(tmp_path / "ck")
    ck = Checkpointer(d, fingerprint=_fp(4, "psum"))
    tree = {"params": {"w": np.zeros((3,), np.float32)}}
    ck.save(0, 2, tree, lr_scale=0.5)
    ck.mark_clean()
    ck2 = Checkpointer(d, fingerprint=_fp(4, "psum"), sweep_debris=False)
    res = ck2.load_latest_verified(
        {"params": {"w": np.zeros((3,), np.float32)}}, verify="none")
    assert res is not None
    assert ck2.last_loaded_manifest["lr_scale"] == pytest.approx(0.5)


def test_supervisor_probe_rejects_nonsense_counts(tmp_path, monkeypatch):
    """A probed count of 0 (or a bogus THEANOMPI_ELASTIC_DEVICES) is a
    FAILED probe — the previous topology is kept, never --devices 0."""
    body = """
import os, sys
marker = os.path.join(STATE, "n")
n = int(open(marker).read()) if os.path.exists(marker) else 0
open(marker, "w").write(str(n + 1))
if n == 0:
    sys.exit(70)
sys.exit(0 if sys.argv[sys.argv.index("--devices") + 1] == "8" else 71)
"""
    sup = Supervisor(
        _script_child(tmp_path, body) + ["--devices", "8"],
        max_restarts=2, backoff_base=0.0, jitter=0.0,
        resilience_path=str(tmp_path / "r.json"),
        sleep=lambda s: None, elastic=True, device_probe=lambda: 0)
    assert sup.run() == 0
    # the env-override route validates identically
    monkeypatch.setenv("THEANOMPI_ELASTIC_DEVICES", "0")
    sup2 = Supervisor(["true"], elastic=True,
                      resilience_path=str(tmp_path / "r2.json"))
    assert sup2._probe_devices(2) is None


def test_reshard_refuses_verify_none(tmp_path):
    """--resume-reshard + checkpoint_verify='none' is a typed refusal:
    the plan is computed from the manifest that verify='none' skips.
    An EMPTY directory is still a fresh start, not a refusal — an
    elastic restart that crashed before its first checkpoint must
    restart, not die with exit 79."""
    empty = Checkpointer(str(tmp_path / "empty"), reshard=True)
    assert empty.load_latest_verified({}, verify="none") is None

    d = _write_zero1_dir(tmp_path)
    ck = Checkpointer(d, fingerprint=_fp(4, "zero1"), reshard=True,
                      sweep_debris=False)
    with pytest.raises(CheckpointReshardError, match="verified loads"):
        ck.load_latest_verified({}, verify="none")


def test_reshard_plan_cli_rejects_unknown_strategy(tmp_path):
    """A --strategy typo must be a usage error, not a false 'plannable'
    verdict the real resume would then reject."""
    d = _write_zero1_dir(tmp_path)
    with pytest.raises(SystemExit) as ei:
        ck_mod.main(["--reshard-plan", d, "--to-devices", "4",
                     "--strategy", "psumbucket"])
    assert ei.value.code == 2


def test_supervisor_ignores_stale_reshard_events(tmp_path):
    """A fresh elastic supervisor over a directory holding YESTERDAY'S
    reshard.apply events must not stamp today's first attempt as
    'applied' — only events newer than this run count."""
    from theanompi_tpu.resilience.events import record_event

    rpath = str(tmp_path / "r.json")
    record_event(rpath, "reshard.apply", epoch=0, old_n=8, new_n=4)
    sup = Supervisor([sys.executable, "-c", "import sys; sys.exit(0)"],
                     max_restarts=0, resilience_path=rpath,
                     sleep=lambda s: None, elastic=True,
                     device_probe=lambda: 4)
    assert sup.run() == 0
    art = json.load(open(rpath))
    assert "reshard" not in art["attempts"][0]
    assert [e["name"] for e in art["events"]] == ["reshard.apply"]  # carried


# -- training matrix (in-process, tiny wide_resnet) --------------------------

def _rule(devices, n_epochs, ck, strategy, **cfg):
    from theanompi_tpu import BSP

    rule = BSP(config={"verbose": False, "checkpoint_dir": ck,
                       "exch_strategy": strategy, **cfg})
    rule.init(devices=devices, modelfile="theanompi_tpu.models.wide_resnet",
              modelclass="WideResNet",
              model_config={**TINY_CFG, "n_epochs": n_epochs})
    return rule


def _assert_params_match_ckpt(trainer, ck, epoch):
    leaves = jax.tree_util.tree_flatten_with_path(trainer.params)[0]
    with np.load(os.path.join(ck, f"ckpt_e{epoch:04d}.npz")) as z:
        for path, leaf in leaves:
            key = "params::" + ck_mod._leaf_key(path)
            np.testing.assert_array_equal(np.asarray(leaf), z[key],
                                          err_msg=key)


def test_reshard_roundtrip_psum_bucket(tmp_path):
    """mesh8 -> mesh4 -> mesh8 for psum_bucket: each resume restores the
    checkpoint params EXACTLY (replicated params re-place bit-equal), the
    LR factor tracks 1.0 -> 0.5 -> 1.0, the run completes with a
    continuous epoch sequence, and the blind resume refuses."""
    ck = str(tmp_path / "ck")
    _rule(8, 1, ck, "psum_bucket").wait()

    down = _rule(4, 2, ck, "psum_bucket", resume_reshard=True)
    # a blind (non-reshard) consumer at the same mesh4 topology still
    # refuses the mesh8 checkpoint with the typed, actionable mismatch
    blind = Checkpointer(ck, fingerprint=down.trainer._run_fingerprint(),
                         sweep_debris=False)
    with pytest.raises(CheckpointReshardableMismatch, match="mesh"):
        blind.verify_epoch(0)
    assert down.trainer.epoch == 1  # epoch 0 resumed, not restarted
    assert down.trainer.lr_scale == pytest.approx(0.5)
    _assert_params_match_ckpt(down.trainer, ck, 0)  # exact-param equality
    down.wait()
    assert down.trainer.epoch == 2

    up = _rule(8, 3, ck, "psum_bucket", resume_reshard=True)
    assert up.trainer.epoch == 2
    assert up.trainer.lr_scale == pytest.approx(1.0)  # back to baseline
    _assert_params_match_ckpt(up.trainer, ck, 1)
    up.wait()
    assert up.trainer.epoch == 3
    # loss-curve continuity: one val entry per epoch, no resets, finite
    hist = up.trainer.recorder.val_history
    assert hist["epoch"] == [0, 1, 2]
    assert np.isfinite(hist["cost"]).all()
    # audit trail: both transitions planned AND applied
    events = json.load(open(os.path.join(ck, "resilience.json")))["events"]
    names = [e["name"] for e in events]
    assert names.count("reshard.plan") == 2
    assert names.count("reshard.apply") == 2
    # final lineage is stamped with the mesh8 topology again
    man = json.load(open(os.path.join(ck, "ckpt_e0002.manifest.json")))
    assert man["fingerprint"]["mesh"]["data"] == 8
    assert man["lr_scale"] == pytest.approx(1.0)


def test_reshard_roundtrip_zero1_opt_state_survives(tmp_path):
    """The zero1 matrix: flat-bucket optimizer shards survive mesh8 ->
    mesh4 -> mesh8 payload-exactly (old padding stripped, new padding
    zero), and the re-scattered state trains on to completion."""
    ck = str(tmp_path / "ck")
    _rule(8, 1, ck, "zero1").wait()
    with np.load(os.path.join(ck, "ckpt_e0000.npz")) as z:
        saved = {k: z[k] for k in z.files
                 if k.startswith("opt_state::velocity/")}
    assert saved  # zero1 really stored flat buckets

    down = _rule(4, 2, ck, "zero1", resume_reshard=True)
    t = down.trainer
    _assert_params_match_ckpt(t, ck, 0)
    layout = t.exchanger.zero1_layout(t.params, 4)
    for key, old in saved.items():
        i = int(key.rsplit("/", 1)[1])
        new = np.asarray(t.opt_state["velocity"][i])
        elems = layout[i].elems
        np.testing.assert_array_equal(new[:elems], old[:elems], err_msg=key)
        assert not new[elems:].any()  # re-padding is zeros
    down.wait()

    up = _rule(8, 3, ck, "zero1", resume_reshard=True)
    t = up.trainer
    with np.load(os.path.join(ck, "ckpt_e0001.npz")) as z:
        for i, bucket in enumerate(t.exchanger.zero1_layout(t.params, 8)):
            old = z[f"opt_state::velocity/{i}"]
            new = np.asarray(t.opt_state["velocity"][i])
            np.testing.assert_array_equal(new[:bucket.elems],
                                          old[:bucket.elems])
    up.wait()
    assert t.epoch == 3
    assert t.lr_scale == pytest.approx(1.0)


@pytest.mark.faultinject
def test_elastic_supervised_sigkill_shrink_and_grow(tmp_path,
                                                    subproc_compile_cache):
    """THE acceptance scenario: a supervised zero1 run SIGKILLed one step
    into epoch 1 on mesh8 restarts under --elastic onto mesh4 (the probe
    says 4 chips survived), is SIGKILLed again one step into epoch 2, and
    finishes back on mesh8 — continuous loss curve, correct epoch count,
    reshard.plan/reshard.apply recorded, per-attempt device counts in
    resilience.json."""
    ck = str(tmp_path / "ck")
    rec = str(tmp_path / "rec")
    child = [sys.executable, "-m", "theanompi_tpu.launcher",
             "--rule", "BSP", "--devices", "8",
             "--modelfile", "theanompi_tpu.models.wide_resnet",
             "--modelclass", "WideResNet", *TINY_ARGS,
             # n_train=64 -> 2 steps/epoch on mesh8, 4 on mesh4: the kills
             # land one full step AFTER each epoch boundary.  Saves are
             # synchronous: a step's worth of time was not enough for the
             # async writer to publish on a fast machine (the resumed
             # attempt then found the older epoch and had nothing to
             # reshard — KeyError 'reshard')
             "--set", "n_train=64", "--set", "n_epochs=3",
             "--rule-set", "exch_strategy=zero1",
             "--rule-set", "checkpoint_async=False",
             "--checkpoint-dir", ck, "--record-dir", rec, "--quiet"]
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "JAX_THREEFRY_PARTITIONABLE": "true",
        "PYTHONPATH": REPO,
        # attempt 1 (mesh8, 2 steps/epoch): kill at iteration 3 = epoch
        # 1's second step, after e0000 published; attempt 2 (mesh4, 4
        # steps/epoch, resumed at iteration 2): kill at iteration 7 =
        # epoch 2's second step, after e0001 published
        "THEANOMPI_FAULT_PLAN": "step:kill@3@1,step:kill@7@2",
    }
    probes = iter([4, 8])  # attempt 2 sees 4 chips, attempt 3 sees 8 again
    sup = Supervisor(
        child, max_restarts=3, backoff_base=0.1, jitter=0.0,
        resilience_path=os.path.join(ck, "resilience.json"),
        resume_args=("--resume", "--resume-reshard"),
        elastic=True, device_probe=lambda: next(probes),
        env=env, sleep=lambda s: None)
    os.makedirs(ck, exist_ok=True)
    rc = sup.run()
    art = json.load(open(os.path.join(ck, "resilience.json")))
    assert rc == 0, art
    assert [a["cause"] for a in art["attempts"]] == [
        "crash", "crash", "clean"]
    assert art["attempts"][0]["exit_code"] == -signal.SIGKILL
    assert art["attempts"][1]["devices"] == 4
    assert art["attempts"][2]["devices"] == 8
    assert art["attempts"][1]["reshard"] == "applied"  # 8 -> 4 mid-attempt
    assert art["attempts"][2]["reshard"] == "applied"  # 4 -> 8
    names = [e["name"] for e in art["events"]]
    assert names.count("reshard.plan") == 2
    assert names.count("reshard.apply") == 2
    # continuous loss curve + correct epoch count across both transitions
    val = np.load(os.path.join(rec, "val_history.npy"),
                  allow_pickle=True).item()
    assert list(val["epoch"]) == [0, 1, 2]
    assert np.isfinite(val["cost"]).all()
    # the final checkpoint is back on mesh8, fully verifiable, LR x1.0
    man = json.load(open(os.path.join(ck, "ckpt_e0002.manifest.json")))
    assert man["fingerprint"]["mesh"]["data"] == 8
    assert man["fingerprint"]["exchange"] == "zero1"
    assert man["lr_scale"] == pytest.approx(1.0)
    ck_mod.verify_file(os.path.join(ck, "ckpt_e0002.npz"), "full")


@pytest.mark.faultinject
def test_launcher_reshard_fault_exits_79(tmp_path, capsys):
    """The reshard:fail fault site drives the launcher's one-line error
    contract: CheckpointReshardError -> exit EXIT_RESHARD=79."""
    from theanompi_tpu import launcher
    from theanompi_tpu.models.wide_resnet import WideResNet
    from theanompi_tpu.utils.checkpoint import model_fingerprint

    # a mesh8 checkpoint whose params are never read: the injected fault
    # fires between plan and apply, so only the manifest matters — but the
    # model identity must match or the mismatch would be fatal, not
    # reshardable
    model = WideResNet(dict(TINY_CFG))
    ck = str(tmp_path / "ck")
    writer = Checkpointer(ck, fingerprint={
        "mesh": {"data": 8, "pipe": 1, "model": 1, "seq": 1},
        "exchange": "psum", "n_subb": 1, **model_fingerprint(model)})
    writer.save(0, 1, {"params": {"w": np.zeros((2,), np.float32)}})
    writer.mark_clean()

    rc = launcher.main([
        "--rule", "BSP", "--devices", "4",
        "--modelfile", "theanompi_tpu.models.wide_resnet",
        "--modelclass", "WideResNet", *TINY_ARGS,
        "--checkpoint-dir", ck, "--resume-reshard",
        "--rule-set", "fault_plan=reshard:fail@1", "--quiet"])
    assert rc == EXIT_RESHARD
    err = capsys.readouterr().err
    assert "tmlauncher: error: reshard: CheckpointReshardError" in err
    assert "Traceback" not in err
