"""ISSUE 5 tentpole: checkpoint integrity + the verified recovery chain.

Covers the acceptance matrix: manifests published with every save (async
and sync byte-identical, manifest included), the corruption matrix
(truncate / bit-flip / missing manifest / fingerprint mismatch) against
fast vs full verify, the recovery chain (fallback + quarantine + the
``ckpt.fallback`` audit event), verified retention (`_prune` never
deletes the last verifiable checkpoint), the background scrub, the
``--verify`` scrubber CLI, the dirty-marker clean-shutdown handshake,
cold-``--resume`` fallback on a real trainer, sentinel ``rollback``
through the chain, exit 77 on an exhausted chain, and THE supervised
scenario: SIGKILL + corrupt-latest -> restart -> fallback to the previous
checkpoint -> run completes with the correct final epoch count.
"""

import json
import os
import shutil
import signal
import subprocess
import sys

import numpy as np
import pytest

import jax

from theanompi_tpu.resilience import EXIT_CKPT, FaultPlan, FaultPlanError
from theanompi_tpu.utils.checkpoint import (
    CheckpointChainExhausted,
    CheckpointCorruptError,
    CheckpointFingerprintError,
    Checkpointer,
    main as scrubber_main,
    verify_file,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the trainer config + builder live in conftest.py (ISSUE 11 satellite):
# the session-scoped ``trained_wrn_ckpt`` fixture trains the 2-epoch run
# ONCE, and resuming trainers here are built by the same helper so their
# resume fingerprints match the fixture's checkpoints exactly
from conftest import WRN_TINY as TINY, make_wrn_trainer  # noqa: E402

#: subprocess flavor of TINY (shapes match tests/test_resilience_e2e.py so
#: the session-scoped compile cache is shared across both files' children)
SUB_ARGS = ["--set", "depth=10", "--set", "widen=1", "--set", "batch_size=4",
            "--set", "image_size=8", "--set", "n_train=32",
            "--set", "n_val=16", "--set", "precision='fp32'"]


def _tree(e):
    return {"a": np.arange(6, dtype=np.float32).reshape(2, 3) + e,
            "b": {"c": np.full((4,), e, np.int32)}}


def _template():
    return {"params": {"a": np.zeros((2, 3), np.float32),
                       "b": {"c": np.zeros((4,), np.int32)}}}


def _bitflip(path):
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.seek(size // 2)
        b = f.read(1)
        f.seek(size // 2)
        f.write(bytes([b[0] ^ 0xFF]))


def _truncate(path):
    with open(path, "r+b") as f:
        f.truncate(max(1, os.path.getsize(path) // 2))


def _manifest_of(path):
    return path[:-len(".npz")] + ".manifest.json"


def _events(directory):
    return json.load(open(os.path.join(directory, "resilience.json")))[
        "events"]


# -- manifest + verify unit matrix -------------------------------------------

def test_manifest_published_with_save_and_bit_identical(tmp_path):
    """Every save publishes a manifest; async and sync produce byte-equal
    .npz AND manifest (the manifest carries no timestamps by design)."""
    fp = {"mesh": {"data": 4}, "exchange": "psum"}
    sync_ck = Checkpointer(str(tmp_path / "sync"), async_save=False,
                           fingerprint=fp)
    sync_ck.save(0, 7, {"params": _tree(1)})
    async_ck = Checkpointer(str(tmp_path / "async"), async_save=True,
                            fingerprint=fp)
    async_ck.save(0, 7, {"params": _tree(1)}).join()
    a_npz = open(sync_ck._path(0), "rb").read()
    b_npz = open(async_ck._path(0), "rb").read()
    assert a_npz == b_npz
    a_man = open(_manifest_of(sync_ck._path(0)), "rb").read()
    b_man = open(_manifest_of(async_ck._path(0)), "rb").read()
    assert a_man == b_man
    man = json.loads(a_man)
    assert man["epoch"] == 0 and man["iteration"] == 7
    assert set(man["leaves"]) == {"params::a", "params::b/c"}
    for meta in man["leaves"].values():
        assert {"shape", "dtype", "nbytes", "crc32"} <= set(meta)
    assert man["fingerprint"]["mesh"] == {"data": 4}


def test_snapshot_owns_its_bytes(tmp_path):
    """The save-time snapshot must copy, not view, device buffers: on the
    CPU backend ``np.asarray(jax.Array)`` aliases the buffer itself, and
    the next step's donation rewrites it under the async writer — a torn
    ``.npz`` whose manifest CRCs then (flakily) fail resume verification.
    Regression for the supervised-SIGKILL e2e flake."""
    ck = Checkpointer(str(tmp_path))
    dev = jax.device_put(np.arange(6, dtype=np.float32))
    flat = ck._snapshot({"params": {"a": dev, "b": np.ones((2,), np.int32)}})
    for key, arr in flat.items():
        assert arr.base is None and arr.flags.owndata, (
            f"snapshot leaf {key!r} does not own its bytes — it aliases "
            f"a (donatable) device buffer")


def test_verify_matrix_truncate_bitflip_manifest(tmp_path):
    """truncate fails even the fast check; a bit-flip passes fast (by
    design — it is structural only) and fails full; a dropped manifest
    fails fast."""
    ck = Checkpointer(str(tmp_path), fingerprint={"m": 1})
    ck.save(0, 1, {"params": _tree(0)})
    path = ck._path(0)
    verify_file(path, "fast")
    verify_file(path, "full")

    # fingerprint is checked on verify_epoch, not raw verify_file
    ck.verify_epoch(0, "full")
    ck_other = Checkpointer(str(tmp_path), fingerprint={"m": 2})
    with pytest.raises(CheckpointFingerprintError, match="resume-force"):
        ck_other.verify_epoch(0, "fast")

    _bitflip(path)
    verify_file(path, "fast")  # structural check cannot see a data flip
    with pytest.raises(CheckpointCorruptError, match="CRC|read failed"):
        verify_file(path, "full")

    ck.save(1, 2, {"params": _tree(1)})
    _truncate(ck._path(1))
    with pytest.raises(CheckpointCorruptError, match="unreadable|leaf set"):
        verify_file(ck._path(1), "fast")

    ck.save(2, 3, {"params": _tree(2)})
    os.remove(_manifest_of(ck._path(2)))
    with pytest.raises(CheckpointCorruptError, match="manifest"):
        verify_file(ck._path(2), "fast")


def test_chain_falls_back_quarantines_and_audits(tmp_path):
    """Corrupt newest two of three -> the chain restores epoch 0, moves the
    bad pairs under corrupt/, records ckpt.quarantine + ckpt.fallback in
    resilience.json, and repoints latest.json at the verified epoch."""
    d = str(tmp_path)
    ck = Checkpointer(d, keep=5, fingerprint={"m": 1})
    for e in range(3):
        ck.save(e, e * 10, {"params": _tree(e)})
    _bitflip(ck._path(2))
    os.remove(_manifest_of(ck._path(1)))

    ep, it, restored = ck.load_latest_verified(_template(), verify="full")
    assert (ep, it) == (0, 0)
    np.testing.assert_array_equal(restored["params"]["a"], _tree(0)["a"])
    q = sorted(os.listdir(os.path.join(d, "corrupt")))
    assert "ckpt_e0001.npz" in q and "ckpt_e0002.npz" in q
    names = [e["name"] for e in _events(d)]
    assert names.count("ckpt.quarantine") == 2
    fb = [e for e in _events(d) if e["name"] == "ckpt.fallback"][0]
    assert fb["bad_epochs"] == [2, 1] and fb["restored_epoch"] == 0
    # the pointer never advertises a quarantined file
    assert json.load(open(os.path.join(d, "latest.json")))["epoch"] == 0
    # and latest_epoch() agrees post-fallback
    assert ck.latest_epoch() == 0


def test_chain_exhausted_vs_fresh_start(tmp_path):
    ck = Checkpointer(str(tmp_path))
    assert ck.load_latest_verified(_template()) is None  # fresh: no error
    ck.save(0, 1, {"params": _tree(0)})
    _truncate(ck._path(0))
    with pytest.raises(CheckpointChainExhausted, match="corrupt/"):
        ck.load_latest_verified(_template())


def test_fingerprint_mismatch_refused_unless_forced(tmp_path):
    d = str(tmp_path)
    Checkpointer(d, fingerprint={"mesh": {"data": 4}}).save(
        0, 1, {"params": _tree(0)})
    with pytest.raises(CheckpointFingerprintError, match="mesh"):
        Checkpointer(d, fingerprint={"mesh": {"data": 8}}) \
            .load_latest_verified(_template())
    # the mismatch is a refusal, not a corruption: nothing was quarantined
    assert not os.path.exists(os.path.join(d, "corrupt"))
    ep, _, _ = Checkpointer(d, fingerprint={"mesh": {"data": 8}},
                            resume_force=True) \
        .load_latest_verified(_template())
    assert ep == 0


def test_corrupt_read_wrapped_even_without_verify(tmp_path):
    """verify='none' still surfaces a typed CheckpointCorruptError on an
    unreadable file (the chain must classify late rot too)."""
    ck = Checkpointer(str(tmp_path))
    ck.save(0, 1, {"params": _tree(0)})
    _truncate(ck._path(0))
    with pytest.raises(CheckpointCorruptError):
        ck.load(0, _template(), verify="none")


# -- verified retention + scrub ----------------------------------------------

def test_prune_never_deletes_last_verifiable(tmp_path):
    """keep=1 with every later publish torn: the only good ancestor must
    survive any number of newer corrupt files (keep-n used to count the
    corrupt ones and rotate the good ancestor out)."""
    plan = FaultPlan.parse(
        "checkpoint:manifest_drop@1;checkpoint:manifest_drop@2;"
        "checkpoint:manifest_drop@3")
    ck = Checkpointer(str(tmp_path), keep=1, fault_plan=plan)
    for e in range(4):
        ck.save(e, e, {"params": _tree(e)})
    files = sorted(f for f in os.listdir(tmp_path)
                   if f.startswith("ckpt_e") and f.endswith(".npz"))
    assert "ckpt_e0000.npz" in files  # the only verifiable one survived
    # the idle-time scrub already quarantined the older torn publishes
    # (e1, e2); the chain steps over whatever newer corruption remains
    ep, _, _ = ck.load_latest_verified(_template())
    assert ep == 0


def test_prune_counts_only_verified_toward_keep(tmp_path):
    """With no corruption, keep-n behaves exactly as before; with a
    corrupt file in the middle, the keep-n window is computed over the
    verified set only."""
    ck = Checkpointer(str(tmp_path), keep=2)
    for e in range(3):
        ck.save(e, e, {"params": _tree(e)})
    files = [f for f in os.listdir(tmp_path) if f.endswith(".npz")]
    assert sorted(files) == ["ckpt_e0001.npz", "ckpt_e0002.npz"]


def test_prune_protects_newest_full_verified_against_silent_rot(tmp_path):
    """Fast verification cannot see a data-byte bit-flip, so keep-n alone
    could rotate the last hash-proven checkpoint out while its newer
    sibling is silently rotten.  The newest FULL-verified (scrubbed)
    checkpoint must survive until a newer one is hash-proven."""
    ck = Checkpointer(str(tmp_path), keep=1)
    ck.save(0, 0, {"params": _tree(0)})
    ck.save(1, 1, {"params": _tree(1)})  # scrub full-verifies e0 here
    files = sorted(f for f in os.listdir(tmp_path) if f.endswith(".npz"))
    # keep=1 would have deleted e0; the full-verified protection spares it
    assert files == ["ckpt_e0000.npz", "ckpt_e0001.npz"]
    _bitflip(ck._path(1))  # newest rots; fast verify still passes it
    ep, _, restored = ck.load_latest_verified(_template(), verify="full")
    assert ep == 0  # fell back to the protected hash-proven ancestor
    np.testing.assert_array_equal(np.asarray(restored["params"]["a"]),
                                  _tree(0)["a"])


def test_scrub_and_prune_skip_foreign_files(tmp_path):
    """A stray operator file matching the retention glob (e.g. an
    out-of-band backup `ckpt_e0000.bak.npz`) must not crash the writer
    thread's scrub/quarantine (regression: unguarded int() on the epoch
    slice) and is never deleted, scrubbed, or quarantined."""
    foreign = tmp_path / "ckpt_e0000.bak.npz"
    foreign.write_bytes(b"not a checkpoint at all")
    ck = Checkpointer(str(tmp_path), keep=1)
    for e in range(3):
        ck.save(e, e, {"params": _tree(e)})  # scrub+prune run each save
    ck.join_pending()  # a writer-thread crash would re-raise here
    assert foreign.exists()
    assert not os.path.exists(tmp_path / "corrupt" / foreign.name)
    assert ck.available_epochs() == sorted(ck.available_epochs())
    # the scrubber CLI applies the same membership rule: a healthy chain
    # plus a foreign file exits 0, not 77
    assert scrubber_main(["--verify", str(tmp_path)]) == 0


def test_background_scrub_quarantines_rotted_older(tmp_path):
    """The writer's idle-time scrub full-verifies one older checkpoint per
    save and quarantines rot before a resume ever needs it."""
    ck = Checkpointer(str(tmp_path), keep=5)
    ck.save(0, 0, {"params": _tree(0)})
    _bitflip(ck._path(0))  # rots on disk after a good publish
    ck.save(1, 1, {"params": _tree(1)})  # scrub runs here
    q = os.path.join(tmp_path, "corrupt")
    assert os.path.isdir(q) and "ckpt_e0000.npz" in os.listdir(q)
    assert any(e["name"] == "ckpt.quarantine"
               and e["reason"].startswith("scrub:")
               for e in _events(str(tmp_path)))


def test_dirty_marker_lifecycle(tmp_path):
    ck = Checkpointer(str(tmp_path))
    assert not ck.was_unclean()
    ck.save(0, 0, {"params": _tree(0)})
    assert ck.was_unclean()  # held until the clean-shutdown handshake
    ck.mark_clean()
    assert not ck.was_unclean()


# -- fault-plan grammar -------------------------------------------------------

def test_corruption_fault_specs_parse_and_apply(tmp_path):
    plan = FaultPlan.parse("checkpoint:bitflip@0,checkpoint:truncate@1;"
                           "checkpoint:manifest_drop@2")
    assert [s.action for s in plan.specs] == ["bitflip", "truncate",
                                              "manifest_drop"]
    with pytest.raises(FaultPlanError, match="invalid for site"):
        FaultPlan.parse("checkpoint:explode@0")

    # one dir per action so the writer's own scrub can't quarantine the
    # evidence before the assertion reads it
    for epoch, action, level, match in (
            (0, "bitflip", "full", "CRC|read failed"),
            (1, "truncate", "fast", "unreadable|leaf set"),
            (2, "manifest_drop", "fast", "manifest")):
        d = str(tmp_path / action)
        ck = Checkpointer(
            d, fault_plan=FaultPlan.parse(f"checkpoint:{action}@{epoch}"))
        ck.save(epoch, 1, {"params": _tree(epoch)})
        with pytest.raises(CheckpointCorruptError, match=match):
            verify_file(ck._path(epoch), level)
    # a bit-flip is invisible to the structural fast check (by design)
    verify_file(Checkpointer(str(tmp_path / "bitflip"))._path(0), "fast")


# -- scrubber CLI -------------------------------------------------------------

def test_scrubber_cli_report_and_quarantine(tmp_path, capsys):
    d = str(tmp_path)
    ck = Checkpointer(d, keep=5)
    for e in range(2):
        ck.save(e, e, {"params": _tree(e)})
    assert scrubber_main(["--verify", d]) == 0
    out = capsys.readouterr().out
    assert "2/2 checkpoints verifiable" in out and ": OK (" in out

    _bitflip(ck._path(1))
    assert scrubber_main(["--verify", d]) == EXIT_CKPT
    assert "CORRUPT" in capsys.readouterr().out
    # --fast misses the data flip by design
    assert scrubber_main(["--verify", d, "--fast"]) == 0
    capsys.readouterr()
    # --quarantine moves the bad pair out
    assert scrubber_main(["--verify", d, "--quarantine"]) == EXIT_CKPT
    assert "ckpt_e0001.npz" in os.listdir(os.path.join(d, "corrupt"))
    assert scrubber_main(["--verify", d]) == 0  # what remains verifies


# -- trainer-level matrix -----------------------------------------------------

_tiny_trainer = make_wrn_trainer


def test_cold_resume_falls_back_on_corrupt_latest(tmp_path, mesh4,
                                                  trained_wrn_ckpt):
    """A cold try_resume whose latest checkpoint is bit-flipped lands on
    the previous epoch with its exact params (the zip-CRC read error is
    classified as corruption even under the fast verify a clean-exit
    directory gets)."""
    # corrupt a COPY of the session run (epochs 0+1 published, clean)
    ck = str(tmp_path / "ck")
    shutil.copytree(trained_wrn_ckpt, ck)

    t2 = _tiny_trainer(mesh4, ck)
    assert not t2.checkpointer.was_unclean()
    params_e0 = t2.checkpointer.load(
        0, {"params": t2.params}, verify="full")["params"]
    _bitflip(os.path.join(ck, "ckpt_e0001.npz"))
    assert t2.try_resume()
    assert t2.epoch == 1  # fell back: epoch 0 completed, 1 is next
    for a, b in zip(jax.tree.leaves(t2.params),
                    jax.tree.leaves(params_e0)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert "ckpt_e0001.npz" in os.listdir(os.path.join(ck, "corrupt"))
    assert any(e["name"] == "ckpt.fallback" for e in _events(ck))


def test_trainer_fingerprint_mismatch_and_force(tmp_path, mesh8,
                                                trained_wrn_ckpt):
    """Resuming under a different mesh is refused with the typed error;
    resume_force turns it into a warned override (params are replicated
    under BSP, so the arrays themselves restore fine)."""
    ck = str(tmp_path / "ck")
    shutil.copytree(trained_wrn_ckpt, ck)  # the mesh4 session run
    t8 = _tiny_trainer(mesh8, ck)
    with pytest.raises(CheckpointFingerprintError, match="mesh"):
        t8.try_resume()
    t8f = _tiny_trainer(mesh8, ck, resume_force=True)
    assert t8f.try_resume()
    assert t8f.epoch == 2


def test_launcher_exit_77_on_exhausted_chain(tmp_path, capsys):
    """Acceptance: an exhausted chain exits 77 with a one-line
    `tmlauncher: error:` message."""
    from theanompi_tpu.launcher import main as tm_main

    ck = str(tmp_path / "ck")
    c = Checkpointer(ck)
    c.save(0, 1, {"params": _tree(0)})
    _truncate(c._path(0))
    rc = tm_main([
        "--rule", "BSP", "--devices", "4",
        "--modelfile", "theanompi_tpu.models.wide_resnet",
        "--modelclass", "WideResNet",
        "--set", "depth=10", "--set", "widen=1", "--set", "batch_size=8",
        "--set", "image_size=8", "--set", "n_train=32", "--set", "n_val=16",
        "--set", "n_epochs=1", "--set", "precision='fp32'",
        "--checkpoint-dir", ck, "--resume", "--quiet",
    ])
    assert rc == EXIT_CKPT == 77
    err = capsys.readouterr().err
    lines = [ln for ln in err.splitlines()
             if ln.startswith("tmlauncher: error:")]
    assert len(lines) == 1 and "checkpoint" in lines[0]


@pytest.mark.faultinject
def test_sentinel_rollback_through_verified_chain(tmp_path):
    """Satellite: a NaN-triggered rollback whose latest checkpoint is
    corrupt steps back to the verified ancestor and the run completes
    (it used to re-raise into the corrupt load)."""
    from theanompi_tpu import BSP

    ck = str(tmp_path / "ck")
    # devices=2, batch 8 -> global 16 -> 2 steps/epoch over n_train=32...
    # use batch_size=4 -> 4 steps/epoch: e0 saved (it 4), e1 saved+flipped
    # (it 8), NaN at step 9 (epoch 2) -> rollback -> chain lands on e0
    rule = BSP(config={"verbose": False, "print_freq": 1,
                       "fault_plan": "step:nan@9;checkpoint:bitflip@1",
                       "sentinel_policy": "rollback",
                       "checkpoint_dir": ck})
    rule.init(devices=2, modelfile="theanompi_tpu.models.wide_resnet",
              modelclass="WideResNet",
              model_config={**TINY, "batch_size": 4, "n_epochs": 3})
    rule.wait()
    t = rule.trainer
    assert t.sentinel.rollbacks == 1
    assert t.epoch == 3  # ran to completion after the rollback replay
    for leaf in jax.tree.leaves(t.params):
        assert np.isfinite(np.asarray(leaf)).all()
    assert "ckpt_e0001.npz" in os.listdir(os.path.join(ck, "corrupt"))
    events = _events(ck)
    assert any(e["name"] == "ckpt.fallback" and e["restored_epoch"] == 0
               for e in events)


# -- THE supervised acceptance scenario ---------------------------------------

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
def test_supervised_sigkill_with_corrupt_latest_falls_back(
        tmp_path, subproc_compile_cache):
    """Acceptance: a supervised run whose latest checkpoint is
    fault-injected corrupt is SIGKILLed, restarts, full-verifies (attempt
    2 after an unclean death), quarantines the bad epoch-1 files, falls
    back to epoch 0, replays, and finishes all 3 epochs — with the
    fallback recorded in resilience.json alongside the supervisor's
    attempt records."""
    ck = str(tmp_path / "ck")
    p = subprocess.run(
        [sys.executable, "-m", "theanompi_tpu.launcher",
         "--rule", "BSP", "--devices", "4",
         "--modelfile", "theanompi_tpu.models.wide_resnet",
         "--modelclass", "WideResNet", *SUB_ARGS, "--quiet",
         "--set", "n_epochs=3",
         # synchronous saves: epoch 1's (bit-flipped) checkpoint must be
         # PUBLISHED before the kill one step later — against the async
         # writer the kill wins that race on a fast machine, leaving a
         # clean epoch-0 latest and nothing to fall back from
         "--rule-set", "checkpoint_async=False",
         "--checkpoint-dir", ck,
         "--supervise", "--max-restarts", "3", "--backoff-base", "0.1"],
        # 2 steps/epoch (batch 4 x 4 workers over n_train=32): epoch-1's
        # checkpoint is bit-flipped as it publishes, then the child is
        # SIGKILLed one step into epoch 2 — attempt 1 only
        env=_child_env(
            THEANOMPI_FAULT_PLAN="checkpoint:bitflip@1@1;step:kill@5@1"),
        cwd=REPO, capture_output=True, text=True, timeout=480)
    assert p.returncode == 0, p.stderr[-2000:]

    art = json.load(open(os.path.join(ck, "resilience.json")))
    assert [a["cause"] for a in art["attempts"]] == ["crash", "clean"]
    assert art["attempts"][0]["exit_code"] == -signal.SIGKILL
    # the chain's audit events survived the supervisor's summary rewrites
    names = [e["name"] for e in art["events"]]
    assert "ckpt.quarantine" in names
    fb = [e for e in art["events"] if e["name"] == "ckpt.fallback"]
    assert fb and fb[0]["bad_epochs"] == [1] and fb[0]["restored_epoch"] == 0
    assert fb[0]["verify"] == "full"  # unclean exit -> full hash verify
    assert "ckpt_e0001.npz" in os.listdir(os.path.join(ck, "corrupt"))
    # correct final epoch count: all 3 epochs completed after the replay
    assert json.load(open(os.path.join(ck, "latest.json")))["epoch"] == 2
    assert os.path.exists(os.path.join(ck, "ckpt_e0002.npz"))
    # clean completion dropped the dirty marker
    assert not os.path.exists(os.path.join(ck, "dirty"))


def test_supervisor_classifies_exit_77_fatal():
    from theanompi_tpu.resilience import classify_exit

    assert classify_exit(77) == "checkpoint"
    assert classify_exit(70) == "crash"
