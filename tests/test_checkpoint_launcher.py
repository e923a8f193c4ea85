"""Checkpoint/resume + tmlauncher CLI tests."""

import os

import numpy as np
import pytest

import jax

from theanompi_tpu.launcher import main as tm_main
from theanompi_tpu.utils.checkpoint import Checkpointer

TINY = {"depth": 10, "widen": 1, "batch_size": 8, "image_size": 16,
        "n_train": 128, "n_val": 64, "n_epochs": 2, "precision": "fp32",
        "lr": 0.05}


def test_checkpointer_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    tree = {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": {"c": np.ones((4,), np.int32)}}
    ck.save(0, 10, {"params": tree})
    ck.save(1, 20, {"params": tree})
    ck.save(2, 30, {"params": tree})
    # retention: only 2 newest kept
    files = [f for f in os.listdir(tmp_path) if f.endswith(".npz")]
    assert len(files) == 2
    assert ck.latest_epoch() == 2 and ck.latest_iteration() == 30

    template = {"a": np.zeros((2, 3), np.float32),
                "b": {"c": np.zeros((4,), np.int32)}}
    out = ck.load(2, {"params": template})["params"]
    np.testing.assert_array_equal(out["a"], tree["a"])
    np.testing.assert_array_equal(out["b"]["c"], tree["b"]["c"])


def test_checkpointer_shape_mismatch(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(0, 1, {"params": {"a": np.zeros((2,), np.float32)}})
    with pytest.raises(ValueError, match="shape"):
        ck.load(0, {"params": {"a": np.zeros((3,), np.float32)}})


@pytest.mark.slow
@pytest.mark.parametrize("checkpoint_async", [True, False],
                         ids=["async", "sync"])
def test_bsp_resume_continues_state(tmp_path, mesh8, checkpoint_async):
    """Train 2 epochs with checkpointing; resume restores params exactly
    (parametrized over the async/sync writer — ISSUE 3)."""
    from theanompi_tpu import BSP

    cfg = {"verbose": False, "print_freq": 4,
           "checkpoint_dir": str(tmp_path / "ck"),
           "checkpoint_async": checkpoint_async}
    rule = BSP(config=cfg)
    rule.init(devices=8, modelfile="theanompi_tpu.models.wide_resnet",
              modelclass="WideResNet", model_config=dict(TINY))
    rule.wait()
    params_after = jax.tree.map(np.asarray, rule.trainer.params)
    iters_after = rule.trainer.iteration

    rule2 = BSP(config={**cfg, "resume": True})
    rule2.init(devices=8, modelfile="theanompi_tpu.models.wide_resnet",
               modelclass="WideResNet", model_config=dict(TINY))
    t2 = rule2.trainer
    assert t2.epoch == TINY["n_epochs"], "resume should start after last epoch"
    assert t2.iteration == iters_after
    for a, b in zip(jax.tree.leaves(t2.params), jax.tree.leaves(params_after)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # wait() is a no-op now (all epochs done) and must not crash
    rule2.wait()


@pytest.mark.slow
def test_easgd_checkpoint_includes_center(tmp_path):
    from theanompi_tpu import EASGD

    cfg = {"verbose": False, "tau": 2, "scale_lr": False,
           "checkpoint_dir": str(tmp_path / "ck")}
    rule = EASGD(config=cfg)
    rule.init(devices=8, modelfile="theanompi_tpu.models.wide_resnet",
              modelclass="WideResNet", model_config={**TINY, "n_epochs": 1})
    rule.wait()
    center = jax.tree.map(np.asarray, rule.trainer.center)

    rule2 = EASGD(config={**cfg, "resume": True})
    rule2.init(devices=8, modelfile="theanompi_tpu.models.wide_resnet",
               modelclass="WideResNet", model_config={**TINY, "n_epochs": 1})
    for a, b in zip(jax.tree.leaves(rule2.trainer.center),
                    jax.tree.leaves(center)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_launcher_kv_parsing():
    from theanompi_tpu.launcher import _parse_kv

    d = _parse_kv(["lr=0.1", "lrn=False", "stage_blocks=(1,1,1,1)",
                   "name=foo"])
    assert d == {"lr": 0.1, "lrn": False, "stage_blocks": (1, 1, 1, 1),
                 "name": "foo"}
    with pytest.raises(SystemExit):
        _parse_kv(["novalue"])


def _launch_subprocess(tmp_path, cache_dir, tag):
    """One tmlauncher subprocess on a 4-virtual-device CPU mesh with the
    compile cache placed by JAX_COMPILATION_CACHE_DIR + telemetry; -> its
    ``tmlauncher: compiles`` counters as a dict."""
    import re
    import subprocess
    import sys

    from theanompi_tpu.telemetry.sink import read_events, sink_files

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    # the CPU backend keeps jax's 1 s caching floor (setup_compile_cache);
    # lift it through jax's own variables so "compiled nothing new" is
    # exact here as it is on the chip
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    tel = str(tmp_path / f"tel_{tag}")
    out = subprocess.run(
        [sys.executable, "-m", "theanompi_tpu.launcher",
         "--rule", "BSP", "--devices", "4",
         "--modelfile", "theanompi_tpu.models.wide_resnet",
         "--modelclass", "WideResNet",
         "--set", "depth=10", "--set", "widen=1", "--set", "batch_size=4",
         "--set", "image_size=8", "--set", "n_train=16", "--set", "n_val=8",
         "--set", "n_epochs=1", "--set", "precision='fp32'",
         "--set", "verbose=False", "--telemetry-dir", tel],
        env=env, check=True, timeout=480, capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ).stdout
    gauges = [e["value"] for p in sink_files(tel) for e in read_events(p)
              if e.get("kind") == "gauge"
              and e.get("name") == "compile.first_step_s"]
    assert len(gauges) == 1, f"expected one first-compile gauge, got {gauges}"
    assert f"compile_cache={cache_dir}" in out
    line = re.search(r"^tmlauncher: compiles (.*)$", out, re.M).group(1)
    return {k: float(v) for k, v in (kv.split("=") for kv in line.split())}


def test_compile_cache_smoke(tmp_path):
    """ISSUE 3 CI satellite: two launcher subprocesses sharing a compile
    cache — the first populates it, the second compiles NOTHING new
    (every compile request is a cache hit; the launcher's own counters
    say so, which is also what chip_smoke.py's second run shows on the
    chip).  Subprocesses, not in-process runs: the persistent-cache win is
    precisely the cross-process one."""
    cache = tmp_path / "ccache"
    cold = _launch_subprocess(tmp_path, cache, "cold")
    assert cold["compiled"] > 0 and cold["hits"] < cold["requests"]
    entries = [f for f in os.listdir(cache) if f.endswith("-cache")]
    assert entries, "first run did not populate the compile cache"
    warm = _launch_subprocess(tmp_path, cache, "warm")
    assert warm["compiled"] == 0 and warm["hits"] == warm["requests"] > 0, (
        f"second run compiled anew: cold {cold} -> warm {warm}")


@pytest.mark.slow
def test_launcher_end_to_end(tmp_path, capsys):
    rc = tm_main([
        "--rule", "BSP", "--devices", "4",
        "--modelfile", "theanompi_tpu.models.wide_resnet",
        "--modelclass", "WideResNet",
        "--set", "depth=10", "--set", "widen=1", "--set", "batch_size=8",
        "--set", "image_size=16", "--set", "n_train=64", "--set", "n_val=32",
        "--set", "n_epochs=1", "--set", "precision='fp32'",
        "--record-dir", str(tmp_path / "rec"),
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "tmlauncher: done" in out
    assert os.path.exists(tmp_path / "rec" / "summary.json")
