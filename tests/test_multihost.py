"""Multi-host (multi-controller) integration: 2 jax.distributed processes.

VERDICT.md round-1 missing #2: the reference ran N real processes under
mpirun (SURVEY.md §3.1); round 1 had exactly one tested controller.  Here
two OS processes join a jax.distributed CPU runtime (Gloo collectives),
build one 8-device mesh spanning both, train BSP with sync-BN, checkpoint,
and resume — with process 1's checkpoint dir EMPTY, proving the resume
decision and arrays flow from process 0 (ADVICE.md: the non-shared-FS
desync).
"""

import os
import socket
import subprocess
import sys

import pytest

WORKER = os.path.join(os.path.dirname(__file__), "_multihost_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_two_process_distributed_bsp(tmp_path):
    port = _free_port()
    dir0 = str(tmp_path / "ckpt_proc0")
    dir1 = str(tmp_path / "ckpt_proc1")  # stays empty: proc 0 is authoritative
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": os.path.dirname(os.path.dirname(WORKER)),
    }
    # no persistent compile cache for the two-process workers (they never
    # had one: the session fixture's variable is now jax's own name)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, str(pid), str(port), dir0, dir1],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env,
        )
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=600)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("multihost workers timed out:\n" + "\n---\n".join(outs))
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} failed:\n{out[-3000:]}"
        assert f"MULTIHOST_OK pid={pid}" in out, out[-3000:]
        assert f"MULTIHOST_RULES_OK pid={pid}" in out, out[-3000:]
    # proc 1 never wrote a checkpoint; proc 0 did
    assert any(f.startswith("ckpt_e") for f in os.listdir(dir0))
    assert not os.path.exists(os.path.join(dir1, "latest.json"))
