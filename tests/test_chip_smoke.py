"""``chip_smoke.py`` and the one-process-per-chip rule (ISSUE 21).

The smoke's real run needs the chip and goes through the chip tool; what
tier-1 can lock on the CPU is the contract around it:

- with no accelerator (this sandbox) it exits non-zero, says why in one
  line and prints no result;
- alone in a directory it exits non-zero;
- its parent process is stdlib-only (a parent that has touched jax holds
  the chip and its children cannot get it);
- the explicit ``--rehearse-cpu`` run drives every phase end to end at
  toy widths, kernels under the interpreter, and labels itself a
  rehearsal on the CPU;
- the supervising parents and the loader workers never open a backend —
  probed with a ``JAX_PLATFORMS`` no backend answers to, under which any
  backend initialisation raises.
"""

import ast
import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(cmd, cwd=REPO, timeout=600, **env_over):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env.update(env_over)
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=timeout)


def test_smoke_without_a_chip_fails_with_one_line():
    out = _run([sys.executable, SMOKE], JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    (line,) = [ln for ln in out.stderr.splitlines()
               if ln.startswith("chip_smoke:")]
    assert "platform='cpu'" in line and "no accelerator" in line


def test_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    out = _run([sys.executable, str(tmp_path / "chip_smoke.py")],
               cwd=str(tmp_path))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no theanompi_tpu package" in out.stderr


def test_smoke_parent_is_stdlib_only():
    tree = ast.parse(open(SMOKE).read())
    roots = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            roots |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            roots.add((node.module or "").split(".")[0])
    assert roots <= set(sys.stdlib_module_names) | {"__future__"}, roots


def test_smoke_cpu_rehearsal_runs_every_phase(tmp_path):
    """The one explicit CPU rehearsal: tmlauncher (LM with the flash
    kernels interpreted, ResNet-50), tmserve plain and int8 with the
    paged-decode and fused-int8 kernels interpreted, and the decode (one
    K/V head a query head, and grouped) and state-update parity checks — each a fresh child, none of it a result
    for the chip."""
    # one CPU device: the session's 8 virtual devices would add the
    # multichip phases (they run on the four-chip host, not in tier-1)
    out = _run([sys.executable, SMOKE, "--rehearse-cpu"], timeout=900,
               JAX_PLATFORMS="cpu", XLA_FLAGS="")
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last == {"rehearsal": True, "ok": True,
                    "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    for phase in ("lm_train", "resnet_train", "serve_bf16", "serve_int8",
                  "decode_parity", "decode_parity_grouped",
                  "state_update_parity"):
        assert f"chip_smoke: {phase} ok on platform=cpu" in out.stdout
    assert "multichip phases skipped" in out.stdout
    assert '"attention": "pallas_interpret"' in out.stdout
    assert '"decode_attention": "kernel_interpret"' in out.stdout
    assert '"decode_dequantized": 0' in out.stdout
    assert '"state_update": "kernel_interpret"' in out.stdout


#: a platform name no backend answers to: ``jax.devices()`` — any backend
#: initialisation — raises under it, imports do not
NO_BACKEND = "no_such_platform"


def test_parents_and_workers_import_without_opening_a_backend():
    code = (
        "import theanompi_tpu.launcher, theanompi_tpu.serving.cli\n"
        "import theanompi_tpu.resilience.replica, theanompi_tpu.fleet.jobs\n"
        "import theanompi_tpu.router.cli\n"
        # what a spawned loader worker imports (shm_loader._worker)
        "import theanompi_tpu.models.data.shm_loader\n"
        "import theanompi_tpu.models.data.imagenet\n"
        "import theanompi_tpu.models.data.stream\n"
        "import jax\n"
        "try:\n    jax.devices()\n"
        "except RuntimeError:\n    print('probe armed')\n")
    out = _run([sys.executable, "-c", code], JAX_PLATFORMS=NO_BACKEND)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "probe armed" in out.stdout


def test_supervising_launcher_parent_never_opens_a_backend(tmp_path):
    """``tmlauncher --supervise``: the child needs the backend and dies on
    the unusable platform; the parent must survive to record that — a
    parent that touched jax would have died first (and, on the chip,
    would hold it against its own child)."""
    ck = str(tmp_path / "ck")
    out = _run([sys.executable, "-m", "theanompi_tpu.launcher",
                "--supervise", "--max-restarts", "0", "--backoff-base",
                "0.01", "--checkpoint-dir", ck, "--set", "depth=10"],
               JAX_PLATFORMS=NO_BACKEND)
    art = json.load(open(os.path.join(ck, "resilience.json")))
    assert len(art["attempts"]) == 1
    assert art["attempts"][0]["cause"].startswith("crash")
    assert "tmlauncher: error: environment:" in out.stderr
    assert "Traceback" not in out.stderr
