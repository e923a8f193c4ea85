#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls —
``python -m theanompi_tpu.launcher`` (tmlauncher) and ``python -m
theanompi_tpu.serving`` (tmserve) — at the full width of models the repo
supports, with depth and run length cut and weights random from a seed:

1. ``lm_train``     TransformerLM, dim 512 x 8 heads x 8 layers, T 2048,
                    vocab 32768, batch 16, bf16, fused loss: a few steps of
                    one epoch plus its validation pass.  Attention must
                    resolve to the compiled pallas flash kernels.
2. ``resnet_train`` ResNet-50 at batch 256, bf16: the same.
3. ``serve_bf16``   tmserve, dim 2048 x 16 heads (head_dim 128) x 8 layers,
                    vocab 32768, context 2048: 8 requests of 300-token
                    prompts, 32 new tokens each.  Decode attention must
                    resolve to the compiled paged kernel.
4. ``serve_int8``   the same with ``--quantize-int8``: every int8 leaf of
                    the decode step must go to the fused matmul.
5. ``decode_parity`` one decode-attention step at the served head geometry
                    through the kernel and through
                    ``PagedKVCache.attend_decode``'s pure-JAX path, at
                    layer 1 of a two-layer pool (the kernel's layer index
                    is checked too).
   ``decode_parity_grouped`` the same through the kernel of grouped pools
                    and the grouped gather at the two served geometries:
                    48 query heads over 8 K/V heads with a 16 384-token
                    table, 32 over 2 with a 4096-token one.
6. ``state_update_parity`` one decode step of a Mamba-2 layer at the served
                    state geometry (128 heads in 8 groups, 64 x 128 float32
                    a head) through the state-update kernel and through
                    ``Mamba2.decode``'s plain lines, at layer 1 of a
                    two-layer pool.
7. ``multichip``    only where jax reports >= 4 chips: tmlauncher
                    ``--devices 4`` on both training models and
                    ``__graft_entry__.py dryrun 4`` on the real devices;
                    all four must hold shards.

This process is stdlib-only and never imports jax: a parent that has
touched jax holds the chip and its children cannot get it.  Every phase is
a fresh child, one at a time, and each reports the device it ran on; any
phase that fails, or that ran on something other than a TPU, fails the
run.  Set-up (compile) time is reported apart from step/request time and
no rate or utilisation is derived from either.

The last stdout line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
A failing run exits non-zero and prints no such line.  The per-phase
report also goes to ``chiprun_out/chip_smoke.json``.

``--rehearse-cpu`` is the explicit rehearsal for a machine without a chip:
the same phases at toy widths with the kernels under the pallas
interpreter.  It is never chosen implicitly and its last line carries
``"rehearsal": true`` with the CPU device.
"""

from __future__ import annotations

import argparse
import ast
import json
import math
import os
import re
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out")

#: the whole run must end inside the driver's 1200 s, compilation included
DEADLINE_S = 1140.0

LM = "theanompi_tpu.models.transformer_lm"
RESNET = "theanompi_tpu.models.resnet50"

#: model widths per mode: (lm train, resnet train, served lm, serve flags,
#: parity geometries).  The chip widths are round r4's trainer configs
#: and the narrowest server the paged-decode gate admits in bf16
#: (heads % 16 == 0, head_dim % 128 == 0).
WIDTHS = {
    "chip": {
        "steps": 4,
        "lm": dict(dim=512, heads=8, n_layers=8, seq_len=2048, vocab=32768,
                   batch_size=16, dropout=0.0),
        "resnet": dict(batch_size=256, shard_size=256),
        "serve": dict(dim=2048, heads=16, n_layers=8, seq_len=2048,
                      vocab=32768, dropout=0.0, precision="bf16"),
        "serve_args": ["--requests", "8", "--prompt-len", "300",
                       "--max-new-tokens", "32", "--max-batch", "8",
                       "--decode-kernel", "auto"],
        "parity": [dict(heads=16, head_dim=128, block_size=16, max_batch=8,
                        max_context=2048, dtype="bfloat16",
                        decode_impl="kernel")],
        "parity_grouped": [
            dict(heads=48, kv_heads=8, head_dim=128, block_size=16,
                 max_batch=8, max_context=16384, dtype="bfloat16",
                 decode_impl="kernel"),
            dict(heads=32, kv_heads=2, head_dim=128, block_size=16,
                 max_batch=8, max_context=4096, dtype="bfloat16",
                 decode_impl="kernel")],
        "state_parity": dict(heads=128, groups=8, head_dim=64, state=128,
                             max_batch=4, impl="kernel"),
        "expect": {"platform": "tpu", "attention": "pallas",
                   "decode": "kernel", "weights_held": "bfloat16"},
    },
    "rehearsal": {
        "steps": 2,
        "lm": dict(dim=64, heads=1, n_layers=1, seq_len=128, vocab=8192,
                   batch_size=2, dropout=0.0, attn_impl="pallas",
                   precision="fp32"),
        "resnet": dict(batch_size=2, shard_size=4, image_size=32,
                       store_size=40, stage_blocks=(1, 1, 1, 1),
                       n_classes=4, precision="fp32"),
        "serve": dict(dim=64, heads=2, n_layers=1, seq_len=64, vocab=256,
                      dropout=0.0, precision="fp32"),
        "serve_args": ["--requests", "2", "--prompt-len", "10",
                       "--max-new-tokens", "4", "--max-batch", "2",
                       "--block-size", "4", "--decode-kernel", "on"],
        "parity": [dict(heads=2, head_dim=32, block_size=4, max_batch=2,
                        max_context=32, dtype="float32",
                        decode_impl="kernel_interpret")],
        "parity_grouped": [
            dict(heads=6, kv_heads=2, head_dim=32, block_size=4, max_batch=8,
                 max_context=64, dtype="float32",
                 decode_impl="kernel_interpret")],
        "state_parity": dict(heads=8, groups=2, head_dim=8, state=128,
                             dim=32, max_batch=2, impl="kernel_interpret"),
        "expect": {"platform": "cpu", "attention": "pallas_interpret",
                   "decode": "kernel_interpret", "weights_held": "float32"},
    },
}


class PhaseFailed(Exception):
    pass


def run_child(name: str, cmd: list[str],
              deadline: float) -> tuple[str, float]:
    """Run one phase child to completion in its own process group; ->
    (stdout, wall seconds).  Killed with its whole group at the deadline,
    so nothing this script started outlives it."""
    left = deadline - time.monotonic()
    if left <= 0:
        raise PhaseFailed(f"{name}: no time left before the deadline")
    t0 = time.monotonic()
    proc = subprocess.Popen(
        cmd, cwd=HERE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, start_new_session=True)
    try:
        out, err = proc.communicate(timeout=left)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        out, err = proc.communicate()
        raise PhaseFailed(f"{name}: killed at the deadline after "
                          f"{time.monotonic() - t0:.0f}s; stderr tail: "
                          f"{err[-1500:]}") from None
    except BaseException:
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait()
        raise
    wall = time.monotonic() - t0
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, f"chip_smoke.{name}.log"), "w") as f:
        f.write(f"$ {' '.join(cmd)}\n--- stdout ---\n{out}\n"
                f"--- stderr ---\n{err}\n")
    if proc.returncode != 0:
        raise PhaseFailed(f"{name}: exit {proc.returncode}; stderr tail: "
                          f"{err[-1500:]}")
    return out, wall


def sets(cfg: dict) -> list[str]:
    out = []
    for k, v in cfg.items():
        out += ["--set", f"{k}={v!r}" if isinstance(v, str) else f"{k}={v}"]
    return out


def check_device(name: str, got: dict, want: dict) -> None:
    if got != want:
        raise PhaseFailed(f"{name}: ran on {got}, the run started on {want}")


def check_device_line(name: str, out: str, want: dict) -> None:
    """The ``device platform=… kind=… count=…`` line tmlauncher and the
    dry run print must name the device the run started on."""
    m = _DEVICE_RE.search(out)
    if not m:
        raise PhaseFailed(f"{name}: no device line in the output")
    check_device(name, {"platform": m.group(1), "kind": m.group(2),
                        "count": int(m.group(3))}, want)


def check_shards(name: str, what: str, held: str, in_use: str, n: int,
                 device: dict) -> dict:
    """All ``n`` devices must hold shards, each with bytes in use (the CPU
    backend keeps no memory statistics and reports None)."""
    shards = {"devices": int(held), "bytes_in_use": ast.literal_eval(in_use)}
    if shards["devices"] != n or (device["platform"] == "tpu"
                                  and not all(shards["bytes_in_use"])):
        raise PhaseFailed(f"{name}: {what}: {held} of {n} devices hold "
                          f"shards, bytes_in_use {shards['bytes_in_use']}")
    return shards


# -- phases -------------------------------------------------------------------

def preflight(mode: str, deadline: float) -> dict:
    """The device as jax reports it, from a child that exits (and so lets
    go of the chip) before any phase starts."""
    out, _ = run_child("preflight", [
        sys.executable, "-c",
        "import json; from theanompi_tpu.parallel.mesh import "
        "device_summary; print(json.dumps(device_summary()))"], deadline)
    device = json.loads(out.strip().splitlines()[-1])
    want = WIDTHS[mode]["expect"]["platform"]
    if device["platform"] != want:
        raise PhaseFailed(
            f"jax reports platform={device['platform']!r} "
            f"({device['kind']}, {device['count']} device(s)); this run "
            f"needs {want!r}"
            + ("" if mode == "rehearsal"
               else " — there is no accelerator to smoke"))
    return device


_DEVICE_RE = re.compile(r"^(?:tmlauncher|dryrun): device platform=(\S+) "
                        r"kind='([^']*)' count=(\d+)", re.M)
_PATHS_RE = re.compile(r"^tmlauncher: mesh (\{.*?\}) global_batch=(\d+) "
                       r"paths: (.*)$", re.M)
_ITER_RE = re.compile(r"^iter (\d+): cost (\S+) .*\| wait \S+ calc (\S+)s",
                      re.M)
_VAL_RE = re.compile(r"^epoch 0: val_cost (\S+)", re.M)
_COMPILES_RE = re.compile(r"^tmlauncher: compiles (.*)$", re.M)
_SHARDS_RE = re.compile(r"^tmlauncher: shards devices=(\d+) "
                        r"bytes_in_use=(\[.*\])$", re.M)


def train_phase(name: str, modelfile: str, modelclass: str, cfg: dict,
                steps: int, devices: int, device: dict,
                deadline: float) -> dict:
    """A few steps of one short epoch plus its validation pass, through
    tmlauncher; every step's loss must be finite."""
    per_worker = cfg["batch_size"]
    cfg = {**cfg, "n_train": per_worker * devices * steps,
           "n_val": per_worker * devices, "n_epochs": 1}
    out, wall = run_child(name, [
        sys.executable, "-m", "theanompi_tpu.launcher", "--rule", "BSP",
        "--devices", str(devices), "--modelfile", modelfile,
        "--modelclass", modelclass, *sets(cfg),
        "--rule-set", "print_freq=1"], deadline)
    check_device_line(name, out, device)
    m = _PATHS_RE.search(out)
    if not m:
        raise PhaseFailed(f"{name}: no mesh/paths line")
    paths = dict(kv.split("=", 1) for kv in m.group(3).split() if "=" in kv)
    iters = [(int(i), float(c), float(t)) for i, c, t in
             _ITER_RE.findall(out)]
    if [i for i, _, _ in iters] != list(range(1, steps + 1)):
        raise PhaseFailed(f"{name}: expected steps 1..{steps}, got "
                          f"{[i for i, _, _ in iters]}")
    bad = [(i, c) for i, c, _ in iters if not math.isfinite(c)]
    if bad:
        raise PhaseFailed(f"{name}: non-finite loss at (step, loss) {bad}")
    val = _VAL_RE.search(out)
    if not val or not math.isfinite(float(val.group(1))):
        raise PhaseFailed(f"{name}: no finite validation loss")
    if "tmlauncher: done." not in out:
        raise PhaseFailed(f"{name}: the launcher did not report done")
    compiles = _COMPILES_RE.search(out)
    shards = _SHARDS_RE.search(out)
    if not shards:
        raise PhaseFailed(f"{name}: no shards line")
    shards = check_shards(name, "parameters", *shards.groups(), devices,
                          device)
    return {
        "devices": devices, "mesh": m.group(1), "paths": paths,
        "losses": [c for _, c, _ in iters],
        "val_loss": float(val.group(1)),
        # the first step pays trace + compile (or the cache load)
        "setup_first_step_s": iters[0][2],
        "later_step_s": [t for _, _, t in iters[1:]],
        "compiles": compiles.group(1) if compiles else None,
        "shards": shards,
        "child_wall_s": round(wall, 1),
    }


def lm_phase(name, mode, devices, device, deadline):
    w = WIDTHS[mode]
    res = train_phase(name, LM, "TransformerLM", w["lm"], w["steps"],
                      devices, device, deadline)
    want = w["expect"]["attention"]
    if res["paths"].get("attention") != want:
        raise PhaseFailed(f"{name}: attention resolved to "
                          f"{res['paths'].get('attention')!r}, not {want!r}")
    if res["paths"].get("fused_loss") != "True":
        raise PhaseFailed(f"{name}: the fused loss is off")
    return res


def resnet_phase(name, mode, devices, device, deadline):
    w = WIDTHS[mode]
    return train_phase(name, RESNET, "ResNet50", w["resnet"], w["steps"],
                       devices, device, deadline)


def serve_phase(name: str, mode: str, int8: bool, device: dict,
                deadline: float) -> dict:
    w = WIDTHS[mode]
    cmd = [sys.executable, "-m", "theanompi_tpu.serving",
           "--modelfile", LM, "--modelclass", "TransformerLM",
           *sets(w["serve"]), *w["serve_args"], "--quiet"]
    if int8:
        cmd.append("--quantize-int8")
    out, wall = run_child(name, cmd, deadline)
    rep = json.loads(out.strip().splitlines()[-1])
    check_device(name, rep["device"], device)
    n_req = int(w["serve_args"][w["serve_args"].index("--requests") + 1])
    n_new = int(w["serve_args"][
        w["serve_args"].index("--max-new-tokens") + 1])
    if rep["terminal_states"].get("done") != n_req:
        raise PhaseFailed(f"{name}: terminal states "
                          f"{rep['terminal_states']}, wanted {n_req} done")
    if rep["generated_tokens"] != n_req * n_new:
        raise PhaseFailed(f"{name}: {rep['generated_tokens']} tokens "
                          f"generated, wanted {n_req * n_new}")
    paths = rep["paths"]
    if paths["decode_attention"] != w["expect"]["decode"]:
        raise PhaseFailed(f"{name}: decode attention resolved to "
                          f"{paths['decode_attention']!r}, not "
                          f"{w['expect']['decode']!r}")
    if mode == "chip" and set(paths["prefill_attention"].values()) != {
            "pallas"}:
        raise PhaseFailed(f"{name}: prefill attention "
                          f"{paths['prefill_attention']}, wanted pallas")
    if paths.get("weights_held") != w["expect"]["weights_held"]:
        raise PhaseFailed(f"{name}: the engine holds its weights in "
                          f"{paths.get('weights_held')!r}, not "
                          f"{w['expect']['weights_held']!r}")
    if int8:
        i8 = paths.get("int8_matmul") or {}
        if not i8.get("decode_fused") or i8.get("decode_dequantized"):
            raise PhaseFailed(f"{name}: int8 leaves in the decode step "
                              f"{i8}: wanted all fused, none dequantized")
    return {
        "paths": paths, "terminal_states": rep["terminal_states"],
        "generated_tokens": rep["generated_tokens"],
        "decode_steps": rep["decode_steps"],
        # model build + quantization before the first request
        "setup_before_loop_s": rep["setup_s"],
        # the loop's wall INCLUDES the compile of every program's first
        # use; compile_s is the part of the process spent compiling
        "loop_wall_s": rep["wall_s"], "compile": rep["compile"],
        "ttft_ms": rep["ttft_ms"], "decode_step_ms": rep["decode_step_ms"],
        "child_wall_s": round(wall, 1),
    }


def _parity_child(name: str, imports: str, call: str, device: dict,
                  deadline: float) -> tuple[dict, float]:
    """Run one kernel-against-plain-lines check in a fresh child: ``call``
    (an expression, after ``imports``) gives the check's report, a dict
    with ``ok``; -> (the report, wall seconds), on the expected device."""
    code = (
        "import json, jax, jax.numpy as jnp\n"
        "from theanompi_tpu.parallel.mesh import device_summary, "
        "setup_compile_cache\n"
        f"{imports}\n"
        "setup_compile_cache()\n"
        f"res = {call}\n"
        "print(json.dumps({'device': device_summary(), **res}))\n")
    out, wall = run_child(name, [sys.executable, "-c", code], deadline)
    res = json.loads(out.strip().splitlines()[-1])
    check_device(name, res.pop("device"), device)
    return res, wall


def parity_phase(name: str, mode: str, key: str, device: dict,
                 deadline: float) -> dict:
    """``decode_parity`` at each geometry of ``WIDTHS[mode][key]``, all in
    one child (``cases`` of the report)."""
    calls = ", ".join(
        "decode_parity(dtype=jnp.{}, **{!r})".format(
            kw["dtype"], {k: v for k, v in kw.items() if k != "dtype"})
        for kw in WIDTHS[mode][key])
    res, wall = _parity_child(
        name, "from theanompi_tpu.serving.kv_cache import decode_parity",
        f"{{'cases': [{calls}]}}", device, deadline)
    for case in res["cases"]:
        if not case["ok"]:
            raise PhaseFailed(
                f"{name}: kernel vs PagedKVCache.attend_decode fallback at "
                f"{case['heads']} heads over {case['kv_heads']}: max abs err "
                f"{case['max_abs_err']:.3g} > tolerance "
                f"{case['tolerance']:.3g} (finite={case['finite']})")
    return {**res, "child_wall_s": round(wall, 1)}


def state_parity_phase(name: str, mode: str, device: dict,
                       deadline: float) -> dict:
    res, wall = _parity_child(
        name, "from theanompi_tpu.ops.mamba2 import state_update_parity",
        f"state_update_parity(**{WIDTHS[mode]['state_parity']!r})",
        device, deadline)
    if not res["ok"]:
        raise PhaseFailed(
            f"{name}: state update through {res['state_update']!r} vs "
            f"Mamba2.decode's plain lines: output max abs err "
            f"{res['max_abs_err_out']:.3g} (tolerance "
            f"{res['tolerance_out']:.3g}), state "
            f"{res['max_abs_err_state']:.3g} (tolerance "
            f"{res['tolerance_state']:.3g}), finite={res['finite']}, other "
            f"layer unchanged={res['other_layer_unchanged']}")
    return {**res, "child_wall_s": round(wall, 1)}


_DRYRUN_RE = re.compile(r"^dryrun: (\S+) devices=(\d+) "
                        r"bytes_in_use=(\[.*\])$", re.M)


def dryrun_phase(name: str, n: int, device: dict, deadline: float) -> dict:
    out, wall = run_child(name, [
        sys.executable, os.path.join(HERE, "__graft_entry__.py"),
        "dryrun", str(n)], deadline)
    if "dryrun_multichip OK" not in out:
        raise PhaseFailed(f"{name}: the dry run did not report OK")
    check_device_line(name, out, device)
    rows = {comp: check_shards(name, comp, held, in_use, n, device)
            for comp, held, in_use in _DRYRUN_RE.findall(out)}
    if not rows:
        raise PhaseFailed(f"{name}: no per-composition shard lines")
    return {"compositions": rows, "child_wall_s": round(wall, 1)}


# -- driver -------------------------------------------------------------------

def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="explicit CPU rehearsal at toy widths (never a "
                   "result for the chip)")
    args = p.parse_args(argv)
    mode = "rehearsal" if args.rehearse_cpu else "chip"

    if not os.path.isdir(os.path.join(HERE, "theanompi_tpu")):
        print(f"chip_smoke: {HERE} holds no theanompi_tpu package — run "
              f"it from the root of a checkout", file=sys.stderr)
        return 2

    deadline = time.monotonic() + DEADLINE_S
    t_start = time.monotonic()
    report: dict = {"mode": mode, "phases": {}}
    try:
        device = report["device"] = preflight(mode, deadline)
        print(f"chip_smoke: device {json.dumps(device)}", flush=True)
        # (name, phase function, its arguments before device and deadline)
        phases = [
            ("lm_train", lm_phase, (mode, 1)),
            ("resnet_train", resnet_phase, (mode, 1)),
            ("serve_bf16", serve_phase, (mode, False)),
            ("serve_int8", serve_phase, (mode, True)),
            ("decode_parity", parity_phase, (mode, "parity")),
            ("decode_parity_grouped", parity_phase, (mode, "parity_grouped")),
            ("state_update_parity", state_parity_phase, (mode,)),
        ]
        if device["count"] >= 4:
            phases += [
                ("lm_train_4dev", lm_phase, (mode, 4)),
                ("resnet_train_4dev", resnet_phase, (mode, 4)),
                ("dryrun_4dev", dryrun_phase, (4,)),
            ]
        else:
            print(f"chip_smoke: multichip phases skipped: jax reports "
                  f"{device['count']} device(s), they need 4", flush=True)
            report["phases"]["multichip"] = {
                "skipped": f"{device['count']} device(s), needs 4"}
        for name, fn, args in phases:
            t0 = time.monotonic()
            res = fn(name, *args, device, deadline)
            report["phases"][name] = res
            print(f"chip_smoke: {name} ok on platform={device['platform']} "
                  f"kind={device['kind']!r} count={device['count']} in "
                  f"{time.monotonic() - t0:.0f}s: "
                  f"{json.dumps(res, sort_keys=True)}", flush=True)
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    except (OSError, ValueError, KeyError, IndexError) as e:
        # a child that printed something this script cannot read
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return 1
    report["wall_s"] = round(time.monotonic() - t_start, 1)
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "chip_smoke.json"), "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    print(f"chip_smoke: all phases passed in {report['wall_s']}s "
          f"(set-up and step times above are host clocks around fenced "
          f"work; no rate or utilisation is claimed)", flush=True)
    last = {"ok": True, "device": device}
    if mode == "rehearsal":
        last = {"rehearsal": True, **last}
    print(json.dumps(last), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
