"""Per-op roofline profiling from the TPU's own trace (VERDICT r2 #1).

BASELINE.md claims the ResNet-50 step is HBM-bound; until round 3 that was
asserted from aggregate cost analysis, not shown.  This tool produces the
evidence: it runs the compiled train step under ``jax.profiler.trace``,
parses the xplane protobuf the TPU runtime writes (per-HLO-op device
durations, with the op's full HLO text embedded in the event name), and
joins three sources per op:

- **time**: device duration summed over the profiled steps (ground truth);
- **bytes**: operand + result tensor sizes parsed from the op's HLO text —
  an HBM-traffic estimate (exact for fusions, whose top-level operands and
  results are precisely what crosses HBM; VMEM-resident reuse inside a
  fusion never appears, which is the point);
- **flops**: ``dot``/``convolution`` instructions counted from the compiled
  module's text, including those INSIDE fused computations (attributed to
  the calling fusion op — the event text alone hides them).

Each op then gets achieved GB/s and TFLOP/s against the chip's peaks and a
verdict: ``hbm`` (>= 50% of peak bandwidth), ``mxu`` (>= 50% of peak
compute), or ``latency/other``.  The summary answers the roofline question
directly: what fraction of step time sits on ops already near a roof.

Collective ops (``all-reduce``/``all-gather``/``collective-permute``/
``all-to-all``) are tagged so the same trace yields the communication share
— the profiler-backed comm measurement VERDICT r2 #5 asks for (the old
differential method is noise-dominated on the virtual mesh).

CLI::

    python -m theanompi_tpu.utils.roofline --model resnet50 --out ROOFLINE.json
"""

from __future__ import annotations

import glob
import json
import os
import re
import tempfile
from collections import defaultdict

import numpy as np

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_SHAPE_RE = re.compile(r"\b(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64)\[([0-9,]*)\]")

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")


def _numel(dims: str) -> int:
    if not dims:
        return 1
    return int(np.prod([int(d) for d in dims.split(",")]))


def _text_bytes(text: str) -> int:
    """Sum of all tensor-literal sizes in an HLO snippet (result+operands)."""
    return sum(_DTYPE_BYTES[m.group(1)] * _numel(m.group(2))
               for m in _SHAPE_RE.finditer(text))


def _operand_names(line: str) -> list[str]:
    """Operand instruction names of an HLO ``dot``/``convolution``
    (``dot(%a, %b)`` — XLA spells operands by bare name)."""
    m = re.search(r"\b(?:dot|convolution)\(([^)]*)\)", line)
    if not m:
        return []
    return [t.strip().lstrip("%") for t in m.group(1).split(",") if t.strip()]


def _dot_flops(line: str, shapes: dict[str, str]) -> int:
    """2*M*N*K*batch for an HLO ``dot``; operand shapes via symbol table."""
    ops = _operand_names(line)
    if len(ops) < 2:
        return 0
    lhs_s, rhs_s = shapes.get(ops[0]), shapes.get(ops[1])
    if lhs_s is None or rhs_s is None:
        return 0
    lhs_dims = [int(d) for d in lhs_s.split(",")] if lhs_s else []
    con = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", line)
    bat = re.search(r"lhs_batch_dims=\{([0-9,]*)\}", line)
    con_idx = [int(i) for i in con.group(1).split(",")] if con and con.group(1) else []
    bat_idx = [int(i) for i in bat.group(1).split(",")] if bat and bat.group(1) else []
    k = int(np.prod([lhs_dims[i] for i in con_idx])) if con_idx else 1
    b = int(np.prod([lhs_dims[i] for i in bat_idx])) if bat_idx else 1
    m = _numel(lhs_s) // max(k * b, 1)
    n = _numel(rhs_s) // max(k * b, 1)
    return 2 * b * m * n * k


def _win_field(line: str, key: str, ndim: int, default: int):
    m = re.search(rf"\b{key}=([0-9x_\-]+)", line)
    if not m:
        return [(default, default)] * ndim if key == "pad" else [default] * ndim
    parts = m.group(1).split("x")
    if key == "pad":
        # pad entries are "lo_hi"; a bare "N" means symmetric N
        return [tuple(int(v) for v in p.split("_")) if "_" in p
                else (int(p), int(p)) for p in parts]
    return [int(p) for p in parts]


def _conv_flops(line: str, shapes: dict[str, str]) -> int:
    """Exact 2*MACs for an HLO ``convolution``, any form (fwd/dgrad/wgrad).

    MACs are separable per spatial dim: for each output position, count the
    window taps that land inside the (lhs-dilated) input on real (non-hole)
    elements; the total is the product of per-dim sums times batch and the
    feature dims.  Grad convs' huge padded/dilated windows therefore count
    their TRUE work (a naive out*window*feat product over-counts them by
    the stride^2-and-more factors the zeros absorb).  The kernel ``i`` dim
    is per-group in HLO, so grouped convs need no extra division.
    """
    out = _SHAPE_RE.search(line)
    dl = re.search(r"dim_labels=(\w+)_(\w+)->(\w+)", line)
    win = re.search(r"window=\{size=([0-9x]+)", line)
    ops = _operand_names(line)
    if not (out and dl and len(ops) >= 2):
        return 0
    lhs_s, rhs_s = shapes.get(ops[0]), shapes.get(ops[1])
    if lhs_s is None or rhs_s is None:
        return 0
    lhs_spec, rhs_spec, out_spec = dl.groups()
    lhs_dims = [int(d) for d in lhs_s.split(",")]
    rhs_dims = [int(d) for d in rhs_s.split(",")]
    out_dims = [int(d) for d in out.group(2).split(",")]
    # matmuls lowered to HLO convolution carry NO window (dim_labels like
    # bf_io->bf): zero spatial dims, taps product stays 1
    sizes = [int(x) for x in win.group(1).split("x")] if win else []
    nd = len(sizes)
    strides = _win_field(line, "stride", nd, 1)
    pads = _win_field(line, "pad", nd, 0)
    lhs_dil = _win_field(line, "lhs_dilate", nd, 1)
    rhs_dil = _win_field(line, "rhs_dilate", nd, 1)
    taps_total = 1
    for d in range(nd):
        lab = str(d)
        in_sp = lhs_dims[lhs_spec.index(lab)]
        out_sp = out_dims[out_spec.index(lab)]
        k, st, (plo, _), ld, rd = sizes[d], strides[d], pads[d], lhs_dil[d], rhs_dil[d]
        in_eff = (in_sp - 1) * ld + 1
        base = np.arange(out_sp)[:, None] * st - plo
        ks = base + np.arange(k)[None, :] * rd
        valid = (ks >= 0) & (ks < in_eff) & (ks % ld == 0)
        taps_total *= int(valid.sum())
    b = lhs_dims[lhs_spec.index("b")]
    i = rhs_dims[rhs_spec.index("i")]
    of = out_dims[out_spec.index("f")]
    return 2 * b * i * of * taps_total


def hlo_flops_map(hlo_text: str) -> dict[str, int]:
    """instr-name -> flops for dots/convs, fused ones attributed to their
    calling fusion instruction."""
    lines = hlo_text.splitlines()
    # pass 1: symbol table (instruction name -> result shape dims string)
    shapes: dict[str, str] = {}
    defn = re.compile(r"(?:ROOT\s+)?%?([\w.\-]+)\s*=")
    for line in lines:
        im = defn.match(line.strip())
        if not im:
            continue
        sm = _SHAPE_RE.search(line)
        if sm:
            shapes.setdefault(im.group(1), sm.group(2))
    # pass 2: flops per dot/conv, attributed through fused computations
    comp_flops: dict[str, int] = defaultdict(int)
    flops: dict[str, int] = defaultdict(int)
    cur_comp = None
    fusion_calls: list[tuple[str, str]] = []
    for line in lines:
        ls = line.strip()
        if ls.endswith("{") and "(" in ls and "=" not in ls.split("(")[0]:
            cur_comp = ls.split()[0].lstrip("%").split("(")[0]
            continue
        if ls == "}":
            cur_comp = None
            continue
        im = defn.match(ls)
        if not im:
            continue
        name = im.group(1)
        f = 0
        if " dot(" in ls:
            f = _dot_flops(ls, shapes)
        elif " convolution(" in ls:
            f = _conv_flops(ls, shapes)
        if f:
            if cur_comp and cur_comp != "ENTRY":
                comp_flops[cur_comp] += f
            flops[name] += f
        cm = re.search(r"calls=%?([\w.\-]+)", ls)
        if cm and " fusion(" in ls:
            fusion_calls.append((name, cm.group(1)))
    for instr, comp in fusion_calls:
        if comp in comp_flops:
            flops[instr] += comp_flops[comp]
    return dict(flops)


def _load_xplane_ops(logdir: str):
    """-> list of (op_name, hlo_text, duration_ps) from the newest xplane."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {logdir}")
    xs = xplane_pb2.XSpace()
    with open(paths[-1], "rb") as f:
        xs.ParseFromString(f.read())
    out = []
    for plane in xs.planes:
        if "TPU" not in plane.name and "device" not in plane.name.lower():
            continue
        emeta = {k: v.name for k, v in plane.event_metadata.items()}
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                text = emeta.get(ev.metadata_id, "")
                nm = text.split(" = ")[0].strip().lstrip("%") if " = " in text else text
                out.append((nm, text, int(ev.duration_ps)))
    return out


def _op_kind(text: str) -> str:
    for c in COLLECTIVE_KINDS:
        if f" {c}(" in text or f" {c}-start(" in text:
            return "collective"
    if " convolution(" in text:
        return "conv"
    if " dot(" in text:
        return "dot"
    if " fusion(" in text:
        if "convolution_fusion" in text or "conv" in text.split(" = ")[0]:
            return "conv-fusion"
        return "fusion"
    if " copy(" in text:
        return "copy"
    if " custom-call(" in text:
        return "custom-call"
    if " while(" in text:
        return "while"
    return "other"


def profile_step(trainer, batch, steps: int = 4, lr: float = 0.01,
                 peak_flops: float | None = None,
                 peak_gbps: float | None = None,
                 logdir: str | None = None) -> dict:
    """Profile ``steps`` dispatched train steps; -> the roofline artifact.

    The step must already be compiled+warmed (first call outside the trace).
    Ops are aggregated by name across steps and normalized per step.
    """
    import jax

    logdir = logdir or tempfile.mkdtemp(prefix="roofline_")
    m = trainer.train_iter(batch, lr=lr)   # warm outside the trace
    float(m["cost"])
    with jax.profiler.trace(logdir):
        for _ in range(steps):
            m = trainer.train_iter(batch, lr=lr)
        float(m["cost"])  # single sync, run()-loop dispatch pattern

    try:
        hlo = trainer.compiled_step_text(batch)
    except Exception:  # lint: swallow-ok — FLOP map degrades to empty
        hlo = ""
    fmap = hlo_flops_map(hlo) if hlo else {}

    agg: dict[str, dict] = {}
    for nm, text, dur_ps in _load_xplane_ops(logdir):
        a = agg.setdefault(nm, {"name": nm, "kind": _op_kind(text),
                                "calls": 0, "time_ps": 0,
                                "bytes": _text_bytes(text)})
        a["calls"] += 1
        a["time_ps"] += dur_ps

    # 'while' wraps its body ops (double count) — keep it but mark it
    rows = []
    total_ps = sum(a["time_ps"] for a in agg.values() if a["kind"] != "while")
    for a in agg.values():
        t_s = a["time_ps"] / 1e12
        per_step_calls = a["calls"] / steps
        fl = fmap.get(a["name"], 0) * per_step_calls * steps
        by = a["bytes"] * a["calls"]
        row = {
            "op": a["name"], "kind": a["kind"],
            "calls_per_step": round(per_step_calls, 2),
            "time_ms_per_step": round(t_s / steps * 1e3, 4),
            "time_share": round(a["time_ps"] / total_ps, 4) if total_ps else 0.0,
            "bytes_mb_per_step": round(by / steps / 2**20, 2),
            "gflops_per_step": round(fl / steps / 1e9, 2),
        }
        if t_s > 0:
            row["achieved_gbps"] = round(by / t_s / 1e9, 1)
            row["achieved_tflops"] = round(fl / t_s / 1e12, 2)
            frac = 0.0
            if peak_gbps:
                frac = max(frac, row["achieved_gbps"] / peak_gbps)
            if peak_flops:
                frac = max(frac, row["achieved_tflops"] * 1e12 / peak_flops)
            row["roof_frac"] = round(min(frac, 1.0), 3)
            bound = "latency/other"
            if peak_gbps and row["achieved_gbps"] >= 0.5 * peak_gbps:
                bound = "hbm"
            if peak_flops and row["achieved_tflops"] * 1e12 >= 0.5 * peak_flops:
                bound = "mxu"
            row["bound"] = bound
        rows.append(row)
    rows.sort(key=lambda r: -r["time_ms_per_step"])

    body = [r for r in rows if r["kind"] != "while"]
    comm_ps = sum(r["time_ms_per_step"] for r in body if r["kind"] == "collective")
    step_ms = total_ps / steps / 1e9
    at_half = sum(r["time_share"] for r in body if r.get("roof_frac", 0) >= 0.5)
    at_80 = sum(r["time_share"] for r in body if r.get("roof_frac", 0) >= 0.8)
    return {
        "steps_profiled": steps,
        "device_step_ms": round(step_ms, 3),
        "total_gflops_per_step": round(sum(r["gflops_per_step"] for r in body), 1),
        "total_bytes_gb_per_step": round(
            sum(r["bytes_mb_per_step"] for r in body) / 1024, 3),
        "bytes_note": ("bytes are operand+result sizes per op — an HBM "
                       "upper bound (producer+consumer both count a "
                       "crossing; short-lived VMEM residency not modeled)"),
        "comm_share": round(comm_ps / step_ms, 4) if step_ms else 0.0,
        "time_share_at_half_roof": round(at_half, 4),
        "time_share_at_80pct_roof": round(at_80, 4),
        "peak_tflops": round(peak_flops / 1e12, 1) if peak_flops else None,
        "peak_gbps": peak_gbps,
        "ops": rows[:60],
    }


def main(argv=None):
    import argparse

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--model", default="resnet50")
    p.add_argument("--steps", type=int, default=4)
    p.add_argument("--out", default="ROOFLINE.json")
    args = p.parse_args(argv)

    import jax

    import bench as benchmod  # repo-root bench.py: shared model builders
    from theanompi_tpu.telemetry.metrics import device_peaks

    device = benchmod.require_accelerator()
    peaks = device_peaks(device["kind"])  # unknown device: an error
    trainer, model = benchmod.build_trainer(args.model)
    batch = next(iter(model.data.train_batches(trainer.global_batch, 0, seed=0)))
    from theanompi_tpu.utils.helper_funcs import shard_batch

    placed = shard_batch(trainer.mesh, batch, spec=trainer.batch_spec)
    jax.block_until_ready(placed)
    art = profile_step(trainer, placed, steps=args.steps,
                       peak_flops=peaks["bf16_tflops"] * 1e12,
                       peak_gbps=peaks["hbm_gbps"])
    art["model"] = args.model
    art["device"] = device
    with open(args.out + ".tmp", "w") as f:
        json.dump(art, f, indent=1)
    os.replace(args.out + ".tmp", args.out)
    print(json.dumps({k: art[k] for k in
                      ("model", "device_step_ms", "total_gflops_per_step",
                       "total_bytes_gb_per_step", "comm_share",
                       "time_share_at_half_roof",
                       "time_share_at_80pct_roof")}))
    for r in art["ops"][:12]:
        print(f"{r['time_ms_per_step']:9.3f} ms  {r['time_share']:6.1%}  "
              f"{r['kind']:11s} {r.get('achieved_gbps', 0):8.0f} GB/s "
              f"{r.get('achieved_tflops', 0):7.2f} TF/s  {r['op'][:48]}")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
