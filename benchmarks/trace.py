"""The one reduction from a profiler trace to numbers.

``load_events`` turns the newest ``*.xplane.pb`` under a directory into
plain rows; ``reduce`` turns rows into busy/idle time, time per device
operation, kernel (custom-call) time, collective time and its exposed
part, and the idle gaps put down to the host span that covers them.  The
rows are plain lists so a small recorded trace can be kept as JSON and the
reduction checked on it without a chip.

What a v5e trace looks like (jax 0.9.0, libtpu 0.0.34): one plane
``/device:TPU:<n>`` per chip with lines ``XLA Modules`` (one event per
executed program), ``XLA Ops`` (one per executed HLO instruction, named by
its whole HLO text ``%name = shape opcode(operands)``; a ``while`` event
spans the events of its body) and ``Async XLA Ops`` (start-to-done spans of
asynchronous copies and collectives); the plane ``/host:CPU`` carries host
threads, ``TraceAnnotation`` spans among them.  All on one clock, in ns.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
#: events that span other events of the same line: not work of their own
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def load_events(directory: str, span_names=()) -> dict:
    """-> ``{"device": [[chip, line, name, start_ns, dur_ns], ...],
    "host": [[name, start_ns, dur_ns], ...]}`` of the newest trace under
    ``directory``.  Host rows are kept only for ``span_names`` (all
    annotations when empty would be every runtime event: too many)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {directory}")
    data = ProfileData.from_file(paths[-1])
    keep = set(span_names)
    device, host = [], []
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name in (OPS_LINE, ASYNC_LINE, MODULES_LINE):
                    for e in line.events:
                        device.append([chip, line.name, e.name,
                                       float(e.start_ns), float(e.duration_ns)])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in keep:
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
    return {"device": device, "host": host}


def op_name(text: str) -> tuple[str, str]:
    """HLO text of one event -> (instruction name, opcode)."""
    head, _, rest = text.partition(" = ")
    if not rest:
        return text.lstrip("%"), ""
    m = _OPCODE.search(" " + rest)
    return head.lstrip("%"), (m.group(1) if m else "")


def label(name: str, opcode: str) -> str:
    """A stable label for the breakdown: the instruction's name without its
    trailing number, with a custom call marked as one."""
    base = re.sub(r"([.\-](\d+|remat\d*|clone))+$", "", name)
    return f"custom-call/{base}" if opcode == "custom-call" else base


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(merged) -> float:
    return sum(b - a for a, b in merged)


def subtract(a_merged, b_merged) -> list[tuple[float, float]]:
    """The parts of ``a`` that no interval of ``b`` covers (both merged)."""
    out, j = [], 0
    for a0, a1 in a_merged:
        cur = a0
        while j < len(b_merged) and b_merged[j][1] <= cur:
            j += 1
        k = j
        while k < len(b_merged) and b_merged[k][0] < a1:
            b0, b1 = b_merged[k]
            if b0 > cur:
                out.append((cur, b0))
            cur = max(cur, b1)
            k += 1
        if cur < a1:
            out.append((cur, a1))
    return out


def _is_collective(opcode: str) -> bool:
    return opcode.startswith(COLLECTIVES)


def _inside(runs, t: float) -> bool:
    """Does ``t`` lie in one of the sorted, disjoint ``runs``?"""
    i = bisect.bisect_right(runs, (t, float("inf"))) - 1
    return i >= 0 and runs[i][0] <= t < runs[i][1]


def _main_module(chips: dict, module: str) -> dict | None:
    """Per-run seconds inside the whole runs of one program: each chip's
    first and last run are dropped, since the trace may have cut them."""
    runs_n, tot = 0, {"kernel_s": 0.0, "collective_s": 0.0,
                      "exposed_collective_s": 0.0, "busy_s": 0.0, "run_s": 0.0}
    for c in chips.values():
        runs = sorted((s, e) for nm, s, e in c["modules"] if nm == module)[1:-1]
        if not runs:
            continue
        runs_n += len(runs)
        ops = [o for o in c["ops"] if _inside(runs, o[2])]
        tot["run_s"] += length(runs)
        tot["busy_s"] += length(union((s, e) for _, _, s, e in ops))
        tot["kernel_s"] += sum(e - s for _, oc, s, e in ops if oc == "custom-call")
        compute = union((s, e) for _, oc, s, e in ops if not _is_collective(oc))
        cu = union((s, e) for s, e in c["coll"] if _inside(runs, s))
        tot["collective_s"] += length(cu)
        tot["exposed_collective_s"] += length(subtract(cu, compute))
    if not runs_n:
        return None
    return {"name": module, "runs": runs_n / len(chips),
            **{k: v / runs_n / 1e9 for k, v in tot.items()}}


def reduce(events: dict, n_devices: int, span_names=(), main_module=None) -> dict:
    """Seconds, averaged over the chips that ran anything.  The traced
    window is from the first to the last device event of any chip.
    ``per_run`` holds seconds per whole run of ``main_module``."""
    chips: dict[int, dict] = {}
    for chip, line, text, start, dur in events["device"]:
        c = chips.setdefault(chip, {"ops": [], "coll": [], "modules": []})
        end = start + dur
        if line == MODULES_LINE:
            c["modules"].append((text.split("(")[0], start, end))
            continue
        name, opcode = op_name(text)
        if _is_collective(opcode):
            c["coll"].append((start, end))
        if line == OPS_LINE and opcode not in CONTAINERS:
            c["ops"].append((name, opcode, start, end))
    if not chips:
        raise ValueError("the trace holds no device event")
    t0 = min(o[2] for c in chips.values() for o in c["ops"])
    t1 = max(o[3] for c in chips.values() for o in c["ops"])
    busy = coll = exposed = kernel = 0.0
    per_op: dict[str, float] = {}
    kernels: dict[str, float] = {}
    gaps: list[tuple[float, float]] = []
    for c in chips.values():
        all_ops = union((s, e) for _, _, s, e in c["ops"])
        busy += length(all_ops)
        compute = union((s, e) for _, oc, s, e in c["ops"] if not _is_collective(oc))
        cu = union(c["coll"])
        coll += length(cu)
        exposed += length(subtract(cu, compute))
        for name, opcode, s, e in c["ops"]:
            lab = label(name, opcode)
            per_op[lab] = per_op.get(lab, 0.0) + (e - s)
            if opcode == "custom-call":
                kernel += e - s
                kernels[lab] = kernels.get(lab, 0.0) + (e - s)
        gaps.extend(subtract([(t0, t1)], all_ops))
    n = len(chips)
    # idle gaps of all chips, put down to the innermost host span covering
    # the gap's middle (spans nest: the shortest covering one is innermost)
    by_span: dict[str, float] = {}
    spans = [(s, s + d, nm) for nm, s, d in events["host"]
             if not span_names or nm in span_names]
    for g0, g1 in gaps:
        mid = (g0 + g1) / 2.0
        cover = [(e - s, nm) for s, e, nm in spans if s <= mid <= e]
        nm = min(cover)[1] if cover else "outside any span"
        by_span[nm] = by_span.get(nm, 0.0) + (g1 - g0)
    top = lambda d: [[k, v / n / 1e9] for k, v in  # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])]
    modules: dict[str, int] = {}
    for c in chips.values():
        for nm, _, _ in c["modules"]:
            modules[nm] = modules.get(nm, 0) + 1
    return {
        "chips": n, "chips_expected": n_devices,
        "window_s": (t1 - t0) / 1e9, "busy_s": busy / n / 1e9,
        "collective_s": coll / n / 1e9, "exposed_collective_s": exposed / n / 1e9,
        "kernel_s": kernel / n / 1e9, "kernels": top(kernels),
        "device_ops": top(per_op), "idle_gaps": top(by_span),
        "module_runs": {k: v / n for k, v in modules.items()},
        "per_run": _main_module(chips, main_module) if main_module else None,
    }
