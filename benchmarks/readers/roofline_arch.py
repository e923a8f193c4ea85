"""The paged-decode kernel's share of its roofline in an adapter-driven
cell: the least time the chip could take for one decode step's attention
(the adapter's ``paged_decode_flops`` / ``paged_decode_bytes`` at the mean
``kv_tokens`` and ``batch`` tags of the window's ``span`` records, through
``benchmarks/work.py::roofline_seconds``) over the device time of the
decode program's custom calls in one whole run, from the trace.  In %.
``roof`` names the roof the declaration expects to bind; another one
binding is an error.  A program whose spans carry no ``kv_tokens`` tag, or
an adapter without the two functions, reads nothing."""

import importlib

from benchmarks.readers.program_span import descendants, window
from benchmarks.work import roofline_seconds


def read(run: dict, roof: str, root: str, span: str):
    per_run = run["trace"] and run["trace"].get("per_run")
    if not per_run or not per_run["kernel_s"]:
        return None
    arch = importlib.import_module(f"benchmarks.arch.{run['cfg']['model_type']}")
    found = window(run, root)
    if found is None or not hasattr(arch, "paged_decode_bytes"):
        return None
    picked = [r for r in descendants(*found, span)[0]
              if "kv_tokens" in r.tags and r.tags.get("batch")]
    if not picked:
        return None
    kv = sum(r.tags["kv_tokens"] for r in picked) / len(picked)
    slots = sum(r.tags["batch"] for r in picked) / len(picked)
    least, binding = roofline_seconds(
        arch.paged_decode_flops(run["cfg"], kv),
        arch.paged_decode_bytes(run["cfg"], kv, slots), run["peaks"])
    if binding != roof:
        raise ValueError(f"declared roof {roof!r}, but {binding!r} binds")
    return 100.0 * least / per_run["kernel_s"]
