"""One kernel's share of its roofline in an adapter-driven cell: the least
time the chip could take for one run of the main program's attention (the
adapter's ``paged_decode_flops`` / ``paged_decode_bytes`` at the mean
``kv_tokens`` and ``batch`` tags of the window's ``span`` records, through
``benchmarks/work.py::roofline_seconds``) over the device time of ONE
kernel a run of the main program: the trace's ``kernels`` row named
``kernel`` over the main module's ``module_runs``.  In %.  Unlike
``roofline_arch``, no other custom call of the program (an expert layer's
grouped products, say) is counted in the kernel's time.  ``roof`` names
the roof the declaration expects to bind; another one binding is an error.
A trace without that kernel's row, spans without a ``kv_tokens`` tag, or an
adapter without the two functions read nothing."""

import importlib

from benchmarks.readers.program_span import descendants, window
from benchmarks.work import roofline_seconds


def read(run: dict, kernel: str, roof: str, root: str, span: str):
    trace = run["trace"]
    if not trace:
        return None
    seconds = dict(trace.get("kernels", [])).get(kernel)
    runs = trace.get("module_runs", {}).get(run["traffic"]["main_module"])
    arch = importlib.import_module(f"benchmarks.arch.{run['cfg']['model_type']}")
    if not seconds or not runs or not hasattr(arch, "paged_decode_bytes"):
        return None
    found = window(run, root)
    if found is None:
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
    return 100.0 * least / (seconds / runs)
