"""A kernel's share of its roofline: the least time the chip could take for
one run's kernel work (the larger of FLOPs over the bf16 peak and bytes
over the HBM peak, both from ``benchmarks/work.py``) over the device time
of the program's custom calls in one whole run, from the trace.  In %.
``roof`` names the roof the declaration expects to bind; another one
binding at these shapes means the declaration is wrong, and is an error."""

from benchmarks.work import roofline_seconds


def read(run: dict, roof: str):
    trace, c = run["trace"], run["counters"]
    per_run = trace and trace.get("per_run")
    if not per_run or not per_run["kernel_s"] or "kernel_flops_per_run" not in c:
        return None
    least, binding = roofline_seconds(c["kernel_flops_per_run"],
                                      c["kernel_bytes_per_run"], run["peaks"])
    if binding != roof:
        raise ValueError(f"declared roof {roof!r}, but {binding!r} binds")
    return 100.0 * least / per_run["kernel_s"]
