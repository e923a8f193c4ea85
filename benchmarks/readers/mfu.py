"""The whole step's share of the chips' peak: the FLOPs the algorithm
needs (``benchmarks/work.py``) for the work the window did while no
profiler was attached, over that time, over chips x the published bf16
peak.  In %."""


def read(run: dict):
    c = run["counters"]
    if not c.get("mfu_flops") or not c.get("mfu_s"):
        return None
    return 100.0 * c["mfu_flops"] / c["mfu_s"] / run["chips"] / run["peaks"]["bf16_flops"]
