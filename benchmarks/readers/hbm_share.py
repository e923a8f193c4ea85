"""Peak bytes in use on the fullest chip over the chip's published HBM, in %."""


def read(run: dict):
    peak = run["counters"].get("memory_peak_bytes")
    return 100.0 * peak / run["peaks"]["hbm_bytes"] if peak else None
