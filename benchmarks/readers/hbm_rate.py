"""The whole step's share of the chips' HBM bandwidth: the LEAST bytes the
decode steps had to move (the adapter's ``decode_bytes``: held weights
once, state read and written, K/V in context, logits) for the work the
window did while no profiler was attached, over that time, over chips x
the published bytes per second.  In %.  Prefill's bytes are left out, so
the share reads low rather than high."""


def read(run: dict):
    c = run["counters"]
    if not c.get("hbm_bytes") or not c.get("mfu_s"):
        return None
    return (100.0 * c["hbm_bytes"] / c["mfu_s"] / run["chips"]
            / run["peaks"]["hbm_bytes_per_s"])
