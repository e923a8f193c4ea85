"""One number of the trace reduction (``benchmarks/trace.py``): ``key`` of
the whole traced window, or of one whole run of the main program with
``per_run``; ``idle_share`` is 1 - busy over the traced window."""


def read(run: dict, key: str, per_run: bool = False, scale: float = 1.0):
    trace = run["trace"]
    if not trace:
        return None
    if key == "idle_share":
        return scale * (1.0 - trace["busy_s"] / trace["window_s"])
    src = trace.get("per_run") if per_run else trace
    if not src or key not in src:
        return None
    return scale * src[key]
