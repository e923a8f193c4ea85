"""One of the run's counters, or one over another, times a scale."""


def read(run: dict, num: str, den: str | None = None, scale: float = 1.0):
    counters = run["counters"]
    if num not in counters or (den is not None and not counters.get(den)):
        return None
    value = counters[num] / (counters[den] if den is not None else 1.0)
    return value * scale
