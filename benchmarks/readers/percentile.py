"""A percentile of one of the run's series of clock readings."""

from benchmarks.common import percentile


def read(run: dict, series: str, q: float):
    values = run["series"].get(series)
    return percentile(values, q) if values else None
