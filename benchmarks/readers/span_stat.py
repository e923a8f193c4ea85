"""One of the program's spans over the window's steps, by a statistic
``program_span`` does not have.

The window and the family are ``program_span``'s: the last
``counters["steps"]`` spans named ``root`` in the program's ring and every
record beneath them.  ``stat`` is

- ``p50``: the median duration (ms) of the spans named ``span`` —
  ``program_span.read``'s, handed over as it is;
- ``ms_per_root``: their summed duration (ms) over the number of roots:
  what a span that not every step has costs a step;
- ``excess_ms``: the sum (ms) of ``duration - median`` over those longer
  than ``factor`` x the median — the time lost to stalls of a span whose
  median is its norm; 0.0 where none is that long.

No span of that name beneath the roots reads nothing (the metric is left
out); a ring that has wrapped is ``window``'s error.
"""

from statistics import median

from benchmarks.readers import program_span


def read(run: dict, root: str, span: str, stat: str, factor=None):
    if stat == "p50":
        return program_span.read(run, root, span, "p50")
    if stat not in ("ms_per_root", "excess_ms"):
        raise ValueError(f"stat {stat!r}: p50, ms_per_root or excess_ms")
    if stat == "excess_ms" and factor is None:
        raise ValueError("stat 'excess_ms' needs a factor")
    found = program_span.window(run, root)
    if found is None:
        return None
    records, roots = found
    picked, _ = program_span.descendants(records, roots, span)
    if not picked:
        return None
    durations = [r.t1 - r.t0 for r in picked]
    if stat == "ms_per_root":
        return sum(durations) / len(roots) * 1e3
    norm = median(durations)
    return sum(d - norm for d in durations if d > factor * norm) * 1e3
