"""A percentile of one of the program's own spans over the window's steps.

The program keeps its spans in an in-memory ring
(``theanompi_tpu/telemetry/spans.py``); the window is the last
``counters["steps"]`` spans named ``root`` there (no step of either driver
runs after the window closes; warm-up, check steps and lead-in lie
before).  ``span`` is ``root`` itself or a descendant of a root by
``parent``; ``stat`` is ``p50`` (median duration, ms) or ``self_p50``
(median of duration minus the part its children cover, ms).  A program
without the ring reads nothing; a ring that has wrapped, or holds fewer
roots than the window had steps, is an error.
"""

from benchmarks.common import percentile


def window(run: dict, root: str):
    """-> (every record held, the window's roots), or None where the
    program has no ring."""
    try:
        from theanompi_tpu.telemetry import spans
    except ImportError:
        return None
    steps = int(run["counters"]["steps"])
    if spans.dropped():
        raise RuntimeError(f"the span ring has wrapped ({spans.dropped()} "
                           f"records gone): it no longer holds the window")
    records = spans.snapshot()
    roots = [r for r in records if r.name == root and not r.instant][-steps:]
    if len(roots) < steps:
        raise RuntimeError(f"the ring holds {len(roots)} {root!r} spans; the "
                           f"window had {steps} steps")
    return records, roots


def descendants(records, roots, name: str) -> tuple[list, dict]:
    """-> (the spans named ``name`` among ``roots`` and all beneath them,
    every record's children by its id)."""
    inside = {r.id for r in roots}
    out = [r for r in roots if r.name == name]
    children: dict = {}
    for r in records:
        children.setdefault(r.parent, []).append(r)
    todo = list(roots)
    while todo:
        for c in children.get(todo.pop().id, ()):
            if c.id not in inside:
                inside.add(c.id)
                todo.append(c)
                if c.name == name and not c.instant:
                    out.append(c)
    return out, children


def self_seconds(span, children) -> float:
    """``span``'s duration minus the part its children cover."""
    covered, end = 0.0, span.t0
    for c in sorted(children.get(span.id, ()), key=lambda c: c.t0):
        a, b = max(c.t0, end), min(c.t1, span.t1)
        if b > a:
            covered += b - a
            end = b
    return (span.t1 - span.t0) - covered


def read(run: dict, root: str, span: str, stat: str):
    found = window(run, root)
    if found is None:
        return None
    records, roots = found
    picked, children = descendants(records, roots, span)
    if not picked:
        return None
    if stat == "p50":
        values = [r.t1 - r.t0 for r in picked]
    elif stat == "self_p50":
        values = [self_seconds(r, children) for r in picked]
    else:
        raise ValueError(f"stat {stat!r}: p50 or self_p50")
    return percentile(values, 50) * 1e3
