"""The program's instants (``jit.build``: one per phase of building a
program) counted or summed before or inside the window.

The window is the one ``program_span`` reads: from the start of the first
to the end of the last of the last ``counters["steps"]`` spans named
``root``.  ``where`` is ``window`` (at or after the first root's start, up
to the last root's end: what the benchmark builds after the window, its
reference among it, is neither) or ``setup`` (before it); ``field`` is
``count`` or ``seconds`` (the sum of the instants' ``seconds``);
``phases`` keeps only instants whose ``phase`` is listed.  An instant
tagged ``nested`` lies inside another of its phase (an inner jit's trace
inside the outer program's) and is left out: its seconds are the outer's.
"""

from benchmarks.readers.program_span import window


def read(run: dict, root: str, name: str, where: str, field: str,
         phases=None):
    found = window(run, root)
    if found is None:
        return None
    records, roots = found
    t_open, t_close = roots[0].t0, roots[-1].t1
    if where == "window":
        inside = lambda t: t_open <= t <= t_close  # noqa: E731
    elif where == "setup":
        inside = lambda t: t < t_open  # noqa: E731
    else:
        raise ValueError(f"where {where!r}: window or setup")
    marks = [r for r in records if r.name == name and r.instant
             and inside(r.t0) and not r.tags.get("nested")
             and (phases is None or r.tags.get("phase") in phases)]
    if field == "count":
        return float(len(marks))
    if field == "seconds":
        return float(sum(r.tags["seconds"] for r in marks))
    raise ValueError(f"field {field!r}: count or seconds")
