"""One tag of the program's spans over another, summed over the window.

Over the spans named ``span`` beneath the window's roots
(:mod:`benchmarks.readers.program_span`): the sum of tag ``num`` over the
sum of tag ``den``, multiplied by the counters ``times`` names and divided
by those ``over`` names.  A span without the ``num`` tag (a program that
does not count it) is left out; with none left the metric reads nothing.
"""

from benchmarks.readers.program_span import descendants, window


def read(run: dict, root: str, span: str, num: str, den: str,
         times=(), over=()):
    found = window(run, root)
    if found is None:
        return None
    picked, _ = descendants(*found, span)
    picked = [r for r in picked if num in r.tags and den in r.tags]
    total = sum(r.tags[den] for r in picked)
    if not picked or not total:
        return None
    value = sum(r.tags[num] for r in picked) / total
    for name in times:
        value *= run["counters"][name]
    for name in over:
        if not run["counters"].get(name):
            return None
        value /= run["counters"][name]
    return float(value)
