"""The reduction of ``lib/xplane.py`` as plain loops over Python lists.

What ``lib/xplane.py`` was before it held a device's operations as numpy
arrays, kept as the reference its arrays are held to
(``test_reduction.py``: on ``tiny_trace.json``, and on a trace kept from
the chip with ``python3 benchmark/tests/test_reduction.py <file>``). Too
slow for a window of millions of operations, which is why it is here.
"""

from __future__ import annotations


def clip(ops, t0: float, t1: float) -> list[tuple[str, float, float]]:
    """The parts of ``ops`` inside ``[t0, t1]``."""
    out = []
    for name, s, d in ops:
        lo, hi = max(s, t0), min(s + d, t1)
        if hi > lo:
            out.append((name, lo, hi - lo))
    return out


def busy_intervals(ops) -> list[tuple[float, float]]:
    """The union of the operations' intervals, merged and in order."""
    merged: list[list[float]] = []
    for _n, s, d in sorted(ops, key=lambda op: op[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], s + d)
        else:
            merged.append([s, s + d])
    return [(a, b) for a, b in merged]


def busy_seconds(ops, t0: float, t1: float) -> float:
    return sum(b - a for a, b in busy_intervals(clip(ops, t0, t1)))


def idle_gaps(ops, t0: float, t1: float) -> list[tuple[float, float]]:
    gaps, at = [], t0
    for a, b in busy_intervals(clip(ops, t0, t1)):
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if t1 > at:
        gaps.append((at, t1))
    return gaps


def _union(intervals) -> list[tuple[float, float]]:
    return busy_intervals([("", a, b - a) for a, b in intervals])


def _split(a, b):
    """Two sorted lists of disjoint intervals → (a ∩ b, a − b)."""
    both, only, j = [], [], 0
    for lo, hi in a:
        at = lo
        while j < len(b) and b[j][1] <= at:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            s, e = b[k]
            if s > at:
                only.append((at, s))
            both.append((max(s, at), min(e, hi)))
            at = max(at, min(e, hi))
            k += 1
        if hi > at:
            only.append((at, hi))
    return both, only


def attribute(gaps, spans, order: list[str],
              rest: str = "outside_any_span") -> dict[str, float]:
    """Seconds of ``gaps`` under each kind of host span. ``spans`` are
    ``(name, start, end)``; where spans of several kinds cover an instant,
    the kind earliest in ``order`` takes it; what none covers is ``rest``."""
    by_name: dict[str, list[tuple[float, float]]] = {}
    for name, s, e in spans:
        by_name.setdefault(name, []).append((s, e))
    out: dict[str, float] = {}
    todo = _union(gaps)
    for name in order:
        both, todo = _split(todo, _union(by_name.get(name, [])))
        if both:
            out[name] = sum(b - a for a, b in both)
    if todo:
        out[rest] = sum(b - a for a, b in todo)
    return out


def op_sums(ops, t0: float, t1: float) -> dict[str, float]:
    out: dict[str, float] = {}
    for name, _s, d in clip(ops, t0, t1):
        out[name] = out.get(name, 0.0) + d
    return out


def seconds_within(ops, intervals) -> float:
    """Busy seconds of ``ops`` inside the union of ``intervals``."""
    both, _only = _split(busy_intervals(ops), _union(intervals))
    return sum(b - a for a, b in both)
