"""From the profiler's trace to intervals, and from intervals to numbers.

``read()`` opens the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps,
for each device, the operations of its "XLA Ops" line as ``(name, start,
duration)`` in seconds, moved onto the host's ``time.time()`` clock: the
harness brackets the trace with ``TraceAnnotation`` marks whose host times
it knows, and the offset between the two clocks is read from them (the
program does not annotate its own spans yet).

Everything after that is arithmetic on intervals and is what
``tests/test_reduction.py`` checks against the small recorded trace beside
it: the union of busy intervals, the idle gaps between them, which host
span covers each gap, sums by operation.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

MARK = "bench.mark"
_SUFFIX = re.compile(r"(\.\d+)+$")


@dataclass
class Trace:
    """Device operations by device, on the host's clock, in seconds."""
    devices: dict[str, list[tuple[str, float, float]]] = field(
        default_factory=dict)
    clock_offset_s: float = 0.0      # profiler clock minus host clock
    marks_found: int = 0

    def to_json(self) -> dict:
        return {"devices": {k: [list(op) for op in v]
                            for k, v in self.devices.items()},
                "clock_offset_s": self.clock_offset_s,
                "marks_found": self.marks_found}

    @classmethod
    def from_json(cls, doc: dict) -> "Trace":
        return cls({k: [(n, float(s), float(d)) for n, s, d in v]
                    for k, v in doc["devices"].items()},
                   float(doc.get("clock_offset_s", 0.0)),
                   int(doc.get("marks_found", 0)))


def op_name(raw: str) -> str:
    """``fusion.123`` → ``fusion``: the kind of operation, so that sums
    survive a recompilation that renumbers them."""
    return _SUFFIX.sub("", raw.split(" ")[0].lstrip("%")) or raw


def find_trace(logdir: Path) -> Path | None:
    found = sorted(Path(logdir).glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def read(path: Path, marks: list[float], n_devices: int) -> Trace:
    """Reduce the trace at ``path``. ``marks`` are the host times at which
    the harness entered a ``TraceAnnotation(MARK)``, in order."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    seen: list[float] = []
    raw: dict[str, list[tuple[str, float, float]]] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = list(plane.lines)
            chosen = [ln for ln in lines if ln.name == "XLA Ops"] or [
                ln for ln in lines
                if ln.name not in ("Steps", "XLA Modules", "XLA TraceMe")]
            ops = [(op_name(ev.name), ev.start_ns / 1e9,
                    ev.duration_ns / 1e9)
                   for ln in chosen for ev in ln.events]
            raw[plane.name] = sorted(ops, key=lambda op: op[1])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                seen += [ev.start_ns / 1e9 for ev in ln.events
                         if ev.name == MARK]
    seen.sort()
    offset = 0.0
    if seen and len(seen) == len(marks):
        diffs = sorted(s - m for s, m in zip(seen, marks))
        offset = diffs[len(diffs) // 2]
    names = sorted(raw, key=lambda n: int(n.rsplit(":", 1)[1].split()[0]))
    return Trace({n: [(name, s - offset, d) for name, s, d in raw[n]]
                  for n in names[:n_devices]},
                 offset, len(seen) if len(seen) == len(marks) else 0)


# ------------------------------------------------------------ arithmetic


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
