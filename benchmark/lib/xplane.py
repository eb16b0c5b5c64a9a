"""From the profiler's trace to intervals, and from intervals to numbers.

``read()`` opens the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps,
for each device, the operations of its "XLA Ops" line as :class:`Ops`
(three numpy arrays: start and duration in seconds, the name as an index),
moved onto the host's ``time.time()`` clock: the harness brackets the
trace with ``TraceAnnotation`` marks whose host times it knows, and the
offset between the two clocks is read from them.

Everything after that is arithmetic on intervals and is what
``tests/test_reduction.py`` checks against the small recorded trace beside
it: the union of busy intervals, the idle gaps between them, which host
span covers each gap, sums by operation. Intervals are ``[n, 2]`` arrays
of ``(start, end)``, sorted and disjoint; every function also takes plain
lists of ``(name, start, duration)`` or ``(start, end)`` tuples. A window
of a decode-heavy cell holds millions of operations, so nothing here
walks them one by one in Python.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

MARK = "bench.mark"
_SUFFIX = re.compile(r"(\.\d+)+$")


class Ops:
    """One device's operations: ``start`` and ``dur`` in seconds (float64),
    ``name_id`` an index into ``names``. Iterates as ``(name, start,
    duration)`` tuples."""
    __slots__ = ("names", "name_id", "start", "dur")

    def __init__(self, names: list[str], name_id, start, dur):
        self.names = names
        self.name_id = np.asarray(name_id, np.int32)
        self.start = np.asarray(start, np.float64)
        self.dur = np.asarray(dur, np.float64)

    @classmethod
    def of(cls, ops) -> "Ops":
        """``ops`` itself, or tuples ``(name, start, duration)`` as one."""
        if isinstance(ops, cls):
            return ops
        ops = list(ops)
        ids: dict[str, int] = {}
        name_id = [ids.setdefault(op[0], len(ids)) for op in ops]
        return cls(list(ids), name_id, [op[1] for op in ops],
                   [op[2] for op in ops])

    def take(self, keep, start=None, dur=None) -> "Ops":
        return Ops(self.names, self.name_id[keep],
                   (self.start if start is None else start)[keep],
                   (self.dur if dur is None else dur)[keep])

    def __len__(self) -> int:
        return len(self.start)

    def __iter__(self):
        names = self.names
        return ((names[i], s, d) for i, s, d in zip(
            self.name_id.tolist(), self.start.tolist(), self.dur.tolist()))


@dataclass
class Trace:
    """Device operations by device, on the host's clock, in seconds."""
    devices: dict[str, Ops] = field(default_factory=dict)
    clock_offset_s: float = 0.0      # profiler clock minus host clock
    marks_found: int = 0

    def to_json(self) -> dict:
        return {"devices": {k: [list(op) for op in v]
                            for k, v in self.devices.items()},
                "clock_offset_s": self.clock_offset_s,
                "marks_found": self.marks_found}

    @classmethod
    def from_json(cls, doc: dict) -> "Trace":
        return cls({k: Ops.of((n, float(s), float(d)) for n, s, d in v)
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
    raw: dict[str, Ops] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = list(plane.lines)
            chosen = [ln for ln in lines if ln.name == "XLA Ops"] or [
                ln for ln in lines
                if ln.name not in ("Steps", "XLA Modules", "XLA TraceMe")]
            names: dict[str, int] = {}
            by_raw: dict[str, int] = {}     # "fusion.123" → id of "fusion"
            ids, start_ns, dur_ns = [], [], []
            for ln in chosen:
                for ev in ln.events:
                    name = ev.name
                    i = by_raw.get(name)
                    if i is None:
                        i = by_raw[name] = names.setdefault(op_name(name),
                                                            len(names))
                    ids.append(i)
                    start_ns.append(ev.start_ns)
                    dur_ns.append(ev.duration_ns)
            ops = Ops(list(names), ids, np.asarray(start_ns, np.float64) / 1e9,
                      np.asarray(dur_ns, np.float64) / 1e9)
            raw[plane.name] = ops.take(np.argsort(ops.start, kind="stable"))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                seen += [ev.start_ns / 1e9 for ev in ln.events
                         if ev.name == MARK]
    seen.sort()
    offset = 0.0
    if seen and len(seen) == len(marks):
        diffs = sorted(s - m for s, m in zip(seen, marks))
        offset = diffs[len(diffs) // 2]
    order = sorted(raw, key=lambda n: int(n.rsplit(":", 1)[1].split()[0]))
    return Trace({n: Ops(raw[n].names, raw[n].name_id, raw[n].start - offset,
                         raw[n].dur) for n in order[:n_devices]},
                 offset, len(seen) if len(seen) == len(marks) else 0)


# ------------------------------------------------------------ arithmetic

_NONE = np.empty((0, 2), np.float64)


def _intervals(iv) -> np.ndarray:
    return np.asarray(iv, np.float64).reshape(-1, 2)


def _seconds(iv: np.ndarray) -> float:
    return float((iv[:, 1] - iv[:, 0]).sum())


def clip(ops, t0: float, t1: float) -> Ops:
    """The parts of ``ops`` inside ``[t0, t1]``."""
    ops = Ops.of(ops)
    lo = np.maximum(ops.start, t0)
    hi = np.minimum(ops.start + ops.dur, t1)
    return ops.take(hi > lo, lo, hi - lo)


def _merged(start: np.ndarray, dur: np.ndarray) -> np.ndarray:
    """The union of ``[start, start + dur]``: an interval that starts at or
    before the end of those before it joins them."""
    if not len(start):
        return _NONE
    order = np.argsort(start, kind="stable")
    s = start[order]
    top = np.maximum.accumulate(s + dur[order])
    first = np.flatnonzero(np.append(True, s[1:] > top[:-1]))
    return np.stack([s[first], top[np.append(first[1:] - 1, len(s) - 1)]], 1)


def busy_intervals(ops) -> np.ndarray:
    """The union of the operations' intervals, merged and in order."""
    ops = Ops.of(ops)
    return _merged(ops.start, ops.dur)


def busy_seconds(ops, t0: float, t1: float) -> float:
    return _seconds(busy_intervals(clip(ops, t0, t1)))


def idle_gaps(ops, t0: float, t1: float) -> np.ndarray:
    busy = busy_intervals(clip(ops, t0, t1))
    gaps = np.stack([np.append(t0, busy[:, 1]), np.append(busy[:, 0], t1)], 1)
    return gaps[gaps[:, 1] > gaps[:, 0]]


def _union(intervals) -> np.ndarray:
    iv = _intervals(intervals)
    return _merged(iv[:, 0], iv[:, 1] - iv[:, 0])


def _split(a: np.ndarray, b: np.ndarray):
    """Two sorted arrays of disjoint intervals → (a ∩ b, a − b), as the
    pieces between one boundary of either and the next."""
    if not len(a) or not len(b):
        return _NONE, a
    cuts = np.unique(np.concatenate([a.ravel(), b.ravel()]))
    lo, hi = cuts[:-1], cuts[1:]

    def covers(iv: np.ndarray) -> np.ndarray:
        i = np.searchsorted(iv[:, 0], lo, side="right") - 1
        return (i >= 0) & (lo < iv[np.maximum(i, 0), 1])

    in_a, in_b = covers(a), covers(b)
    pieces = np.stack([lo, hi], 1)
    return pieces[in_a & in_b], pieces[in_a & ~in_b]


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
        if len(both):
            out[name] = _seconds(both)
    if len(todo):
        out[rest] = _seconds(todo)
    return out


def op_sums(ops, t0: float, t1: float) -> dict[str, float]:
    ops = clip(ops, t0, t1)
    n = len(ops.names)
    secs = np.bincount(ops.name_id, weights=ops.dur, minlength=n)
    seen = np.bincount(ops.name_id, minlength=n) > 0
    return {name: float(secs[i]) for i, name in enumerate(ops.names)
            if seen[i]}


def seconds_within(ops, intervals) -> float:
    """Busy seconds of ``ops`` inside the union of ``intervals``."""
    both, _only = _split(busy_intervals(ops), _union(intervals))
    return _seconds(both)
