"""Readers of what the program itself counts and names.

``counters`` sums the hub's counters whose sample name matches a pattern
(``gen_programs_ready_total{how="loaded",stage="decode"}`` is one sample:
a family's labels are part of its name, in alphabetical order), so one
reader serves a family, one of its labels or a pair. ``span_attr_ratio`` is
one span attribute over another, summed over the window. Both return
``None`` where the program has no such counter or attribute, as a commit
from before it got them has not.
"""

from __future__ import annotations

import re

from .readers import Observed


def counters(obs: Observed, pattern: str, at: str):
    """The sum of the hub's counters whose sample name matches ``pattern``:
    ``at="start"`` what they read when the window opened (what set-up
    counted), ``at="window"`` what the window added, 0 where they stood
    still. None where no counter matches."""
    rx = re.compile(pattern)
    if at == "start":
        found = [v for name, v in obs.hub_before.items() if rx.search(name)]
    elif at == "window":
        found = [v - obs.hub_before.get(name, 0.0)
                 for name, v in obs.hub_after.items() if rx.search(name)]
    else:
        raise ValueError(f"at is 'start' or 'window', not {at!r}")
    return sum(found) if found else None


def span_attr_ratio(obs: Observed, span: str, num: str, den: str):
    """100 × Σ ``num`` / Σ ``den`` over the window's ``span``s that carry
    both attributes."""
    pairs = [(s["attrs"][num], s["attrs"][den])
             for s in obs.window_spans(span)
             if num in s.get("attrs", {}) and den in s["attrs"]]
    total = sum(d for _n, d in pairs)
    return 100.0 * sum(n for n, _d in pairs) / total if total else None
