"""Readers: each turns what a run observed into one metric's number.

A metric's data file (``benchmark/end_to_end/<name>.json`` or
``benchmark/layer_metrics/<name>.json``) names a reader and its arguments:
``{"reader": "span_percentile", "args": {"span": "serve.prefill", "p": 50,
"scale": 1000}}``. A reader takes the run's :class:`Observed` and those
arguments and returns a number, or ``None`` when it finds nothing to read
(the harness then leaves the metric out of the line). A new metric that an
existing reader can compute is a data file and an entry in
``BENCHMARK.json``, nothing else. A reader that is not here is named by
path, ``"reader": "<module>:<function>"`` for a module under ``lib/``
(``families.<model_type>:<function>`` for one in a family's own file), and
has the same signature: a new quantity is a new file too.

Times are the host's ``time.time()``; a request's times are taken on the
client's side of HTTP. Spans and counters are the program's own
(``demodel_tpu.utils.trace`` / ``metrics.HUB``); the device's operations
come from the profiler's trace through :mod:`xplane`.
"""

from __future__ import annotations

import importlib
import re
from dataclasses import dataclass, field

from . import families, xplane


@dataclass
class Observed:
    t0: float = 0.0                       # the window, host clock
    t1: float = 0.0
    records: list = field(default_factory=list)    # loadgen.Record
    spans: list = field(default_factory=list)      # the program's span dicts
    hub_before: dict = field(default_factory=dict)  # counters at t0
    hub_after: dict = field(default_factory=dict)   # and at t1
    trace: xplane.Trace | None = None     # device operations, host clock
    model: dict = field(default_factory=dict)      # the config.json keys
    engine: dict = field(default_factory=dict)     # the engine's settings
    peaks: dict = field(default_factory=dict)      # this device kind's row
    chips: int = 1
    setup_s: float = 0.0
    phases: dict = field(default_factory=dict)     # set-up phase → seconds
    memory_peak_bytes: int = 0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def window_spans(self, name: str) -> list[dict]:
        """Spans called ``name`` that lie wholly inside the window."""
        return sorted((s for s in self.spans if s["name"] == name
                       and s["ts"] >= self.t0
                       and s["ts"] + s["dur"] <= self.t1),
                      key=lambda s: s["ts"])


def percentile(values, p: float) -> float | None:
    """Linear interpolation between order statistics (numpy's default)."""
    xs = sorted(values)
    if not xs:
        return None
    k = (len(xs) - 1) * p / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


# ------------------------------------------------------- client's side


def window_tokens(obs: Observed) -> int:
    return sum(1 for r in obs.records for t in r.times
               if obs.t0 <= t <= obs.t1)


def tokens_per_second(obs: Observed):
    """Output tokens that reached clients inside the window, over it."""
    return window_tokens(obs) / obs.seconds if obs.seconds > 0 else None


def token_gaps(obs: Observed) -> list[float]:
    """Every gap between consecutive tokens of one request that ends
    inside the window, in seconds."""
    return [b - a for r in obs.records
            for a, b in zip(r.times, r.times[1:]) if obs.t0 <= b <= obs.t1]


def token_gap_percentile(obs: Observed, p: float):
    v = percentile(token_gaps(obs), p)
    return None if v is None else v * 1e3


def first_token_times(obs: Observed) -> list[float]:
    """Seconds from each request's due time (open loops) or sending to its
    first token, for requests sent and answered inside the window."""
    out = []
    for r in obs.records:
        start = r.due if r.due is not None else r.sent
        if start is not None and r.times and start >= obs.t0 \
                and r.times[0] <= obs.t1:
            out.append(r.times[0] - start)
    return out


def ttft_percentile(obs: Observed, p: float):
    v = percentile(first_token_times(obs), p)
    return None if v is None else v * 1e3


def setup_seconds(obs: Observed):
    return obs.setup_s


def setup_phase_seconds(obs: Observed, phases: list[str]):
    got = [obs.phases[p] for p in phases if p in obs.phases]
    return sum(got) if got else None


# ------------------------------------------------ the program's spans


def span_percentile(obs: Observed, span: str, p: float, scale: float = 1.0):
    v = percentile([s["dur"] for s in obs.window_spans(span)], p)
    return None if v is None else v * scale


def span_attr_share(obs: Observed, span: str, attr: str, of: str):
    """Mean of a span attribute over an engine setting, in percent
    (``batch`` of ``serve.decode-step`` over ``max_batch``)."""
    vals = [s["attrs"][attr] for s in obs.window_spans(span)
            if attr in s.get("attrs", {})]
    if not vals or not obs.engine.get(of):
        return None
    return 100.0 * sum(vals) / len(vals) / float(obs.engine[of])


def span_gap_percentile(obs: Observed, first: str, then: str, key: str,
                        p: float, scale: float = 1.0):
    """Percentile of the time from the end of span ``first`` to the start
    of span ``then`` carrying the same ``key`` attribute (a request waits
    from the end of ``serve.admit`` to the start of its ``serve.prefill``)."""
    ends = {s["attrs"][key]: s["ts"] + s["dur"]
            for s in obs.window_spans(first) if key in s.get("attrs", {})}
    waits = [s["ts"] - ends[s["attrs"][key]]
             for s in obs.window_spans(then)
             if s.get("attrs", {}).get(key) in ends]
    v = percentile([max(w, 0.0) for w in waits], p)
    return None if v is None else v * scale


def span_cycle_outside_share(obs: Observed, span: str, unless: str):
    """Over pairs of successive ``span``s with no ``unless`` span between
    them: the share of the time from one's start to the next one's start
    that is outside the span, in percent. For ``serve.decode-step`` it is
    the gather, pad and write-back the span does not cover."""
    steps = obs.window_spans(span)
    breaks = [s["ts"] for s in obs.window_spans(unless)]
    inside = cycle = 0.0
    for a, b in zip(steps, steps[1:]):
        if any(a["ts"] <= t < b["ts"] for t in breaks):
            continue
        inside += a["dur"]
        cycle += b["ts"] - a["ts"]
    return 100.0 * (1.0 - inside / cycle) if cycle > 0 else None


def setup_span_seconds(obs: Observed, span: str):
    """Duration of the first ``span`` of the run, wherever it lies."""
    found = sorted((s for s in obs.spans if s["name"] == span),
                   key=lambda s: s["ts"])
    return found[0]["dur"] if found else None


def hub_delta(obs: Observed, counter: str, per_second: bool = False):
    if counter not in obs.hub_after:
        return None
    d = obs.hub_after[counter] - obs.hub_before.get(counter, 0.0)
    return d / obs.seconds if per_second else d


# ------------------------------------------------------------ the device


def _device_mean(obs: Observed, fn):
    """``fn(ops)`` averaged over the cell's chips; None with no trace."""
    if obs.trace is None or not obs.trace.devices:
        return None
    vals = [fn(ops) for ops in obs.trace.devices.values()]
    return sum(vals) / len(vals)


def device_busy_seconds(obs: Observed):
    return _device_mean(obs, lambda ops: xplane.busy_seconds(
        ops, obs.t0, obs.t1))


def device_idle_share(obs: Observed):
    busy = device_busy_seconds(obs)
    return None if busy is None else 100.0 * (1.0 - busy / obs.seconds)


def trace_op_share(obs: Observed, pattern: str):
    """Device time of operations whose name matches ``pattern`` over the
    device's busy time, in percent."""
    rx = re.compile(pattern)
    busy = device_busy_seconds(obs)
    if not busy:
        return None
    took = _device_mean(obs, lambda ops: sum(
        d for n, d in xplane.op_sums(ops, obs.t0, obs.t1).items()
        if rx.search(n)))
    return 100.0 * took / busy


def prefill_flops_roofline(obs: Observed, span: str, attr: str):
    """The least time the prefills of the window could take on the MXU
    (operations from the family's ``prefill_flops`` over the peak, divided
    among the cell's chips) over the device time inside their spans."""
    spans = [s for s in obs.window_spans(span) if attr in s.get("attrs", {})]
    took = _device_mean(obs, lambda ops: xplane.seconds_within(
        ops, [(s["ts"], s["ts"] + s["dur"]) for s in spans]))
    if not took:
        return None
    family = families.of(obs.model)
    flops = sum(family.prefill_flops(obs.model, s["attrs"][attr])
                for s in spans)
    least = flops / (obs.peaks["bf16_flops_per_s"] * obs.chips)
    return 100.0 * least / took


def decode_bytes_roofline(obs: Observed, span: str):
    """The least time the window's decode steps could take reading HBM
    (the family's ``decode_bytes``, which is given the attributes of every
    step's span and the cached positions behind every token decoded) over
    the device time inside their spans."""
    spans = obs.window_spans(span)
    took = _device_mean(obs, lambda ops: xplane.seconds_within(
        ops, [(s["ts"], s["ts"] + s["dur"]) for s in spans]))
    if not took:
        return None
    # token k of a request (k >= 2) came from a step that read its prompt
    # and the k - 2 tokens fed before it
    lengths = [len(r.prompt) + k - 1
               for r in obs.records
               for k, t in enumerate(r.times) if k >= 1
               and obs.t0 <= t <= obs.t1]
    need = families.of(obs.model).decode_bytes(
        obs.model, [s.get("attrs", {}) for s in spans], lengths)
    least = need / (obs.peaks["hbm_bytes_per_s"] * obs.chips)
    return 100.0 * least / took


def memory_peak_gb(obs: Observed):
    return obs.memory_peak_bytes / 1e9 if obs.memory_peak_bytes else None


READERS = {f.__name__: f for f in (
    tokens_per_second, token_gap_percentile, ttft_percentile, setup_seconds,
    setup_phase_seconds, span_percentile, span_attr_share,
    span_gap_percentile, span_cycle_outside_share, setup_span_seconds,
    hub_delta, device_idle_share, trace_op_share, prefill_flops_roofline,
    decode_bytes_roofline, memory_peak_gb)}


def resolve(name: str):
    """The reader a metric's data file names: a bare name is one of this
    module's ``READERS``; ``<module>:<function>`` is a function of a module
    under ``lib/`` (``families.<model_type>:<function>``), so that a metric
    no reader here computes arrives as a new file."""
    module, _, function = name.rpartition(":")
    if not module:
        if name not in READERS:
            raise ValueError(
                f"no reader called {name!r}; there are {sorted(READERS)}, "
                "and a reader of another module under benchmark/lib/ is "
                "named \"<module>:<function>\"")
        return READERS[name]
    found = getattr(importlib.import_module(f"{__package__}.{module}"),
                    function, None)
    if not callable(found):
        raise ValueError(f"benchmark/lib/{module.replace('.', '/')}.py has "
                         f"no reader called {function!r}")
    return found


def read(obs: Observed, spec: dict):
    """Apply the reader a metric's data file names; None when there is
    nothing to read."""
    return resolve(spec["reader"])(obs, **spec.get("args", {}))
