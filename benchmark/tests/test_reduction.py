"""Checks of the yardstick's arithmetic. ``python3 benchmark/tests/test_reduction.py``
(or through pytest, which collects the ``test_`` functions) on the CPU.

1. The reduction from device intervals to numbers (``lib/xplane.py``: the
   busy union, the idle gaps, which host span covers each gap, sums by
   operation) against ``tiny_trace.json``, a slice of a trace recorded on
   the chip, rasterised here at 100 ns by other code; and its arrays
   against the plain loops they replaced (``loops.py``), to the last
   digits that summing in another order leaves. ``python3
   benchmark/tests/test_reduction.py <file> ...`` holds the two against
   each other on traces kept from the chip (``run.py --keep``; ``.gz``
   is read too).
2. The operation counts of ``lib/families/llama.py`` against what XLA
   reports for the program's own prefill (``compiled.cost_analysis()``) at
   a small size: the count of what the program executes within 5 %, and
   the count of what the algorithm needs (causal half of attention, the
   head for one position) below it.
"""

from __future__ import annotations

import gzip
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parent.parent))

import loops  # noqa: E402
from lib import xplane  # noqa: E402
from lib.families import llama as costs  # noqa: E402

TICK = 1e-7


def _raster(intervals, t0: float, t1: float) -> np.ndarray:
    grid = np.zeros(int(round((t1 - t0) / TICK)), bool)
    for a, b in intervals:
        lo = int(round((max(a, t0) - t0) / TICK))
        hi = int(round((min(b, t1) - t0) / TICK))
        grid[lo:hi] = True
    return grid


def test_reduction_against_recorded_trace():
    doc = json.loads((HERE / "tiny_trace.json").read_text())
    trace = xplane.Trace.from_json(doc["trace"])
    (ops,) = trace.devices.values()
    t0, t1 = doc["window"]
    busy = _raster([(s, s + d) for _n, s, d in ops], t0, t1)
    assert len(ops) > 2000 and busy.any()

    got = xplane.busy_seconds(ops, t0, t1)
    assert abs(got - busy.sum() * TICK) < 2e-4 * got

    gaps = xplane.idle_gaps(ops, t0, t1)
    assert abs(sum(b - a for a, b in gaps) + got - (t1 - t0)) < 1e-9
    assert all(b > a for a, b in gaps)

    order = ["serve.prefill", "serve.admit", "serve.restore"]
    spans = [(s["name"], s["ts"], s["ts"] + s["dur"]) for s in doc["spans"]]
    by = xplane.attribute(gaps, spans, order)
    idle, taken = ~busy, np.zeros_like(busy)
    for name in order:
        cover = _raster([(a, b) for n, a, b in spans if n == name], t0, t1)
        want = (idle & cover & ~taken).sum() * TICK
        taken |= cover
        assert abs(by.get(name, 0.0) - want) < 1e-4, (name, by, want)
    want = (idle & ~taken).sum() * TICK
    assert abs(by.get("outside_any_span", 0.0) - want) < 1e-4
    assert by["serve.prefill"] > 0.1      # the page-out: the chip waits

    sums = xplane.op_sums(ops, t0, t1)
    plain: dict[str, float] = {}
    for n, _s, d in ops:
        plain[n] = plain.get(n, 0.0) + d
    assert sums.keys() == plain.keys()
    assert all(abs(sums[n] - plain[n]) < 1e-9 for n in plain)
    assert max(sums, key=sums.get) == "fusion"

    prefill = [(a, b) for n, a, b in spans if n == "serve.prefill"]
    inside = xplane.seconds_within(ops, prefill)
    assert abs(inside - (busy & _raster(prefill, t0, t1)).sum() * TICK) \
        < 2e-4 * inside
    assert xplane.op_name("fusion.123") == "fusion"
    assert xplane.op_name("%all-reduce.4.1") == "all-reduce"


def arrays_against_loops(doc: dict, order: list[str]) -> dict[str, float]:
    """Every number the harness takes from a trace, by the arrays and by
    the loops, over the kept ``doc`` (``window`` or ``t0``/``t1``, ``trace``,
    ``spans``): the largest difference found, relative to the value."""
    t0, t1 = doc["window"] if "window" in doc else (doc["t0"], doc["t1"])
    spans = [(s["name"], s["ts"], s["ts"] + s["dur"]) for s in doc["spans"]]
    worst: dict[str, float] = {}

    def hold(what: str, got: float, want: float) -> None:
        worst[what] = max(worst.get(what, 0.0),
                          abs(got - want) / max(abs(want), 1e-12))

    for ops in xplane.Trace.from_json(doc["trace"]).devices.values():
        plain = list(ops)
        hold("busy_seconds", xplane.busy_seconds(ops, t0, t1),
             loops.busy_seconds(plain, t0, t1))
        gaps, pgaps = xplane.idle_gaps(ops, t0, t1), loops.idle_gaps(
            plain, t0, t1)
        assert len(gaps) == len(pgaps)
        assert np.array_equal(np.asarray(gaps), np.asarray(pgaps))
        by, pby = (m.attribute(g, spans, order)
                   for m, g in ((xplane, gaps), (loops, pgaps)))
        assert by.keys() == pby.keys(), (by, pby)
        for name in pby:
            hold("attribute", by[name], pby[name])
        sums, psums = xplane.op_sums(ops, t0, t1), loops.op_sums(
            plain, t0, t1)
        assert sums.keys() == psums.keys()
        for name in psums:
            hold("op_sums", sums[name], psums[name])
        for kind in sorted({n for n, _a, _b in spans}):
            iv = [(a, b) for n, a, b in spans if n == kind]
            hold("seconds_within", xplane.seconds_within(ops, iv),
                 loops.seconds_within(plain, iv))
        clipped = xplane.clip(ops, t0, t1)
        assert list(clipped) == loops.clip(plain, t0, t1)
        assert np.array_equal(xplane.busy_intervals(clipped), np.asarray(
            loops.busy_intervals(loops.clip(plain, t0, t1))))
    return worst


def test_arrays_read_what_the_loops_read():
    import run as harness

    doc = json.loads((HERE / "tiny_trace.json").read_text())
    worst = arrays_against_loops(doc, harness.engine_spans()[0])
    assert set(worst) == {"busy_seconds", "attribute", "op_sums",
                          "seconds_within"}
    assert max(worst.values()) < 1e-12, worst


def test_flop_counts_against_xla():
    import jax
    import jax.numpy as jnp

    from demodel_tpu.models import llama

    hf = {"hidden_size": 256, "intermediate_size": 640,
          "num_hidden_layers": 3, "num_attention_heads": 8,
          "num_key_value_heads": 2, "vocab_size": 4096}
    cfg = llama.LlamaConfig.from_hf(dict(hf, torch_dtype="float32"))
    params = jax.eval_shape(lambda: llama.init_params(jax.random.key(0), cfg))
    T = 384
    compiled = jax.jit(
        lambda p, t: llama.step_prefill(p, t, cfg)).lower(
        params, jax.ShapeDtypeStruct((1, T), jnp.int32)).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    xla = float(cost["flops"])
    executed = costs.as_executed_prefill_flops(hf, T)
    needed = costs.prefill_flops(hf, T)
    assert abs(executed - xla) < 0.05 * xla, (executed, xla)
    assert needed < executed
    # one decode step of 8 sequences of 1000 positions: weights + cache
    yi = {"hidden_size": 4096, "intermediate_size": 11008,
          "num_hidden_layers": 32, "num_attention_heads": 32,
          "num_key_value_heads": 4, "vocab_size": 64000}
    assert costs.kv_bytes_per_position(yi) == 65536
    assert abs(costs.parameters(yi) - 6.061e9) < 1e6
    assert abs(costs.decode_bytes(yi, [{}], [1000] * 8)
               - (2 * (costs.parameters(yi) - 64000 * 4096 - 65 * 4096)
                  + 8000 * 65536)) < 1


if __name__ == "__main__":
    if sys.argv[1:]:
        import run as harness

        for kept in sys.argv[1:]:
            opener = gzip.open if kept.endswith(".gz") else open
            with opener(kept, "rt") as f:
                print(kept, arrays_against_loops(
                    json.load(f), harness.engine_spans()[0]))
        sys.exit(0)
    test_reduction_against_recorded_trace()
    test_arrays_read_what_the_loops_read()
    test_flop_counts_against_xla()
    print("reduction and operation counts: ok")
