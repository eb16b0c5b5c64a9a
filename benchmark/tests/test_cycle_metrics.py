"""The six metrics of the opened engine cycle, held to account on the CPU.

Run by hand (``JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q``).

``test_data_file_*``: each metric is data only, a ``layer_metrics`` file
that names a reader ``lib/readers.py`` has and the span the program
records, and an entry in ``BENCHMARK.json`` that ``cell_metrics`` routes to
the one cell meant (``ttft_p50_ms`` is reported by ``yi6b-score`` alone,
``itl_p50_ms`` by ``yi6b-chat`` alone).

``test_rehearsed_*``: one whole traced rehearsal a cell (toy model, the
CPU's profiler): every metric of the cell reads a number from the spans of
a real engine run, and the spans, which the serving plane annotates with
``jax.profiler.TraceAnnotation``, lie in the profiler's host plane, one
event a span, at the span's start once the harness's clock offset is taken
off. Counts and clocks only: a rehearsal's durations are not device numbers.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parent.parent))

#: metric → (the span it reads, the cell that reports it)
METRICS = {
    "http_parse_p50_ms": ("serve.http-parse", "yi6b-score"),
    "prefill_device_p50_ms": ("serve.prefill-device", "yi6b-score"),
    "decode_h2d_p50_ms": ("serve.decode-h2d", "yi6b-chat"),
    "decode_device_p50_ms": ("serve.decode-device", "yi6b-chat"),
    "decode_fetch_p50_ms": ("serve.decode-fetch", "yi6b-chat"),
    "decode_post_p50_ms": ("serve.decode-post", "yi6b-chat"),
}


@pytest.mark.parametrize("name", sorted(METRICS))
def test_data_file_names_a_reader_and_reaches_its_cell(name):
    import run as harness
    from lib import readers

    span, cell = METRICS[name]
    spec = json.loads((HERE.parent / "layer_metrics"
                       / f"{name}.json").read_text())
    assert readers.resolve(spec["reader"]) is readers.span_percentile
    assert spec["args"] == {"span": span, "p": 50, "scale": 1000}
    bench = json.loads((HERE.parent.parent / "BENCHMARK.json").read_text())
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert "workloads" not in entry
    assert (entry["unit"], entry["better"], entry["source"]) == (
        "ms", "lower", "program_span")
    reported_by = [w["name"] for w in bench["workloads"]
                   if name in {m["name"] for m in harness.cell_metrics(
                       bench, w["name"], "per_layer")}]
    assert reported_by == [cell]


def _host_events(path, prefix: str) -> list[tuple[str, float, float]]:
    """``(name, start, duration)`` in seconds, on the profiler's clock, of
    the host planes' events whose name starts with ``prefix``."""
    from jax.profiler import ProfileData

    return sorted(
        ((ev.name, ev.start_ns / 1e9, ev.duration_ns / 1e9)
         for plane in ProfileData.from_file(str(path)).planes
         if plane.name.startswith("/host:")
         for line in plane.lines for ev in line.events
         if ev.name.startswith(prefix)), key=lambda e: e[1])


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """cell → what one traced rehearsal of it left: the metrics it read,
    the kept spans and clock offset, the host plane's ``serve.*`` events
    and the harness's marks."""
    import run as harness
    from lib import xplane

    from demodel_tpu.utils import trace

    done: dict[str, dict] = {}

    def one(cell: str, capsys) -> dict:
        if cell in done:
            return done[cell]
        keep = tmp_path_factory.mktemp(cell)
        seen: dict = {}
        read = xplane.read

        def reading(path, marks, n_devices):
            seen["events"] = _host_events(path, "serve.")
            seen["marks"] = list(marks)
            return read(path, marks, n_devices)

        mp = pytest.MonkeyPatch()
        mp.setattr(xplane, "read", reading)
        try:
            code, result, reasons = harness.run(harness.parse(
                ["--workload", cell, "--seed", "2147483724", "--seconds",
                 "2", "--trace", "1", "--rehearse", "--keep", str(keep)]))
        finally:
            mp.undo()
            trace.reset()
        assert code == 0 and result["failed"] == 0
        assert reasons == ["a rehearsal is never a result"], reasons
        (line,) = [ln for ln in capsys.readouterr().out.splitlines()
                   if ln.startswith("[bench] rehearsal metrics")]
        kept = json.loads(
            (keep / f"{cell}-2147483724.json").read_text())
        done[cell] = {"metrics": json.loads(line.split(": ", 1)[1]),
                      "spans": kept["spans"],
                      "offset": kept["trace"]["clock_offset_s"],
                      "marks_found": kept["trace"]["marks_found"], **seen}
        return done[cell]

    return one


@pytest.mark.parametrize("cell", ["yi6b-score", "yi6b-chat"])
def test_rehearsed_run_reads_every_metric_of_the_cell(rehearsed, capsys,
                                                      cell):
    got = rehearsed(cell, capsys)["metrics"]
    for name, (_span, where) in METRICS.items():
        if where == cell:
            assert got.get(name) is not None and got[name] > 0, (name, got)
        else:
            assert name not in got


@pytest.mark.parametrize("cell", ["yi6b-score", "yi6b-chat"])
def test_rehearsed_spans_lie_in_the_profilers_host_plane(rehearsed, capsys,
                                                         cell):
    from lib import readers

    run = rehearsed(cell, capsys)
    assert run["marks_found"] == 10
    # what both the profiler and the span buffer saw whole: after the last
    # mark at the session's start, before the first at its end
    lo, hi = run["marks"][4] + 0.01, run["marks"][5]
    spans = sorted((s for s in run["spans"]
                    if lo <= s["ts"] and s["ts"] + s["dur"] <= hi),
                   key=lambda s: s["ts"])
    events = [(n, s - run["offset"], d) for n, s, d in run["events"]
              if lo <= s - run["offset"] and s - run["offset"] + d <= hi]
    names = {span for span, where in METRICS.values() if where == cell}
    assert names <= {s["name"] for s in spans}
    apart = []
    for name in sorted({s["name"] for s in spans}):
        mine = [s for s in spans if s["name"] == name]
        theirs = [e for e in events if e[0] == name]
        # a span that ends within a clock error of an edge may fall on the
        # other side of it in the other clock
        assert abs(len(mine) - len(theirs)) <= 1, (name, len(mine),
                                                   len(theirs))
        if len(mine) == len(theirs):
            apart += [abs(e[1] - s["ts"]) for s, e in zip(mine, theirs)]
    assert len(apart) >= len(spans) // 2
    assert readers.percentile(apart, 50) < 1e-3
