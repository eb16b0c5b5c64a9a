"""Distributed-tracing subsystem (PR 5 tentpole): span nesting across
threads and asyncio, W3C traceparent round-trips through a real dep-light
peer fetch, buffer bounds, the disabled-tracing overhead guard, Chrome
export validity, and the acceptance path — a chaos pull with
``DEMODEL_TRACE`` set produces a JSONL trace showing window-read /
budget-wait / retry / failover stitched across client and peer, which
``tools/trace_report.py`` turns into a critical-path report.

Dep-light like the chaos matrix: warm peers are no-MITM ``ProxyServer``
nodes over directly-seeded stores (no ``cryptography``).
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np
import pytest

from demodel_tpu.utils import metrics as m
from demodel_tpu.utils import trace
from demodel_tpu.utils.faults import PeerHealth

from .chaoshttp import ChaosPeer, FaultPlan, FaultSpec
from .test_fault_injection import MODEL, _assert_exact, _seed_store

REPO = Path(__file__).resolve().parent.parent

#: disabled-tracing budget per span enter/exit. A no-op span is one
#: module-global check + a shared context manager (~0.5 µs even on a
#: loaded 1-CPU CI container); 5 µs holds a 10× margin while still
#: catching an accidental allocation/clock-read on the fast path.
NOOP_BUDGET_SECS = 5e-6


@pytest.fixture(autouse=True)
def _fresh_trace_state(monkeypatch, tmp_path):
    monkeypatch.delenv("DEMODEL_TRACE", raising=False)
    monkeypatch.delenv("DEMODEL_TRACE_BUFFER", raising=False)
    monkeypatch.delenv("DEMODEL_TRACE_SAMPLE", raising=False)
    monkeypatch.delenv("DEMODEL_OBS", raising=False)
    # error-status roots in these tests must not litter the real tempdir
    # with autodump files (the recorder is ALWAYS on by design)
    monkeypatch.setenv("DEMODEL_RECORDER_DIR", str(tmp_path))
    trace.reset()
    m.HUB.reset()
    PeerHealth.reset_shared()
    yield
    trace.reset()
    PeerHealth.reset_shared()


def _records():
    return trace.buffer().snapshot()


def _by_name(name):
    return [r for r in _records() if r["name"] == name]


# ------------------------------------------------------------ fundamentals


def test_disabled_span_is_noop_and_cheap(monkeypatch):
    """The overhead guard: with observability fully OFF (DEMODEL_OBS=0 —
    the kill switch below the default observe tier), span() must return
    the shared no-op after one global check — no allocation, no clock."""
    monkeypatch.setenv("DEMODEL_OBS", "0")
    trace.reset()
    assert not trace.enabled()
    assert not trace.active()
    s = trace.span("anything", key="value")
    assert s is trace.NOOP
    assert trace.current() is None
    assert trace.traceparent() is None
    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        with trace.span("hot-path"):
            pass
    per_op = (time.perf_counter() - t0) / n
    assert per_op < NOOP_BUDGET_SECS, (
        f"disabled span enter/exit costs {per_op * 1e6:.2f}µs "
        f"(budget {NOOP_BUDGET_SECS * 1e6:.0f}µs)")


def test_wrap_is_identity_when_disabled():
    fn = lambda: 1  # noqa: E731
    assert trace.wrap(fn) is fn


def test_parent_child_nesting_same_thread():
    trace.enable()
    with trace.span("parent") as p:
        assert trace.current() is p
        with trace.span("child") as c:
            assert c.trace_id == p.trace_id
            assert c.parent_id == p.span_id
        assert trace.current() is p
    assert trace.current() is None
    recs = _records()
    assert [r["name"] for r in recs] == ["child", "parent"]  # finish order
    assert recs[0]["parent"] == recs[1]["span"]


def test_annotator_entered_and_left_once_per_span_in_order():
    """The hook that puts spans on a second clock (the serving plane
    installs ``jax.profiler.TraceAnnotation``): entered after the span
    starts, left before it finishes, once each, nested like the spans;
    and this module still imports without jax."""
    calls: list[tuple[str, str]] = []

    @contextlib.contextmanager
    def fake(name):
        calls.append(("enter", name))
        try:
            yield
        finally:
            calls.append(("exit", name))

    trace.enable()
    with trace.span("before-install"):
        pass
    trace.set_annotator(fake)
    try:
        with trace.span("outer"):
            with trace.span("inner", k=1):
                assert calls[-1] == ("enter", "inner")
        with pytest.raises(ValueError):
            with trace.span("failing"):
                raise ValueError("boom")
    finally:
        trace.set_annotator(None)
    with trace.span("after-removal"):
        pass
    assert calls == [("enter", "outer"), ("enter", "inner"),
                     ("exit", "inner"), ("exit", "outer"),
                     ("enter", "failing"), ("exit", "failing")]
    assert [r["name"] for r in _records()] == [
        "before-install", "inner", "outer", "failing", "after-removal"]
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; import demodel_tpu.utils.trace as t; "
         "assert t._annotator is None; "
         "assert not [m for m in sys.modules if m.split('.')[0] == 'jax']"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr


def test_failing_annotator_leaves_span_state_whole_and_reset_removes_it():
    """A hook that raises, on entry or on exit, is taken out and costs no
    span its state; ``reset`` takes a sound hook out too."""
    class Broken:
        def __init__(self, where):
            self.where = where

        def __enter__(self):
            if self.where == "enter":
                raise RuntimeError("hook")

        def __exit__(self, *exc):
            raise RuntimeError("hook")

    trace.enable()
    for where in ("enter", "exit"):
        trace.set_annotator(lambda name, where=where: Broken(where))
        with trace.span(f"{where}.outer") as outer:
            assert trace._annotator is None or where == "exit"
            with trace.span(f"{where}.inner") as inner:
                assert trace.current() is inner
            assert trace.current() is outer
        assert trace._annotator is None
        assert trace.current() is None
        assert trace.inflight() == []
    assert [r["name"] for r in _records()] == [
        "enter.inner", "enter.outer", "exit.inner", "exit.outer"]
    trace.set_annotator(contextlib.nullcontext)
    trace.reset()
    assert trace._annotator is None


def test_error_status_recorded():
    trace.enable()
    with pytest.raises(ValueError):
        with trace.span("doomed"):
            raise ValueError("boom")
    (rec,) = _by_name("doomed")
    assert rec["status"] == "error"
    assert "ValueError: boom" in rec["error"]


def test_span_events_carry_offsets():
    trace.enable()
    with trace.span("op") as sp:
        sp.event("retry", attempt=1)
        trace.event("ambient", via="module-helper")
    (rec,) = _by_name("op")
    names = [e["name"] for e in rec["events"]]
    assert names == ["retry", "ambient"]
    assert all(e["t"] >= 0 for e in rec["events"])


def test_thread_propagation_needs_wrap():
    """contextvars do NOT cross threading; trace.wrap captures the
    ambient span at the submit site."""
    trace.enable()

    def child_op():
        with trace.span("t-child"):
            pass

    with ThreadPoolExecutor(max_workers=1) as ex:
        with trace.span("t-root") as root:
            ex.submit(trace.wrap(child_op)).result()   # wrapped: parented
            ex.submit(child_op).result()               # bare: orphaned
    wrapped, orphan = _by_name("t-child")
    assert wrapped["parent"] == root.span_id
    assert wrapped["trace"] == root.trace_id
    assert orphan["parent"] is None
    assert orphan["trace"] != root.trace_id


def test_wrap_per_submit_survives_concurrent_workers():
    """A contextvars.Context is single-entrant: one shared wrapped fn
    across a pool raised 'cannot enter context' on the first concurrent
    pair (review finding). Wrapping PER SUBMIT gives each worker its own
    Context copy — N simultaneous children must all run and parent."""
    import threading as _threading

    trace.enable()
    gate = _threading.Barrier(4)

    def child_op(i):
        gate.wait(timeout=30)  # force 4 wrapped contexts entered at once
        with trace.span("c-child", i=i):
            pass
        return i

    with ThreadPoolExecutor(max_workers=4) as ex:
        with trace.span("c-root") as root:
            futs = [ex.submit(trace.wrap(child_op), i) for i in range(4)]
            assert sorted(f.result() for f in futs) == [0, 1, 2, 3]
    children = _by_name("c-child")
    assert len(children) == 4
    assert all(c["parent"] == root.span_id for c in children)


def test_asyncio_propagation_is_automatic():
    trace.enable()

    async def main():
        with trace.span("a-root") as root:
            async def sub(i):
                with trace.span("a-child", i=i):
                    await asyncio.sleep(0)

            await asyncio.gather(asyncio.create_task(sub(0)),
                                 asyncio.create_task(sub(1)))
            return root

    root = asyncio.run(main())
    children = _by_name("a-child")
    assert len(children) == 2
    assert all(c["parent"] == root.span_id for c in children)
    assert all(c["trace"] == root.trace_id for c in children)


def test_traceparent_roundtrip_and_malformed_headers():
    trace.enable()
    with trace.span("origin") as sp:
        tp = trace.traceparent()
        assert tp == f"00-{sp.trace_id}-{sp.span_id}-01"
        assert trace.parse_traceparent(tp) == (sp.trace_id, sp.span_id)
        hdrs = trace.inject_headers({"Range": "bytes=0-1"})
        assert hdrs["traceparent"] == tp
        assert hdrs["Range"] == "bytes=0-1"
    # peer input never raises
    for bad in ("", "junk", "00-short-ffff-01", "xx-" + "0" * 32 + "-" +
                "0" * 16 + "-01", "00-" + "g" * 32 + "-" + "1" * 16 + "-01"):
        assert trace.parse_traceparent(bad) is None
    # remote parenting: a child of a wire-carried context
    with trace.span("server-side", remote_parent=tp) as child:
        assert child.trace_id == sp.trace_id
        assert child.parent_id == sp.span_id


def test_buffer_is_bounded(monkeypatch):
    monkeypatch.setenv("DEMODEL_TRACE_BUFFER", "16")
    trace.reset()
    trace.enable()
    for i in range(100):
        with trace.span("filler", i=i):
            pass
    buf = trace.buffer()
    assert len(buf) == 16
    assert buf.dropped == 84
    # newest survive
    assert buf.snapshot()[-1]["attrs"]["i"] == 99


def test_metrics_summaries_on_exposition():
    trace.enable()
    with trace.span("window-read"):
        pass
    with trace.span("window-read"):
        pass
    label = 'trace_spans_total{span="window-read"}'
    assert m.HUB.get(label) == 2
    secs = m.HUB.get('trace_span_seconds_total{span="window-read"}')
    assert secs >= 0
    text = m.render()
    assert "# TYPE demodel_trace_spans_total counter" in text
    assert 'demodel_trace_spans_total{span="window-read"} 2' in text


def test_chrome_export_shape(tmp_path):
    trace.enable()
    with trace.span("outer", model="gpt2") as sp:
        sp.event("fault", kind="reset-at-byte")
        with trace.span("inner"):
            pass
    out = tmp_path / "chrome.json"
    n = trace.dump_chrome(str(out))
    doc = json.loads(out.read_text())
    events = doc["traceEvents"]
    assert len(events) == n == 3  # two X spans + one instant
    for ev in events:
        assert ev["ph"] in ("X", "i")
        for k in ("name", "ts", "pid", "tid", "cat"):
            assert k in ev
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
    assert any(ev["name"] == "outer:fault" for ev in events)


def test_jsonl_sink_writes_parseable_lines(tmp_path, monkeypatch):
    path = tmp_path / "sink.jsonl"
    monkeypatch.setenv("DEMODEL_TRACE", str(path))
    trace.reset()
    assert trace.enabled()
    with trace.span("a"):
        with trace.span("b"):
            pass
    lines = path.read_text().strip().splitlines()
    recs = [json.loads(ln) for ln in lines]
    assert [r["name"] for r in recs] == ["b", "a"]
    assert recs[0]["trace"] == recs[1]["trace"]


# ------------------------------------------ head sampling (serve traffic)


def test_sample_zero_drops_whole_traces(monkeypatch):
    """DEMODEL_TRACE_SAMPLE=0: a new root drops from the EXPORT and its
    descendants drop WITH it — never re-rolled into orphan fragments.
    The spans still RUN: sampling is an export-volume knob, so the
    always-on surfaces (flight recorder, stage histograms) stay whole."""
    monkeypatch.setenv("DEMODEL_TRACE_SAMPLE", "0")
    trace.enable()
    with trace.span("root") as root:
        assert isinstance(root, trace.Span)
        with trace.span("child") as child:
            assert isinstance(child, trace.Span)
            assert child.trace_id == root.trace_id
    assert _records() == []  # nothing exported
    assert {r["name"] for r in trace.recorder().snapshot()} == {
        "root", "child"}  # recorder unaffected by the export knob
    assert m.HUB.get_histogram(
        m.labeled("stage_duration_seconds", span="root")) is not None


def test_sample_one_records_everything(monkeypatch):
    monkeypatch.setenv("DEMODEL_TRACE_SAMPLE", "1.0")
    trace.enable()
    with trace.span("root"):
        with trace.span("child"):
            pass
    assert {r["name"] for r in _records()} == {"root", "child"}


def test_sample_decision_is_per_root(monkeypatch):
    """The dice roll happens once per ROOT span; children inherit the
    keep/drop decision from the ambient context."""
    monkeypatch.setenv("DEMODEL_TRACE_SAMPLE", "0.5")
    trace.enable()
    rolls = iter([0.2, 0.9, 0.2])  # keep, drop, keep (rate 0.5)
    monkeypatch.setattr(trace.random, "random", lambda: next(rolls))
    with trace.span("kept"):
        pass
    with trace.span("dropped"):
        with trace.span("dropped-child"):
            pass
    with trace.span("kept2"):
        pass
    assert [r["name"] for r in _records()] == ["kept", "kept2"]


def test_remote_parented_span_bypasses_sampling(monkeypatch):
    """A traceparent from the wire means the CALLING host already made the
    keep decision — the serving side must not drop its half of the trace."""
    monkeypatch.setenv("DEMODEL_TRACE_SAMPLE", "0")
    trace.enable()
    tp = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
    with trace.span("serve", remote_parent=tp):
        pass
    (rec,) = _by_name("serve")
    assert rec["trace"] == "ab" * 16


def test_unsampled_root_crosses_wrap(monkeypatch):
    """A dropped trace's thread fan-out must not re-roll per task: wrap()
    carries the unsampled mark across the executor boundary, so the
    task's span runs but drops from the export with its root."""
    monkeypatch.setenv("DEMODEL_TRACE_SAMPLE", "0")
    trace.enable()
    out = []
    with trace.span("root"):
        fn = trace.wrap(lambda: out.append(trace.span("task")))
    with ThreadPoolExecutor(max_workers=1) as ex:
        ex.submit(fn).result()
    (task,) = out
    assert isinstance(task, trace.Span)
    task.finish()
    assert _records() == []


def test_malformed_sample_rate_records_everything(monkeypatch):
    monkeypatch.setenv("DEMODEL_TRACE_SAMPLE", "lots")
    trace.enable()
    with trace.span("root"):
        pass
    assert _by_name("root")


# ----------------------------------------------------- streaming-sink spans


def _sink_with_fake_delivery(monkeypatch, delivered):
    from demodel_tpu.sink import streaming as st_mod
    from demodel_tpu.sink.hbm import Placement

    def fake_deliver(store, name, key, mesh, plan, cast_to, buffer=None,
                     ici_complete=None):
        delivered.append(name)
        return Placement(mesh_desc="fake")

    monkeypatch.setattr(st_mod, "deliver_file", fake_deliver)
    return st_mod.StreamingSink(store=None, overlap=True)


def test_streaming_sink_deliver_span_stitches_to_submitter(monkeypatch):
    """sink-deliver runs on the sink's worker thread; the submit site's
    ambient span must reach it as its trace parent (carried across the
    queue as a traceparent), so pull traces show where HBM time went."""
    trace.enable()
    delivered: list[str] = []
    sink = _sink_with_fake_delivery(monkeypatch, delivered)

    class Art:
        name = "model-00001-of-00002.safetensors"
        key = "k" * 16
        media_type = ""

    with trace.span("pull-root") as root:
        sink.submit(Art())
        root_trace = root.trace_id
    sink.finish(block=False)
    assert delivered == [Art.name]
    (rec,) = _by_name("sink-deliver")
    assert rec["trace"] == root_trace
    assert rec["attrs"]["file"] == Art.name
    assert rec["attrs"]["tensors"] == 0  # fake placement carries none


def test_streaming_sink_respects_head_sampling(monkeypatch):
    """A sampled-OUT pull must not leak orphan sink-deliver roots from the
    worker side of the queue: the suppression verdict crosses with the
    item (contextvars cannot follow it there)."""
    monkeypatch.setenv("DEMODEL_TRACE_SAMPLE", "0")
    trace.enable()
    delivered: list[str] = []
    sink = _sink_with_fake_delivery(monkeypatch, delivered)

    class Art:
        name = "model.safetensors"
        key = "k" * 16
        media_type = ""

    with trace.span("pull-root"):  # unsampled root (rate 0)
        sink.submit(Art())
    sink.finish(block=False)
    assert delivered == [Art.name]  # delivery itself still happened
    assert _records() == []


def test_streaming_sink_budget_wait_span(monkeypatch):
    """A standalone producer charging the byte budget at submit() gets a
    sink-budget-wait span — the stall the budget can introduce is visible
    in the trace, not silent."""
    import numpy as np_mod

    trace.enable()
    delivered: list[str] = []
    sink = _sink_with_fake_delivery(monkeypatch, delivered)

    class Art:
        name = "model.safetensors"
        key = "k" * 16
        media_type = ""
        buffer = np_mod.zeros(64, dtype=np_mod.uint8)

    sink.submit(Art())
    sink.finish(block=False)
    (rec,) = _by_name("sink-budget-wait")
    assert rec["attrs"] == {"file": Art.name, "bytes": 64}


# --------------------------------------------- wire round-trip (dep-light)


@contextlib.contextmanager
def _warm_nodes(tmp_path, count=1, n_shards=3):
    """``count`` live no-MITM peers all seeded with the SAME model bytes
    (same tag/seed → same store keys and digests), so window failover has
    a real alternative source."""
    from demodel_tpu.config import ProxyConfig
    from demodel_tpu.proxy import ProxyServer
    from demodel_tpu.store import Store

    nodes, seeded = [], None
    try:
        for i in range(count):
            cfg = ProxyConfig(
                host="127.0.0.1", port=0, mitm_hosts=[], no_mitm=True,
                cache_dir=tmp_path / f"peer{i}-cache",
                data_dir=tmp_path / f"peer{i}-data")
            store = Store(cfg.cache_dir / "proxy")
            try:
                seeded = _seed_store(store, "tracetag", n_shards, seed=7)
            finally:
                store.close()
            node = ProxyServer(cfg, verbose=False)
            node.start()
            nodes.append(node)
        yield nodes, seeded
    finally:
        for node in nodes:
            node.stop()


@pytest.fixture()
def _fast_wire(monkeypatch):
    monkeypatch.setenv("DEMODEL_RETRY_BASE_MS", "20")
    monkeypatch.setenv("DEMODEL_RETRY_DEADLINE", "60")
    monkeypatch.setenv("DEMODEL_BREAKER_COOLDOWN", "1")
    monkeypatch.setenv("DEMODEL_PROXY_IDLE_TIMEOUT", "1")


def test_traceparent_roundtrip_through_real_peer_fetch(tmp_path, _fast_wire):
    """A client window read against a REAL dep-light peer (through the
    Python shim that extracts traceparent) stitches: the server-side span
    carries the client span's trace id and parents on it."""
    from demodel_tpu.sink.remote import PeerBlobReader

    trace.enable()
    with _warm_nodes(tmp_path) as (nodes, (tensors, files, _)):
        plan = FaultPlan()  # no faults: pure propagation
        with ChaosPeer(nodes[0].url, plan) as shim:
            f = files[0]
            reader = PeerBlobReader(shim.url, f["key"], f["size"])
            out = np.empty(f["size"], dtype=np.uint8)
            assert reader.pread_into(f["key"], out, 0) == f["size"]

    (client,) = _by_name("window-read")
    serves = _by_name("serve.peer")
    assert serves, "peer shim emitted no server-side spans"
    stitched = [s for s in serves if s["trace"] == client["trace"]]
    assert stitched, (serves, client)
    assert any(s["parent"] == client["span"] for s in stitched)


# ------------------------------------------------- acceptance: chaos pull


def test_traced_chaos_pull_end_to_end(tmp_path, _fast_wire, monkeypatch):
    """The ISSUE acceptance path: a chaos pull (mid-window RST, failover
    to a second warm peer) with ``DEMODEL_TRACE`` set produces a JSONL
    trace that (a) parses, (b) shows window-read / budget-wait /
    retry / failover stitched across client and peer via traceparent,
    (c) converts to valid Chrome trace-event JSON, and (d) yields a
    critical-path report from ``tools/trace_report.py``."""
    jsonl = tmp_path / "pull.jsonl"
    monkeypatch.setenv("DEMODEL_TRACE", str(jsonl))
    # this test pins the TRACE SHAPE of a faulted pull; the adaptive
    # tuner (its own root span, sub-window splitting, a tick thread
    # competing for this 1-CPU box) is pinned off — its in-pull
    # integration is covered by test_tuner.py
    monkeypatch.setenv("DEMODEL_TUNER", "0")
    trace.reset()

    from demodel_tpu.sink.remote import pull_manifest_to_hbm

    with _warm_nodes(tmp_path, count=2) as (nodes, (tensors, files, _)):
        plan = FaultPlan(
            FaultSpec(kind="reset-at-byte", path="/peer/object",
                      times=1, at_byte=1 << 20, min_body=1 << 21),
        )
        with ChaosPeer(nodes[0].url, plan) as shim0, \
                ChaosPeer(nodes[1].url, FaultPlan()) as shim1:
            report, placed = pull_manifest_to_hbm(
                MODEL, [shim0.url, shim1.url])
    _assert_exact(placed, tensors)
    assert plan.fired("reset-at-byte") == 1

    # (a) the JSONL parses, line by line
    recs = [json.loads(ln) for ln in
            jsonl.read_text().strip().splitlines()]
    names = {r["name"] for r in recs}
    assert {"pull", "manifest-discovery", "window-read", "budget-wait",
            "place", "http.request", "serve.peer"} <= names, names

    # (b) one trace end-to-end: everything hangs off the pull root,
    # including the peer-side serve spans (traceparent stitch), and the
    # faulted window carries retry + failover events
    (root,) = [r for r in recs if r["name"] == "pull"]
    assert root["parent"] is None
    in_trace = [r for r in recs if r["trace"] == root["trace"]]
    assert {"window-read", "budget-wait", "serve.peer"} <= {
        r["name"] for r in in_trace}
    events = [(e["name"], e.get("attrs", {}))
              for r in in_trace for e in r.get("events", ())]
    assert any(n == "retry" for n, _ in events), events
    assert any(n == "failover" for n, _ in events), events
    assert any(n == "fault" and a.get("kind") == "reset-at-byte"
               for n, a in events), events
    # the faulted window failed over to the OTHER peer, resuming at the
    # received offset. The linger-0 RST discards whatever the client had
    # not yet drained from the kernel buffer, so a slow-scheduled reader
    # legitimately resumes at 0 — exact positive-offset resume is pinned
    # by the Range-log tests in test_fault_injection; here the contract
    # is the trace shape, and the retry event must agree with the
    # failover on where the resume happened
    failovers = [a for n, a in events if n == "failover"]
    assert failovers, events
    assert all(a["from_peer"] != a["to_peer"] for a in failovers)
    retry_offsets = {a["resume_at"] for n, a in events if n == "retry"}
    assert any(a["resume_at"] in retry_offsets for a in failovers), events

    # (c+d) the report tool: one JSON line + a Perfetto-loadable file
    chrome = tmp_path / "pull.json"
    proc = subprocess.run(
        [sys.executable, "tools/trace_report.py", str(jsonl),
         "--chrome", str(chrome)],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["metric"] == "trace_report"
    assert out["spans"] == len(recs)
    assert out["critical_path"], out
    assert out["critical_path"][0]["name"] == "pull"
    assert "window-read" in out["stages"]
    assert out["stages"]["window-read"]["count"] >= 3
    assert abs(out["wall_secs"] - root["dur"]) < 1e-6

    doc = json.loads(chrome.read_text())
    events = doc["traceEvents"]
    assert events and out["chrome_events"] == len(events)
    for ev in events:
        assert ev["ph"] in ("X", "i")
        assert isinstance(ev["ts"], float) and ev["ts"] > 0
    assert any(ev["name"] == "pull" for ev in events)


def test_trace_report_critical_path_synthetic(tmp_path):
    """The critical-path walk on a hand-built trace: root(10) covers
    fetch(7, ends at 9) which covers wait(6, ends at 8.5) — the chain and
    self-times must come out exactly."""
    rows = [
        {"trace": "t1", "span": "r", "parent": None, "name": "root",
         "ts": 100.0, "dur": 10.0, "pid": 1, "tid": 1, "status": "ok"},
        {"trace": "t1", "span": "f", "parent": "r", "name": "fetch",
         "ts": 102.0, "dur": 7.0, "pid": 1, "tid": 1, "status": "ok"},
        {"trace": "t1", "span": "w", "parent": "f", "name": "wait",
         "ts": 102.5, "dur": 6.0, "pid": 1, "tid": 1, "status": "ok"},
        # an early, short sibling that must NOT appear on the path
        {"trace": "t1", "span": "s", "parent": "r", "name": "setup",
         "ts": 100.1, "dur": 0.5, "pid": 1, "tid": 1, "status": "ok"},
    ]
    p = tmp_path / "synth.jsonl"
    p.write_text("\n".join(json.dumps(r) for r in rows))
    proc = subprocess.run(
        [sys.executable, "tools/trace_report.py", str(p)],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout)
    chain = [(e["name"], e["secs"]) for e in out["critical_path"]]
    assert chain[:3] == [("root", 10.0), ("fetch", 7.0), ("wait", 6.0)]
    # root's critical cover: fetch(7) then setup(0.5) fits before it
    assert out["critical_path"][0]["self_secs"] == pytest.approx(2.5)
    assert out["critical_path"][1]["self_secs"] == pytest.approx(1.0)
    assert out["wall_secs"] == 10.0
    assert out["stages"]["root"]["count"] == 1


def test_trace_report_terminates_on_zero_duration_spans(tmp_path):
    """Regression (review finding): a zero-duration span ending exactly
    at its parent's end used to be re-selected forever by the gating-
    child walk — the reporter must terminate and still report."""
    rows = [
        {"trace": "t", "span": "r", "parent": None, "name": "root",
         "ts": 0.0, "dur": 10.0, "pid": 1, "tid": 1, "status": "ok"},
        {"trace": "t", "span": "z", "parent": "r", "name": "zero",
         "ts": 10.0, "dur": 0.0, "pid": 1, "tid": 1, "status": "ok"},
        {"trace": "t", "span": "w", "parent": "r", "name": "work",
         "ts": 1.0, "dur": 8.0, "pid": 1, "tid": 1, "status": "ok"},
    ]
    p = tmp_path / "zero.jsonl"
    p.write_text("\n".join(json.dumps(r) for r in rows))
    proc = subprocess.run(
        [sys.executable, "tools/trace_report.py", str(p)],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout)
    assert out["wall_secs"] == 10.0
    names = [e["name"] for e in out["critical_path"]]
    assert names[0] == "root" and "zero" in names
