"""Set-up accounted for inside the program: the counters and the span of a
program made ready (``utils/compile_cache.py``, the engine's two dispatch
sites), ``load_model``'s spans, and the benchmark's readers of them
(``benchmark/lib/program.py``). Counts only: CPU, toy models."""

from __future__ import annotations

import sys
import threading
from http.server import ThreadingHTTPServer
from pathlib import Path

import jax
import pytest

from demodel_tpu import serve
from demodel_tpu.models import llama, longcat_flash
from demodel_tpu.serve import GenEngine
from demodel_tpu.utils import compile_cache, trace
from demodel_tpu.utils.metrics import HUB, labeled, parse_labels

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "benchmark"))

PHASES = ("trace_s", "lower_s", "backend_s")


def _programs() -> dict[str, float]:
    """The hub's two families of a program made ready, by sample name."""
    return {k: v for k, v in HUB.snapshot().items()
            if k.startswith(("gen_programs_ready_total{",
                             "gen_program_seconds_total{"))}


def _ready(snap: dict[str, float], stage: str) -> float:
    # .get: another file's test on this worker may have reset the hub
    return sum(snap.get(labeled("gen_programs_ready_total", how=how,
                                stage=stage), 0)
               for how in ("loaded", "compiled"))


@pytest.fixture()
def exporting(monkeypatch):
    for var in ("DEMODEL_TRACE", "DEMODEL_TRACE_SAMPLE", "DEMODEL_OBS"):
        monkeypatch.delenv(var, raising=False)
    trace.reset()
    trace.enable()
    yield
    trace.reset()


def _engine(**kw):
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.key(2), cfg)
    return GenEngine(params, cfg, max_batch=2, queue_limit=8,
                     max_new_tokens=4, kv_mb=1, block_tokens=16, **kw).start()


def _spans(name: str) -> list[dict]:
    return sorted((r for r in trace.buffer().snapshot()
                   if r["name"] == name), key=lambda r: r["ts"])


class TestProgramReady:
    """One shape served three times: the first run makes a prefill and a
    decode program ready, inside a span each; the second makes none; after
    ``jax.clear_caches()`` the third makes both again, which only the
    listener can know."""

    PROMPT = [3, 1, 4, 1, 5]        # 5 + 4 positions: one width for life

    @pytest.fixture()
    def served(self, exporting):
        engine = _engine()
        marks = [_programs()]
        try:
            outs = []
            for i in range(3):
                if i == 2:
                    jax.clear_caches()
                outs.append(engine.generate(self.PROMPT, 4, timeout=240))
                marks.append(_programs())
        finally:
            engine.stop()
        assert outs[0] == outs[1] == outs[2]
        return {"marks": marks, "spans": _spans(compile_cache.READY_SPAN),
                "all": trace.buffer().snapshot(),
                "programs": engine.describe()["programs"]}

    @pytest.mark.parametrize("stage,shape,parent", [
        ("prefill", {"prompt": 5}, "serve.prefill-device"),
        ("decode", {"batch": 1, "width": 512}, "serve.decode-device")])
    def test_one_span_a_stage_with_shape_phases_and_how(self, served, stage,
                                                        shape, parent):
        [span] = [s for s in served["spans"] if s["attrs"]["stage"] == stage]
        attrs = span["attrs"]
        assert {k: attrs[k] for k in shape} == shape
        assert attrs["how"] in ("loaded", "compiled")
        assert all(attrs[p] > 0 for p in PHASES)
        # nested jits' traces are counted once: the phases fit the call
        assert sum(attrs[p] for p in PHASES) <= span["dur"]
        by_id = {r["span"]: r for r in served["all"]}
        assert by_id[span["parent"]]["name"] == parent

    def test_counters_follow_the_programs_not_the_requests(self, served):
        born, first, second, third = served["marks"]
        assert len(served["spans"]) == 2        # none for requests 2 and 3
        for stage in ("prefill", "decode"):
            assert _ready(first, stage) - _ready(born, stage) == 1
            assert _ready(third, stage) - _ready(second, stage) == 1
        assert second == first
        # the engine thread dispatches nothing but its two programs
        assert _ready(third, "other") == _ready(born, "other")

    def test_seconds_are_the_spans_phases(self, served):
        born, first = served["marks"][:2]
        for span in served["spans"]:
            stage, how = span["attrs"]["stage"], span["attrs"]["how"]
            got = {phase: first.get(name, 0) - born.get(name, 0)
                   for phase in ("trace", "lower", "load", "compile")
                   for name in [labeled("gen_program_seconds_total",
                                        phase=phase, stage=stage)]}
            backend = "load" if how == "loaded" else "compile"
            assert got["trace"] == pytest.approx(span["attrs"]["trace_s"],
                                                 abs=2e-6)
            assert got["lower"] == pytest.approx(span["attrs"]["lower_s"],
                                                 abs=2e-6)
            assert got[backend] == pytest.approx(span["attrs"]["backend_s"],
                                                 abs=2e-6)
            assert sum(got.values()) == pytest.approx(
                sum(span["attrs"][p] for p in PHASES), abs=1e-5)

    def test_describe_agrees_with_the_hub_and_names_the_last(self, served):
        programs, hub = served["programs"], served["marks"][-1]
        for name, value in hub.items():
            family, got = parse_labels(name)
            if family == "gen_programs_ready_total":
                assert programs["ready"][got["stage"]][got["how"]] == value
        for phase, secs in programs["seconds"].items():
            assert secs == pytest.approx(sum(
                v for name, v in hub.items()
                if f'phase="{phase}"' in name), abs=1e-5)
        last = programs["last"]
        assert (last["stage"], last["shape"]) == ("decode", [1, 512])
        assert last["how"] in ("loaded", "compiled") and last["backend_s"] > 0

    def test_counted_with_observability_off(self, monkeypatch):
        monkeypatch.setenv("DEMODEL_OBS", "0")
        monkeypatch.delenv("DEMODEL_TRACE", raising=False)
        trace.reset()
        try:
            engine = _engine()
            before = _programs()
            try:
                assert trace.mode() == "off"
                engine.generate(self.PROMPT, 4, timeout=240)
            finally:
                engine.stop()
            recorded = (trace.buffer().snapshot()
                        + trace.recorder().snapshot())
        finally:
            monkeypatch.undo()
            trace.reset()
        after = _programs()
        assert recorded == []
        for stage in ("prefill", "decode"):
            assert _ready(after, stage) - _ready(before, stage) == 1


def test_a_family_of_two_sublayers_a_layer_is_counted_alike(exporting):
    """LongCat-Flash (a pool of twice the model's layers, three statistics
    a step) through the same two dispatch sites: a prompt of 5 makes ready
    a prefill and the narrow step (two tiles of blocks of 2: 64
    positions); one of 63 a second prefill and, where its row passes two
    tiles, the wide step (256 slots): four programs, a span and a count
    each, and none for what the third request repeats."""
    cfg = longcat_flash.LongcatFlashConfig.tiny()
    params = longcat_flash.init_params(jax.random.key(2), cfg)
    engine = GenEngine(params, cfg, max_batch=2, queue_limit=8,
                       max_new_tokens=4, kv_mb=1, block_tokens=2).start()
    born = _programs()
    try:
        for prompt in (5, 63, 63):
            engine.generate(list(range(1, prompt + 1)), 4, timeout=240)
        described = engine.describe()["programs"]
    finally:
        engine.stop()
    after = _programs()
    shapes = [(s["attrs"]["stage"],
               s["attrs"].get("prompt") or s["attrs"]["width"])
              for s in _spans(compile_cache.READY_SPAN)]
    assert shapes == [("prefill", 5), ("decode", 64), ("prefill", 63),
                      ("decode", 512)]
    for stage in ("prefill", "decode"):
        assert _ready(after, stage) - _ready(born, stage) == 2
    assert _ready(after, "other") == _ready(born, "other")
    assert (described["last"]["stage"], described["last"]["shape"]) \
        == ("decode", [1, 512])


class TestLoadModelSpans:
    """``serve.load_model`` over the fake hub: the family loader and the
    engine's birth have their spans, and every program either made ready
    is counted (the cache is placed before the loader runs)."""

    @pytest.fixture()
    def loaded(self, exporting, tmp_path):
        pytest.importorskip("cryptography")     # ProxyConfig → pki
        import chip_smoke
        from demodel_tpu.config import ProxyConfig
        from demodel_tpu.parallel import make_mesh
        from tests.fake_registries import make_hf_handler

        config = dict(chip_smoke.TINYLLAMA, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=8, num_key_value_heads=4,
                      vocab_size=256)
        files = chip_smoke.build_checkpoint(config, n_shards=2)
        hub = ThreadingHTTPServer(
            ("127.0.0.1", 0), make_hf_handler({chip_smoke.MODEL: files}))
        threading.Thread(target=hub.serve_forever, daemon=True).start()
        before = _programs()
        engine = None
        try:
            engine = serve.load_model(
                chip_smoke.MODEL, ProxyConfig(
                    host="127.0.0.1", port=0, mitm_hosts=[], no_mitm=True,
                    cache_dir=tmp_path / "cache", data_dir=tmp_path / "data",
                    use_ecdsa=True),
                endpoint=f"http://127.0.0.1:{hub.server_port}",
                mesh=make_mesh(1), max_batch=2, kv_mb=4)
            described = engine.describe()
        finally:
            if engine is not None:
                engine.stop()
            serve.install(None)
            hub.shutdown()
            hub.server_close()
        return {"engine": engine, "before": before, "after": _programs(),
                "described": described, "spans": trace.buffer().snapshot()}

    def test_the_spans_cover_load_model(self, loaded):
        [load] = _spans("serve.load-model")
        [built] = _spans("serve.build-params")
        [born] = _spans("serve.engine-start")
        inside = [r for r in loaded["spans"] if r["parent"] == load["span"]]
        # the pull's own spans, then the loader's, all inside load-model
        names = [r["name"] for r in sorted(inside, key=lambda r: r["ts"])]
        assert {"registry-fetch", "sink-deliver"} <= set(names)
        assert names[-1] == "serve.build-params"
        assert all(load["ts"] - 1e-3 <= r["ts"] and r["ts"] + r["dur"]
                   <= load["ts"] + load["dur"] + 1e-3 for r in inside)
        assert born["parent"] is None
        assert born["ts"] >= load["ts"] + load["dur"] - 1e-3
        engine = loaded["engine"]
        leaves = jax.tree.leaves(engine.params)
        assert built["attrs"] == {
            "model_type": "llama", "tensors": len(leaves),
            "bytes": sum(a.nbytes for a in leaves)}
        assert born["attrs"] == {
            "max_batch": 2, "kv_mb": 4,
            "pool_bytes": sum(a.nbytes for a in engine.pool.arrays)}

    def test_the_loaders_programs_are_counted_as_other(self, loaded):
        before, after = loaded["before"], loaded["after"]
        assert _ready(after, "other") > _ready(before, "other")
        for stage in ("prefill", "decode"):
            assert _ready(after, stage) == _ready(before, stage)
        ready = loaded["described"]["programs"]["ready"]
        assert sum(ready["other"].values()) == _ready(after, "other")
        assert loaded["described"]["programs"]["last"]["stage"] == "other"


# ----------------------------------------------- the benchmark's readers


def _observed(**kw):
    from lib import readers

    return readers.Observed(t0=100.0, t1=148.0, **kw)


READY = 'gen_programs_ready_total{how="%s",stage="%s"}'
SECS = 'gen_program_seconds_total{phase="%s",stage="%s"}'


class TestProgramReaders:
    HUB_BEFORE = {READY % ("loaded", "decode"): 6, READY % ("loaded", "other"): 50,
                  READY % ("compiled", "prefill"): 2,
                  SECS % ("trace", "decode"): 3.0, SECS % ("lower", "decode"): 2.5,
                  SECS % ("load", "decode"): 4.5, SECS % ("trace", "other"): 0.5,
                  SECS % ("compile", "prefill"): 9.0,
                  "gen_new_shapes_total{stage=\"decode\"}": 6}

    @pytest.mark.parametrize("pattern,at,want", [
        (r"^gen_programs_ready_total\{", "start", 58),
        (r"^gen_program_seconds_total\{", "start", 19.5),
        (r'^gen_program_seconds_total\{phase="(trace|lower)"', "start", 6.0),
        (r'^gen_program_seconds_total\{.*stage="decode"', "start", 10.0),
        (r"^gen_programs_ready_total\{", "window", 0),
        (r"^gen_no_such_total\{", "start", None),
        (r"^gen_no_such_total\{", "window", None)])
    def test_counters(self, pattern, at, want):
        from lib import program

        obs = _observed(hub_before=self.HUB_BEFORE,
                        hub_after=dict(self.HUB_BEFORE))
        got = program.counters(obs, pattern, at)
        assert got == want and (want is None or got is not None)

    def test_counters_over_a_window_that_made_a_program_ready(self):
        from lib import program

        after = dict(self.HUB_BEFORE)
        after[READY % ("compiled", "decode")] = 1    # born inside the window
        after[READY % ("loaded", "decode")] += 1
        obs = _observed(hub_before=self.HUB_BEFORE, hub_after=after)
        assert program.counters(obs, r"^gen_programs_ready_total\{",
                                "window") == 2
        with pytest.raises(ValueError, match="start"):
            program.counters(obs, "x", "end")

    def test_span_attr_ratio(self):
        from lib import program

        def step(ts, **attrs):
            return {"name": "serve.decode-step", "ts": ts, "dur": 0.02,
                    "attrs": attrs}

        spans = [step(101.0, kv_positions_read=256, kv_positions_width=1024),
                 step(102.0, kv_positions_read=768, kv_positions_width=1024),
                 step(103.0, batch=3),                      # carries neither
                 step(99.0, kv_positions_read=1, kv_positions_width=1),
                 {"name": "serve.prefill", "ts": 104.0, "dur": 0.01,
                  "attrs": {"kv_positions_read": 5, "kv_positions_width": 5}}]
        args = ("serve.decode-step", "kv_positions_read", "kv_positions_width")
        assert program.span_attr_ratio(_observed(spans=spans), *args) == 50.0
        assert program.span_attr_ratio(_observed(spans=spans[2:]),
                                       *args) is None
        assert program.span_attr_ratio(_observed(), *args) is None

    def test_the_table_gained_the_eight_and_their_span(self):
        import json

        import run as harness

        root = Path(__file__).resolve().parent.parent
        bench = json.loads((root / "BENCHMARK.json").read_text())
        names = [m["name"] for m in bench["per_layer"]]
        at = names.index("programs_ready_count")    # later PRs add behind
        new = {m["name"]: m for m in bench["per_layer"][at:at + 8]}
        assert list(new) == [
            "programs_ready_count", "programs_ready_s",
            "program_trace_lower_s", "decode_programs_ready_s",
            "programs_ready_in_window", "load_build_params_s",
            "engine_start_s", "kv_read_fill"]
        fill = new.pop("kv_read_fill")
        # no list: the cells that report itl_p50_ms are the decode cells
        assert (fill["layer"], fill["moves"]) == ("scheduler", "itl_p50_ms")
        assert all((m["layer"], m["moves"]) == ("boot", "setup_s")
                   for m in new.values())
        assert not any("workloads" in m for m in (fill, *new.values()))
        order, _programs_spans = harness.engine_spans()
        assert order[0] == compile_cache.READY_SPAN
