"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Boots the configuration through the product's own path (a seeded
checkpoint on a loopback hub → ``serve.load_model`` → ``RestoreServer``
``/generate``), warms every shape the cell's traffic reaches, opens the
measured window, drives the traffic file's loop against HTTP from client
threads, and afterwards holds what the window served against the float32
reference. The last line of standard output is the result as one JSON
object; every line before it is information.

Everything particular to a cell is data found by name from
``BENCHMARK.json``: ``configs/<config>.json``, ``traffic/<traffic>.json``,
``cells/<workload>.json`` (what ``correct`` samples and its limits),
``end_to_end/<metric>.json`` and ``layer_metrics/<metric>.json`` (the
reader of each metric), ``spans/*.json`` (the host spans idle time is
booked to). What knows the architecture is the file of
``lib/families/`` that the configuration's ``model_type`` names. See
``README.md`` beside this file.

Without a TPU (or with fewer chips than the cell asks for) this exits 1 and
prints no result. ``--rehearse`` is the one exception, for finding faults
on the CPU: the same code at a toy model size, counts only, and a last
line that says ``"correct": false`` and carries no metric.
"""

from __future__ import annotations

import time

T_START = time.time()      # set-up counts from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT))

from lib import xplane  # noqa: E402  (no JAX until a trace is read)

MODEL_ID = "bench/model"


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def load_json(path: Path) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


class Phases:
    """Seconds of each set-up phase, by the host's clock."""

    def __init__(self) -> None:
        self.seconds: dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        t = time.perf_counter()
        try:
            yield
        finally:
            self.seconds[name] = self.seconds.get(name, 0.0) \
                + time.perf_counter() - t


class Compiles:
    """Every XLA compilation and every load from the persistent cache,
    with the time it ended: none may fall inside the window. JAX reports a
    load as a compilation too (its duration is the retrieval), so the
    programs compiled here are the compilations less the cache hits."""

    def __init__(self) -> None:
        import jax

        self.events: list[tuple[float, str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.time(), "compile", secs))
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.events.append((time.time(), "cache_load", secs))

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.events.append((time.time(), "cache_hit", 0.0))

    def between(self, t0: float, t1: float) -> list[tuple[float, str, float]]:
        return [e for e in self.events if t0 <= e[0] <= t1
                and e[1] != "cache_load"]

    def count(self, kind: str) -> int:
        return sum(1 for e in self.events if e[1] == kind)

    def summary(self) -> str:
        n = self.count("compile")
        secs = sum(e[2] for e in self.events if e[1] == "compile")
        hits = self.count("cache_hit")
        load = sum(e[2] for e in self.events if e[1] == "cache_load")
        return (f"{n} programs made ready in {secs:.1f} s, {hits} of them "
                f"loaded from the persistent cache in {load:.1f} s, "
                f"{n - hits} compiled here")


def cell_metrics(bench: dict, workload: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports: those
    that list it, and those with no ``workloads`` key whose end-to-end
    metric (``moves``) it reports."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def failure_of(rec, vocab: int) -> str | None:
    """Why a request counts as failed, or None."""
    if rec.error is not None:
        return rec.error
    if rec.status != 200:
        return f"status {rec.status}"
    if any(not 0 <= t < vocab for t in rec.tokens):
        return "token outside the vocabulary"
    if rec.done and len(rec.tokens) != rec.max_new:
        return f"{len(rec.tokens)} tokens for {rec.max_new} asked"
    if not rec.done and not rec.cut:
        return "neither finished nor cut"
    return None


def sample_served(records, seed: int, n: int) -> list:
    """The requests ``correct`` compares: of those the window finished, the
    longest and a seeded draw of the others, up to ``n``; where fewer
    finished, those the window's end cut, longest reply first."""
    import numpy as np

    done = [r for r in records if r.done and r.tokens]
    done.sort(key=lambda r: -(len(r.prompt) + len(r.tokens)))
    picked = done[:1]
    rest = done[1:]
    order = np.random.default_rng([seed, 1_000_081]).permutation(len(rest))
    picked += [rest[i] for i in order[:max(0, n - len(picked))]]
    cut = sorted((r for r in records if r.cut and r.tokens),
                 key=lambda r: -len(r.tokens))
    return picked + cut[:max(0, n - len(picked))]


class HostWatch:
    """What the host did to the process during the window, for telling a
    stall of the machine from a slow program: how late a thread that only
    sleeps 20 ms at a time woke (the interpreter lock held, or the whole
    process not scheduled), and the CPU time the hypervisor took from this
    machine (``steal`` in ``/proc/stat``)."""

    def __init__(self) -> None:
        self._stop = threading.Event()
        self.late: list[float] = []
        self._steal0 = self._steal()
        self._thread = threading.Thread(target=self._tick, daemon=True,
                                        name="bench-hostwatch")
        self._thread.start()

    @staticmethod
    def _steal() -> float:
        try:
            with open("/proc/stat", encoding="ascii") as f:
                return int(f.readline().split()[8]) / os.sysconf("SC_CLK_TCK")
        except (OSError, ValueError, IndexError):
            return float("nan")

    def _tick(self) -> None:
        while not self._stop.is_set():
            t = time.perf_counter()
            time.sleep(0.02)
            self.late.append(time.perf_counter() - t - 0.02)

    def stop(self) -> str:
        self._stop.set()
        self._thread.join(timeout=5)
        worst = sorted(self.late)[-3:]
        return (f"host during the window: a 20 ms sleeper woke late by at "
                f"most {[round(x * 1e3, 1) for x in worst]} ms; hypervisor "
                f"steal {self._steal() - self._steal0:.2f} CPU-s")


def drop_marks(marks: list[float]) -> None:
    """Annotations in the profiler's trace at host times we keep: the two
    clocks' offset is read from them (``xplane.read``)."""
    import jax

    for _ in range(5):
        marks.append(time.time())
        with jax.profiler.TraceAnnotation(xplane.MARK):
            pass
        time.sleep(0.002)


def parse(argv: list[str] | None = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy model on the CPU: counts only, never correct")
    ap.add_argument("--traffic", default=None,
                    help="with --rehearse only: another traffic file than "
                         "the cell's (the open loop has no cell yet)")
    ap.add_argument("--control", choices=("int8",), default=None,
                    help="also read the control of `correct`: the reference "
                         "in this lower precision, on the same requests")
    ap.add_argument("--keep", type=Path, default=None,
                    help="write the reduced trace and the observations here")
    return ap.parse_args(argv)


def main(argv: list[str] | None = None) -> int:
    code, result, _reasons = run(parse(argv))
    if result is not None:
        print(json.dumps(result), flush=True)
    return code


def run(args) -> tuple[int, dict | None, list[str]]:  # noqa: C901
    """One run: ``(exit code, the result line or None, why not correct)``."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"no workload {args.workload!r}; there are {sorted(cells)}",
              file=sys.stderr)
        return 2, None, []
    cell = cells[args.workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg_doc = load_json(ROOT / config["file"])
    if args.traffic and not args.rehearse:
        print("--traffic is for rehearsals: a cell's traffic is the one "
              "BENCHMARK.json names", file=sys.stderr)
        return 2, None, []
    traffic = load_json(HERE / "traffic"
                        / f"{args.traffic or cell['traffic']}.json")
    cell_doc = load_json(HERE / "cells" / f"{cell['name']}.json")
    settings = cfg_doc["benchmark"]
    model = {k: v for k, v in cfg_doc.items() if k != "benchmark"}
    chips = int(cell["chips"])

    if args.rehearse:   # the family's toy sizes, on the CPU: counts only
        os.environ["JAX_PLATFORMS"] = "cpu"     # before anything imports JAX
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                                   f"--xla_force_host_platform_device_count="
                                   f"{chips}").strip()
        from lib import families

        model.update(families.of(model).rehearsal(model))
    import jax

    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse and dev.platform != "tpu":
        print(f"benchmark: no TPU: jax reports platform {dev.platform!r}",
              file=sys.stderr)
        return 1, None, []
    if len(devices) < chips:
        print(f"benchmark: {args.workload} needs {chips} chips, jax reports "
              f"{len(devices)}", file=sys.stderr)
        return 1, None, []
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    say(f"device: {device}; cell {cell['name']} = {cell['config']} x "
        f"{cell['traffic']} on {chips} chip(s), seed {args.seed}, "
        f"{args.seconds:g} s, trace {args.trace}"
        + (" [REHEARSAL: counts only]" if args.rehearse else ""))
    peaks_doc = load_json(HERE / "peaks.json")
    if not args.rehearse and dev.device_kind not in peaks_doc["devices"]:
        print(f"benchmark: no peaks for device kind {dev.device_kind!r} in "
              "peaks.json", file=sys.stderr)
        return 1, None, []
    peaks = peaks_doc["devices"].get(dev.device_kind, {})

    from lib import checkpoint, hub, loadgen, readers, reference

    from demodel_tpu import native, serve
    from demodel_tpu.config import ProxyConfig
    from demodel_tpu.parallel.mesh import make_mesh
    from demodel_tpu.restore.server import RestoreRegistry, RestoreServer
    from demodel_tpu.store import Store
    from demodel_tpu.utils import compile_cache
    from demodel_tpu.utils import trace as spans
    from demodel_tpu.utils.metrics import HUB

    compiles = Compiles()
    phases = Phases()
    after = Phases()        # what a run costs once the window has closed
    phases.seconds["start"] = time.time() - T_START   # imports, the runtime
    cache_dir = compile_cache.place()
    if args.trace:
        os.environ.setdefault("DEMODEL_TRACE_BUFFER", "262144")
        spans.enable()
    obs = readers.Observed(model=model, engine=settings["engine"],
                           peaks=peaks, chips=chips)
    vocab = model["vocab_size"]
    work = Path(tempfile.mkdtemp(prefix="bench-"))
    engine = srv = window = None
    tracing = False
    marks: list[float] = []
    try:
        with phases("native_build"):
            native.lib()
        with phases("checkpoint"):
            ckpt = checkpoint.Checkpoint(model, args.seed,
                                         n_shards=settings["shards"])
            digests = ckpt.digests()
        with hub.serving(MODEL_ID, ckpt, digests) as endpoint:
            with phases("load_model"):
                engine = serve.load_model(
                    MODEL_ID, ProxyConfig(
                        host="127.0.0.1", port=0, mitm_hosts=[],
                        no_mitm=True, cache_dir=work / "cache",
                        data_dir=work / "data", use_ecdsa=True),
                    endpoint=endpoint, mesh=make_mesh(chips),
                    **settings["engine"])
                jax.block_until_ready(jax.tree.leaves(engine.params))
        shutil.rmtree(work / "cache", ignore_errors=True)
        srv = RestoreServer(RestoreRegistry(Store(work / "restore")),
                            host="127.0.0.1").start()

        waves = loadgen.warmup_waves(traffic,
                                     settings["engine"]["max_batch"])

        def warm() -> None:
            for i, wave in enumerate(waves):
                recs = loadgen.run_wave(
                    srv.port, bool(traffic.get("stream", True)),
                    loadgen.warmup_prompts(wave, args.seed, i, vocab),
                    [o for _p, o in wave])
                bad = [failure_of(r, vocab) for r in recs]
                if any(bad):
                    raise RuntimeError(f"warm-up wave {wave} failed: {bad}")

        with phases("warmup"):
            warm()
        if compiles.count("compile") > compiles.count("cache_hit"):
            # A program compiled in this process does not run like the
            # same program loaded from the persistent cache (1024-token
            # prefills took 314 ms against 271 ms all through the window;
            # PERF.md, Findings). Every later run loads; so does this one.
            with phases("rewarm_from_cache"):
                jax.clear_caches()
                warm()
        say(f"warmed {len(waves)} wave(s) of (prompt, output) lengths: "
            f"{waves}")
        say(f"before the window: {compiles.summary()} ({cache_dir})")

        if args.trace:
            with phases("trace_start"):
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(str(work / "trace"),
                                         profiler_options=opts)
                tracing = True
                drop_marks(marks)
        window = loadgen.Window(traffic, srv.port, args.seed, vocab,
                                args.seconds)
        gc.collect()    # what set-up dropped is freed now, not in the window
        with phases("batch_fill"):
            window.open()
        obs.t0, obs.t1 = window.t0, window.t1
        obs.setup_s = window.t0 - T_START
        obs.hub_before = HUB.snapshot()
        host = HostWatch()
        window.close()
        host_line = host.stop()
        obs.hub_after = HUB.snapshot()
        obs.records = [r for r in window.records
                       if r.sent is not None and r.sent <= obs.t1]
        if tracing:
            drop_marks(marks)
            with after("trace_stop"):
                jax.profiler.stop_trace()
            tracing = False
            obs.spans = spans.buffer().snapshot()
            path = xplane.find_trace(work / "trace")
            if path is not None:
                with after("trace_read"):
                    obs.trace = xplane.read(path, marks, chips)
        stats = [d.memory_stats() or {} for d in devices[:chips]]
        obs.memory_peak_bytes = max(
            (s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    finally:
        if tracing:
            jax.profiler.stop_trace()
        if window is not None:
            window.close(now=True)
        if srv is not None:
            srv.stop()
        if engine is not None:
            engine.stop()
            serve.install(None)
        shutil.rmtree(work, ignore_errors=True)

    # ------------------------------------------------------- what was seen
    kv, adm = engine.pool.describe(), engine.admission.describe()
    leaked = bool(kv["in_use_blocks"] or adm["outstanding"])
    engine.params = None
    engine = None
    gc.collect()

    failures = [(r, failure_of(r, vocab)) for r in obs.records]
    failed = [f for f in failures if f[1]]
    n_cut = sum(1 for r in obs.records if r.cut)
    n_done = sum(1 for r, why in failures if r.done and not why)
    say(f"requests: attempted {len(obs.records)}, finished {n_done}, cut by "
        f"the window {n_cut}, failed {len(failed)}"
        + "".join(f"\n[bench]   failed: {why}" for _r, why in failed[:5]))
    say(f"samples: {readers.window_tokens(obs)} tokens, "
        f"{len(readers.token_gaps(obs))} token gaps, "
        f"{len(readers.first_token_times(obs))} first tokens in the window")
    say(host_line)
    gaps = sorted(readers.token_gaps(obs)) or sorted(
        readers.first_token_times(obs))
    if gaps:
        say(f"largest of {len(gaps)} "
            f"{'token gaps' if readers.token_gaps(obs) else 'first-token times'}"
            f" in the window, ms: {[round(g * 1e3) for g in gaps[-5:]]}, "
            f"median {readers.percentile(gaps, 50) * 1e3:.1f}")
    if window.lateness:
        say(f"generator lateness: median "
            f"{readers.percentile(window.lateness, 50) * 1e3:.3f} ms, worst "
            f"{max(window.lateness) * 1e3:.3f} ms")
    in_window = compiles.between(obs.t0, obs.t1)
    say(f"compilations in the window: {len(in_window)}"
        + (f" {in_window[:4]}" if in_window else ""))
    say("set-up seconds by phase: " + ", ".join(
        f"{k} {v:.2f}" for k, v in phases.seconds.items())
        + f"; total to the window's start {obs.setup_s:.2f}")
    obs.phases = dict(phases.seconds)
    rejected = obs.hub_after.get("gen_rejected_total", 0) \
        - obs.hub_before.get("gen_rejected_total", 0)
    say(f"program's counters over the window: decode tokens "
        f"{readers.hub_delta(obs, 'gen_tokens_total{stage=\"decode\"}')}, "
        f"rejected {rejected}; after stop: kv blocks in use "
        f"{kv['in_use_blocks']}, admissions outstanding "
        f"{adm['outstanding']}")

    # ------------------------------------------------------------ correct
    checks: list[tuple[str, float, float]] = []     # name, value, limit
    limits = cell_doc["correct"]["limits"]
    picked = sample_served([r for r, why in failures if not why],
                           args.seed, cell_doc["correct"]["sample_requests"])
    t_ref = time.perf_counter()
    if picked:
        seqs = [r.prompt + r.tokens[:-1] for r in picked]
        wanted = [range(len(r.prompt) - 1,
                        len(r.prompt) - 1 + len(r.tokens)) for r in picked]
        ref = reference.logits(ckpt, seqs, wanted)
        import numpy as np

        gaps = np.concatenate([reference.gaps_below_best(lg, r.tokens)
                               for lg, r in zip(ref, picked)])
        if args.control:
            import jax.numpy as jnp

            low = reference.logits(ckpt, seqs, wanted, mode=args.control)
            cgaps = np.concatenate([
                reference.gaps_below_best(
                    lg, np.asarray(jnp.argmax(lo, axis=1))[:len(r.tokens)])
                for lg, lo, r in zip(ref, low, picked)])
            del low
            say(f"control ({args.control} in the program's place, same "
                f"requests): served_gap_max = {cgaps.max():.6g}, "
                f"served_gap_mean = {cgaps.mean():.6g}, "
                f"{int((cgaps > 0).sum())} of {cgaps.size} not the "
                "reference's first choice")
        del ref
        checks.append(("served_gap_max", float(gaps.max()),
                       limits["served_gap_max"]))
        checks.append(("served_gap_mean", float(gaps.mean()),
                       limits["served_gap_mean"]))
        say(f"reference: {len(picked)} requests "
            f"({sum(1 for r in picked if r.done)} finished, longest "
            f"{max(len(s) for s in seqs)} positions), {gaps.size} served "
            f"tokens compared, {int((gaps > 0).sum())} not the reference's "
            f"first choice, in {time.perf_counter() - t_ref:.1f} s")
    else:
        say("reference: the window served nothing to compare")
    reasons = []
    if not checks:
        reasons.append("nothing compared with the reference")
    reasons += [f"{n} {v:.6g} over {lim:g}" for n, v, lim in checks
                if not v <= lim]
    if in_window:
        reasons.append(f"{len(in_window)} compilations in the window")
    if leaked:
        reasons.append("KV blocks or admissions outstanding after stop")
    if failed:
        reasons.append(f"{len(failed)} requests failed")
    if rejected:
        reasons.append(f"{rejected} requests rejected")
    if args.rehearse:
        reasons.append("a rehearsal is never a result")
    correct = not reasons
    say("correct: " + ("true" if correct else "false: " + "; ".join(reasons)))
    for name, value, limit in checks:   # the last lines of standard error
        print(f"[bench] compared: {name} = {value:.6g} (limit {limit:g}) "
              f"{'ok' if value <= limit else 'OVER'}", file=sys.stderr,
              flush=True)

    # ------------------------------------------------------------ metrics
    t_metrics = time.perf_counter()
    kind = "per_layer" if args.trace else "end_to_end"
    folder = HERE / ("layer_metrics" if args.trace else "end_to_end")
    metrics: dict[str, dict] = {}
    with after("metrics"):
        for m in cell_metrics(bench, cell["name"], kind):
            value = readers.read(obs,
                                 load_json(folder / f"{m['name']}.json"))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device["memory_peak_bytes"] = obs.memory_peak_bytes
    result: dict = {"correct": correct, "attempted": len(obs.records),
                    "failed": len(failed), "metrics": metrics,
                    "device": device}
    if args.trace and obs.trace is not None and obs.trace.devices:
        device["busy_s"] = readers.device_busy_seconds(obs)
        device["window_s"] = obs.seconds
        with after("breakdown"):
            result["breakdown"] = breakdown(obs)
        say(f"trace: {len(obs.trace.devices)} device(s), "
            f"{sum(len(v) for v in obs.trace.devices.values())} operations, "
            f"clock marks matched {obs.trace.marks_found}, offset "
            f"{obs.trace.clock_offset_s:.6f} s; device busy inside engine "
            f"spans {inside_spans_share(obs):.1f} % of all busy")
    say("after the window, seconds: " + ", ".join(
        f"{k} {v:.2f}" for k, v in after.seconds.items())
        + f", reference {t_metrics - t_ref:.2f}")
    if args.keep is not None:
        args.keep.mkdir(parents=True, exist_ok=True)
        with open(args.keep / f"{cell['name']}-{args.seed}.json", "w",
                  encoding="utf-8") as f:
            json.dump({"t0": obs.t0, "t1": obs.t1, "spans": [
                s for s in obs.spans if s["name"].startswith("serve.")],
                "trace": obs.trace.to_json() if obs.trace else None,
                "records": [{"caller": r.caller, "prompt": len(r.prompt),
                             "max_new": r.max_new, "sent": r.sent,
                             "times": r.times, "done": r.done, "cut": r.cut}
                            for r in obs.records]}, f)
    if args.rehearse:
        say("rehearsal metrics (CPU, not device numbers): " + json.dumps(
            {k: v["value"] for k, v in metrics.items()}))
        result["metrics"] = {}
        result.pop("breakdown", None)
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, value, limit in checks}    # comes last
    return 0, result, reasons


def engine_spans() -> tuple[list[str], list[str]]:
    """The host spans of ``spans/*.json``, innermost first (a deeper span
    takes the instant it shares with a shallower one), and those of them
    that hold the device's programs."""
    rows = [row for path in sorted((HERE / "spans").glob("*.json"))
            for row in load_json(path)["spans"]]
    rows.sort(key=lambda row: -row["depth"])
    return ([row["name"] for row in rows],
            [row["name"] for row in rows if row.get("programs")])


def breakdown(obs) -> dict:
    """The ten device operations that took most time, and the idle time by
    the host span that covers it, averaged over the cell's chips."""
    n = len(obs.trace.devices)
    ops: dict[str, float] = {}
    idle: dict[str, float] = {}
    order, _programs = engine_spans()
    known = set(order)
    host = [(s["name"], s["ts"], s["ts"] + s["dur"]) for s in obs.spans
            if s["name"] in known]
    for dev_ops in obs.trace.devices.values():
        for name, secs in xplane.op_sums(dev_ops, obs.t0, obs.t1).items():
            ops[name] = ops.get(name, 0.0) + secs / n
        gaps = xplane.idle_gaps(dev_ops, obs.t0, obs.t1)
        for name, secs in xplane.attribute(gaps, host, order).items():
            idle[name] = idle.get(name, 0.0) + secs / n

    def top(d: dict[str, float]) -> list:
        return [[k, v] for k, v in sorted(d.items(),
                                          key=lambda kv: -kv[1])[:10]]

    return {"device_ops": top(ops), "idle_gaps": top(idle)}


def inside_spans_share(obs) -> float:
    """A check on the clocks: the share of device busy time that falls
    inside the spans that hold the device's programs (prefill and decode
    step; near 100 when the profiler's clock and the host's are aligned)."""
    _order, programs = engine_spans()
    iv = [(s["ts"], s["ts"] + s["dur"]) for s in obs.spans
          if s["name"] in programs]
    tot = ins = 0.0
    for dev_ops in obs.trace.devices.values():
        clipped = xplane.clip(dev_ops, obs.t0, obs.t1)
        tot += xplane.busy_seconds(clipped, obs.t0, obs.t1)
        ins += xplane.seconds_within(clipped, iv)
    return 100.0 * ins / tot if tot else 0.0


if __name__ == "__main__":
    sys.exit(main())
