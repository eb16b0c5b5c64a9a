"""Benchmark driver — prints ONE JSON line.

Primary metric (BASELINE.md): cold-pull→HBM wall-clock / MB/s/chip sustained.

This driver times the DELIVERY side of the system; its twin
``tools/bench_serve.py`` (same one-JSON-line contract) times the SERVE
side — hot-hit re-serving from a warm store through the bounded session
pool. Run both to cover the two halves of the north star.

This drives the REAL pipeline end-to-end, staging the north-star scenario
("cold-pull→HBM from a warm peer, ≥3× faster than hf-cli + restore"):

  setup   a loopback fake HF hub serves a synthetic multi-shard bf16
          safetensors checkpoint; a *peer node* pulls it warm (untimed) and
          serves its content-addressed store over the native /peer API;
  ours    a cold node pulls the model with the peer configured
          (registry walk → peer DCN fetch → C++ chunk store → HBM sink:
          per-tensor range reads → `jax.device_put` under a NamedSharding)
          — timed start→arrays-on-device;
  control the `huggingface-cli + restore` analogue: stream the same files
          from the hub to disk, read them back whole, parse, `device_put`
          — timed the same way.

`vs_baseline` = control/ours speedup (>1 means we beat the baseline path).

One process, on the TPU or not at all: the JSON line names the device it
ran on, and a run that finds no TPU, or whose pull or control leg raises,
exits non-zero and prints no result — a number from another backend is
not a device metric. The only child is the `huggingface-cli download`
control, which never touches JAX.

Env knobs: DEMODEL_BENCH_MB (default 256), DEMODEL_BENCH_SHARDS (default 4).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time
from http.server import ThreadingHTTPServer
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO))  # tests/ holds the fake-hub fixture

TOTAL_MB = int(os.environ.get("DEMODEL_BENCH_MB", "256"))
N_SHARDS = int(os.environ.get("DEMODEL_BENCH_SHARDS", "4"))
MODEL = "bench/llama-synthetic"


def _build_repo(total_mb: int, n_shards: int) -> dict[str, bytes]:
    """filename → bytes: an n-shard bf16 checkpoint of ~total_mb MB."""
    import ml_dtypes

    from demodel_tpu.formats import safetensors as st

    cols = 4096
    rows = total_mb * (1 << 20) // 2 // n_shards // 2 // cols  # 2 tensors/shard
    files: dict[str, bytes] = {
        "config.json": json.dumps({"model_type": "llama", "hidden_size": cols}).encode(),
    }
    weight_map: dict[str, str] = {}
    rng = np.random.default_rng(0)
    for i in range(n_shards):
        fname = f"model-{i + 1:05d}-of-{n_shards:05d}.safetensors"
        tensors = {}
        for j in range(2):
            name = f"blocks.{i}.w{j}"
            tensors[name] = rng.standard_normal((rows, cols), np.float32).astype(
                ml_dtypes.bfloat16
            )
            weight_map[name] = fname
        files[fname] = st.serialize(tensors)
    files["model.safetensors.index.json"] = json.dumps(
        {"metadata": {}, "weight_map": weight_map}
    ).encode()
    return files


def _bench_e2e() -> dict:
    # validate BEFORE the expensive timed section: a typo'd strategy must
    # fail at startup, not after minutes of e2e pulls
    strategy = os.environ.get("DEMODEL_BENCH_STRATEGY", "sharded").strip()
    if strategy not in ("file", "sharded"):
        raise SystemExit(
            f"DEMODEL_BENCH_STRATEGY={strategy!r}: must be 'file' or "
            "'sharded' — a mislabeled strategy would poison the "
            "regression anchors")
    import jax

    from demodel_tpu.config import ProxyConfig
    from demodel_tpu.delivery import pull
    from demodel_tpu.formats import safetensors as st  # noqa: F401 (control path)
    from demodel_tpu.proxy import ProxyServer
    from tests.fake_registries import make_hf_handler

    import requests

    repo_files = _build_repo(TOTAL_MB, N_SHARDS)
    weight_bytes = sum(
        len(v) for k, v in repo_files.items() if k.endswith(".safetensors")
    )

    with tempfile.TemporaryDirectory() as td:
        tmp = Path(td)
        hub = ThreadingHTTPServer(
            ("127.0.0.1", 0), make_hf_handler({MODEL: repo_files})
        )
        import threading

        threading.Thread(target=hub.serve_forever, daemon=True).start()
        endpoint = f"http://127.0.0.1:{hub.server_address[1]}"

        def node_cfg(name: str) -> ProxyConfig:
            # no_mitm: the bench never MITMs (direct HTTP to the fake hub,
            # /peer serving), so no leaf is ever minted
            return ProxyConfig(
                host="127.0.0.1", port=0, mitm_hosts=[], no_mitm=True,
                cache_dir=tmp / f"{name}-cache", data_dir=tmp / f"{name}-data",
                use_ecdsa=True,
            )

        try:
            # ---- warm the peer (untimed) and serve its store over /peer
            cfg_a = node_cfg("peer")
            pull(MODEL, cfg_a, endpoint=endpoint)
            with ProxyServer(cfg_a, verbose=False) as peer_node:
                # warm up jax (compile/alloc/dtype paths) before timing —
                # both contenders transfer bf16, so neither pays first-use
                # setup inside its window
                import ml_dtypes as _md

                jax.block_until_ready(
                    jax.device_put(np.zeros((1024, 1024), np.float32))
                )
                jax.block_until_ready(
                    jax.device_put(np.zeros((256, 4096), _md.bfloat16))
                )
                import ml_dtypes as _md2  # local name for the probe below

                def _link_probe() -> float:
                    """Raw host→device rate for one 64 MB device_put,
                    taken after both delivery legs."""
                    probe = np.zeros((8192, 4096), _md2.bfloat16)
                    t0 = time.perf_counter()
                    jax.block_until_ready(jax.device_put(probe))
                    rate = round(
                        probe.nbytes / 1e6 / (time.perf_counter() - t0), 1)
                    print(f"[bench] link probe: {rate} MB/s "
                          "host→device", file=sys.stderr)
                    return rate

                # ---- ours: cold node, warm peer → HBM, best of two
                # strategies (both legitimate cold pulls):
                #   whole-file — streaming pull: files land in host buffers
                #     over multi-stream fetch, tensors stream to device,
                #     cache persistence continues off-clock;
                #   sharded — manifest-ordered window reads straight off
                #     the peer into per-tensor landing buffers
                #     (sink/remote.py): tensor N+1's fetch overlaps tensor
                #     N's host→device transfer, zero disk/hash on-clock.
                from demodel_tpu.delivery import pull_to_hbm
                from demodel_tpu.sink.remote import pull_manifest_to_hbm

                # RSS accounting for the north-star-scale mode: baseline
                # after jax warmup; peak measured after the strategy legs.
                # The first leg's placement is freed before the second so
                # the peak bounds ONE checkpoint + delivery buffers, not
                # two checkpoints
                import resource

                def _vm_rss_kb() -> int:
                    # CURRENT RSS, not ru_maxrss: the high-water mark
                    # never decreases, so a transient early peak (repo
                    # serialization, warmup) would inflate the baseline
                    # and make the ceiling assertion vacuous
                    with open("/proc/self/status") as f:
                        for line in f:
                            if line.startswith("VmRSS:"):
                                return int(line.split()[1])
                    return 0

                rss0_kb = _vm_rss_kb()

                # correctness oracle inputs captured up front
                blob = repo_files[f"model-00001-of-{N_SHARDS:05d}.safetensors"]
                spec = st.parse_header(blob).tensors["blocks.0.w0"]
                src = spec.to_numpy(blob[spec.start:spec.end])

                def leg_file() -> tuple[float, float, dict]:
                    t0 = time.perf_counter()
                    report, placed = pull_to_hbm(
                        MODEL, node_cfg("cold"), endpoint=endpoint,
                        peers=[peer_node.url], defer_cache_commit=True,
                    )
                    secs = time.perf_counter() - t0
                    t0 = time.perf_counter()
                    placed.finalize()
                    fin_secs = time.perf_counter() - t0
                    assert placed is not None \
                        and len(placed.arrays) == 2 * N_SHARDS
                    got = np.asarray(placed.arrays["blocks.0.w0"])
                    if not np.array_equal(got, src):
                        raise AssertionError(
                            "delivered tensor != source bytes")
                    del got, placed  # free before the next leg (RSS bound)
                    return secs, fin_secs, report

                def leg_sharded() -> tuple[float, dict]:
                    t0 = time.perf_counter()
                    report_sh, placed_sh = pull_manifest_to_hbm(
                        MODEL, [peer_node.url])
                    secs = time.perf_counter() - t0
                    assert len(placed_sh.arrays) == 2 * N_SHARDS
                    got_sh = np.asarray(placed_sh.arrays["blocks.0.w0"])
                    del placed_sh
                    if not np.array_equal(got_sh, src):
                        raise AssertionError(
                            "sharded delivery != source bytes")
                    del got_sh
                    return secs, report_sh

                # the headline strategy runs first
                if strategy == "file":
                    ours_file, finalize_secs, report = leg_file()
                    ours_sharded, report_sh = leg_sharded()
                else:
                    ours_sharded, report_sh = leg_sharded()
                    ours_file, finalize_secs, report = leg_file()
                rss_peak_kb = resource.getrusage(
                    resource.RUSAGE_SELF).ru_maxrss
                # headline strategy is PRE-SELECTED per configuration
                # (validated at function entry), not a per-run min of two
                # attempts: min-of-two vs a single-sample control would
                # bias the recorded ratio and every regression anchor
                # derived from it (advisor r4). The sharded manifest pull
                # is the flagship path; DEMODEL_BENCH_STRATEGY=file
                # headlines whole-file instead.
                ours = ours_file if strategy == "file" else ours_sharded
                link_mbps = _link_probe()
                print(f"[bench] ours: whole-file={ours_file:.3f}s "
                      f"sharded={ours_sharded:.3f}s → headline strategy: "
                      f"{strategy}", file=sys.stderr)
                if os.environ.get("DEMODEL_BENCH_PROFILE"):
                    print(f"[profile] whole-file={ours_file:.3f}s "
                          f"pull={report.get('secs')}s "
                          f"sink={report.get('tpu_sink', {}).get('secs')}s "
                          f"finalize(untimed)={finalize_secs:.3f}s "
                          f"files={[round(f['secs'], 3) for f in report['files']]} "
                          f"sharded={report_sh.get('secs')}s "
                          f"net={report_sh.get('network_bytes')}B",
                          file=sys.stderr)

                # host RSS ceiling: the ceiling (2× + 512 MB slack)
                # catches the failure mode that matters — naive
                # whole-FILE buffering holds ANOTHER full checkpoint on
                # the host (≥3×). Enforced only at scale (≥1 GiB) where
                # it means something; override via
                # DEMODEL_BENCH_RSS_CEILING_MB.
                rss_delta_mb = (rss_peak_kb - rss0_kb) >> 10
                ceiling_mb = int(os.environ.get(
                    "DEMODEL_BENCH_RSS_CEILING_MB",
                    str(int(TOTAL_MB * 2.0 + 512))))
                if TOTAL_MB >= 1024 and rss_delta_mb > ceiling_mb:
                    raise AssertionError(
                        f"peak RSS grew {rss_delta_mb} MB for a "
                        f"{TOTAL_MB} MB checkpoint (ceiling {ceiling_mb})")
                print(f"[bench] rss: +{rss_delta_mb} MB "
                      f"(ceiling {ceiling_mb} MB at scale)", file=sys.stderr)

            # ---- control: hub → disk → parse → device. Two flavors
            # (VERDICT r4 weak #5: the in-process simulation alone can't
            # back the literal ≥3× north-star claim):
            #   real — the ACTUAL `huggingface-cli download` binary on
            #     the clock (HF_ENDPOINT at the fake hub), then parse +
            #     device_put in-process; used for vs_baseline whenever
            #     the binary exists.
            #   sim — the in-process analogue (kept for environments
            #     without the CLI and for continuity with r01-r04
            #     anchors; recorded as control_sim_secs either way).
            import shutil as _shutil
            import subprocess as _sp

            names = [n for n in repo_files if n.endswith(".safetensors")]

            def _parse_and_place(dl) -> float:
                arrs = []
                for name in names:
                    blob = (dl / name.replace("/", "_")).read_bytes()
                    idx = st.parse_header(blob)
                    for spec in idx.tensors.values():
                        arrs.append(jax.device_put(
                            spec.to_numpy(blob[spec.start:spec.end])))
                jax.block_until_ready(arrs)

            dl = tmp / "control"
            dl.mkdir()
            t0 = time.perf_counter()
            sess = requests.Session()
            for name in ["config.json", "model.safetensors.index.json"] + names:
                r = sess.get(f"{endpoint}/{MODEL}/resolve/main/{name}", stream=True)
                r.raise_for_status()
                with open(dl / name.replace("/", "_"), "wb") as f:
                    for chunk in r.iter_content(1 << 20):
                        f.write(chunk)
            _parse_and_place(dl)
            control_sim = time.perf_counter() - t0

            control_real = None
            hf_cli = _shutil.which("huggingface-cli")
            if hf_cli and not os.environ.get("DEMODEL_BENCH_NO_REAL_CONTROL"):
                dl2 = tmp / "control-real"
                env = dict(os.environ)
                env.update({"HF_ENDPOINT": endpoint,
                            # the endpoint is this process's loopback hub:
                            # a sealed machine's offline switch must not
                            # turn the control into a cache lookup
                            "HF_HUB_OFFLINE": "0",
                            "HF_HOME": str(tmp / "hf-home"),
                            "HF_HUB_DISABLE_TELEMETRY": "1",
                            "HF_HUB_DISABLE_XET": "1",
                            "HF_HUB_DISABLE_PROGRESS_BARS": "1"})
                t0 = time.perf_counter()
                try:
                    r = _sp.run([hf_cli, "download", MODEL,
                                 "--local-dir", str(dl2)],
                                env=env, capture_output=True, text=True,
                                timeout=3600)
                except _sp.TimeoutExpired:
                    # a wedged CLI must not sink the whole run after the
                    # expensive "ours" legs — sim control still stands
                    r = None
                    print("[bench] real control timed out — falling back "
                          "to sim control", file=sys.stderr)
                if r is not None and r.returncode == 0:
                    # hf-cli keeps hub-style paths; flatten like _parse
                    # expects
                    for name in names:
                        p = dl2 / name
                        if p.exists() and "/" in name:
                            p.rename(dl2 / name.replace("/", "_"))
                    _parse_and_place(dl2)
                    control_real = time.perf_counter() - t0
                elif r is not None:
                    print(f"[bench] real control failed "
                          f"(rc={r.returncode}): {r.stderr[-300:]} — "
                          "falling back to sim control", file=sys.stderr)
            control = control_real if control_real is not None else control_sim
            print(f"[bench] control: real="
                  f"{'n/a' if control_real is None else round(control_real, 3)}s "
                  f"sim={control_sim:.3f}s", file=sys.stderr)
        finally:
            hub.shutdown()

    mb = weight_bytes / 1e6
    return {
        "metric": "cold_pull_to_hbm_throughput",
        "value": round(mb / ours, 2),
        "unit": "MB/s/chip",
        "vs_baseline": round(control / ours, 3),
        # both strategies on the record (the headline is one, fixed above)
        "strategy": strategy,
        "whole_file_mbps": round(mb / ours_file, 2),
        "sharded_mbps": round(mb / ours_sharded, 2),
        "rss_delta_mb": rss_delta_mb,
        "link_sustained_mbps": link_mbps,
        # which control stack vs_baseline came from, + both on record
        "control": "real-hf-cli" if control_real is not None else "sim",
        "control_sim_secs": round(control_sim, 3),
        **({"control_real_secs": round(control_real, 3)}
           if control_real is not None else {}),
        # sharded-leg phase split (fetch vs device-place vs final block):
        # the network-bound / transfer-bound diagnosis for slow pulls.
        # Emitted unconditionally ({} = the leg reported no split)
        "sharded_phase_secs": report_sh.get("phase_secs") or {},
        **({"sharded_block_secs": report_sh["block_secs"]}
           if report_sh.get("block_secs") is not None else {}),
        # north-star projection: BASELINE.md's Llama-2-7B is ~13 GB —
        # the <30s cold-pull→HBM goal at this run's measured rate
        "projected_13gb_s": round(13000 / (mb / ours), 1),
    }


def _check_regression(out: dict) -> dict:
    """Perf regression gate: compare against the most recent recorded
    round (``BENCH_r*.json``) whose metric MATCHES, skipping rounds that
    recorded another metric; with no such record the line passes through.
    Also reports ``vs_best`` against the best matching round ever recorded.
    A drop >10% vs either anchor is flagged loudly on stderr and in the
    JSON — a regressed number must never ship silently again."""
    try:
        anchors = []  # (filename, value), oldest → newest, matching metric only
        for pf in sorted(REPO.glob("BENCH_r*.json")):
            try:
                prev = json.loads(pf.read_text()).get("parsed", {})
            except ValueError:
                continue
            if prev.get("metric") == out["metric"] and prev.get("value", 0) > 0:
                anchors.append((pf.name, float(prev["value"])))
        if not anchors:
            return out
        prev_name, prev_val = anchors[-1]
        best_name, best_val = max(anchors, key=lambda a: a[1])
        out["vs_prev"] = round(out["value"] / prev_val, 3)
        out["vs_best"] = round(out["value"] / best_val, 3)
        if out["value"] < 0.9 * prev_val:
            out["regressed"] = True
            print(f"PERF REGRESSION: {out['value']} {out['unit']} < "
                  f"last matching round's {prev_val} ({prev_name})",
                  file=sys.stderr)
        elif out["value"] < 0.9 * best_val:
            out["regressed_vs_best"] = True
            print(f"PERF below best-ever: {out['value']} {out['unit']} < "
                  f"{best_val} ({best_name})", file=sys.stderr)
    except Exception as e:  # noqa: BLE001 — the gate must not kill the bench
        print(f"regression check skipped: {e}", file=sys.stderr)
    return out


def main() -> int:
    import jax

    from demodel_tpu.utils import compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench: no TPU — jax reports platform {dev.platform!r}; "
              "this benchmark only runs on the chip", file=sys.stderr)
        return 1
    compile_cache.place()
    out = _bench_e2e()
    out["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(jax.devices())}
    print(json.dumps(_check_regression(out)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
