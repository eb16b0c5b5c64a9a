"""Token-serving benchmark driver — prints ONE JSON line (same contract
as ``bench.py``/``bench_serve.py``/``bench_store.py``; those time MB/s
planes, this one gives the suite its tokens/s axis).

Scenario legs:

  prefill   tokens/s through ``serve.prefill`` (requests sized so the
            prompt dominates: max_new=1).
  decode    steady-state decode tokens/s with the continuous batch full.
  batching  the tentpole contract: the SAME requests served (a) all
            admitted up front (continuous batching interleaves them) vs
            (b) strictly one-at-a-time; the rc gate holds the continuous
            leg at ≥ 1.5× the sequential tokens/s.
  overflow  a thundering herd against a 1-wide engine with a tiny
            waiting room, through the REAL ``/generate`` HTTP surface:
            every request must answer 200 or 503+Retry-After — the
            zero-silent-drops admission contract — and the KV pool must
            account back to zero after the run.

Env knobs: DEMODEL_GENBENCH_REQS (16), DEMODEL_GENBENCH_PROMPT (32),
DEMODEL_GENBENCH_NEW (48), DEMODEL_GENBENCH_BATCH (8). ``--smoke`` (or
DEMODEL_GENBENCH_SMOKE=1) shrinks everything for CI; the rc gates
(batching ratio, overflow accounting, KV leak) hold at every size.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def _env_i(name: str, default: int) -> int:
    raw = os.environ.get(name, "").strip()
    try:
        return int(raw) if raw else default
    except ValueError:
        return default


SMOKE = ("--smoke" in sys.argv
         or os.environ.get("DEMODEL_GENBENCH_SMOKE", "").strip() == "1")
N_REQS = _env_i("DEMODEL_GENBENCH_REQS", 4 if SMOKE else 16)
PROMPT_LEN = _env_i("DEMODEL_GENBENCH_PROMPT", 8 if SMOKE else 32)
MAX_NEW = _env_i("DEMODEL_GENBENCH_NEW", 8 if SMOKE else 48)
MAX_BATCH = _env_i("DEMODEL_GENBENCH_BATCH", 4 if SMOKE else 8)


def _build():
    import jax

    from demodel_tpu.models import llama

    if SMOKE:
        cfg = llama.LlamaConfig.tiny()
    else:
        cfg = llama.LlamaConfig(
            vocab_size=512, hidden_size=128, intermediate_size=256,
            num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=4)
    params = llama.init_params(jax.random.key(7), cfg)
    return params, cfg


def _prompts(cfg, n: int) -> list[list[int]]:
    return [[(7 * i + 3 * j + 1) % cfg.vocab_size
             for j in range(PROMPT_LEN)] for i in range(n)]


def _drain(engine, prompts, max_new: int) -> tuple[float, int]:
    """Submit everything up front, wait for all; (wall_s, tokens)."""
    t0 = time.perf_counter()
    reqs = [engine.submit(p, max_new) for p in prompts]
    toks = sum(len(r.result(timeout=600)) for r in reqs)
    return time.perf_counter() - t0, toks


def _sequential(engine, prompts, max_new: int) -> tuple[float, int]:
    """One request at a time — the no-batching reference serving mode."""
    t0 = time.perf_counter()
    toks = 0
    for p in prompts:
        toks += len(engine.submit(p, max_new).result(timeout=600))
    return time.perf_counter() - t0, toks


def _throughput_legs(params, cfg) -> dict:
    from demodel_tpu import serve

    engine = serve.GenEngine(params, cfg, max_batch=MAX_BATCH,
                             queue_limit=max(64, 4 * N_REQS),
                             max_new_tokens=max(MAX_NEW, 8),
                             kv_mb=64).start()
    try:
        prompts = _prompts(cfg, N_REQS)
        # warm the jit caches (prefill shape + decode buckets) so the
        # measured legs time serving, not XLA compilation
        _drain(engine, prompts[:MAX_BATCH], 2)
        _sequential(engine, prompts[:1], 2)

        pre_s, _ = _drain(engine, prompts, 1)
        prefill_tok_s = N_REQS * PROMPT_LEN / pre_s if pre_s else 0.0

        cont_s, cont_toks = _drain(engine, prompts, MAX_NEW)
        seq_s, seq_toks = _sequential(engine, prompts, MAX_NEW)
        cont_tok_s = cont_toks / cont_s if cont_s else 0.0
        seq_tok_s = seq_toks / seq_s if seq_s else 0.0
        ratio = cont_tok_s / seq_tok_s if seq_tok_s else 0.0
        kv_after = engine.pool.describe()
    finally:
        engine.stop()
    return {
        "requests": N_REQS, "prompt_len": PROMPT_LEN, "max_new": MAX_NEW,
        "max_batch": MAX_BATCH,
        "prefill_tok_s": round(prefill_tok_s, 2),
        "decode_tok_s": round(cont_tok_s, 2),
        "continuous_s": round(cont_s, 3),
        "sequential_s": round(seq_s, 3),
        "continuous_tok_s": round(cont_tok_s, 2),
        "sequential_tok_s": round(seq_tok_s, 2),
        "batching_ratio": round(ratio, 3),
        "batching_ok": bool(ratio >= 1.5),
        "kv_blocks_in_use_after": kv_after["in_use_blocks"],
        "kv_budget_in_use_after": kv_after["budget"]["in_use_bytes"],
    }


def _overflow_leg(params, cfg, tmp: Path) -> dict:
    """The admission contract through the real HTTP surface."""
    from demodel_tpu import serve
    from demodel_tpu.restore.server import RestoreRegistry, RestoreServer
    from demodel_tpu.store import Store

    engine = serve.GenEngine(params, cfg, max_batch=1, queue_limit=2,
                             max_new_tokens=max(MAX_NEW, 8),
                             kv_mb=16).start()
    serve.install(engine)
    store = Store(tmp / "store")
    server = RestoreServer(RestoreRegistry(store), host="127.0.0.1").start()
    url = f"http://127.0.0.1:{server.port}/generate"
    n = max(8, 2 * N_REQS)
    prompts = _prompts(cfg, n)
    results: list[dict] = [None] * n  # type: ignore[list-item]

    def _one(i: int) -> None:
        body = json.dumps({"prompt": prompts[i],
                           "max_new_tokens": MAX_NEW}).encode()
        req = urllib.request.Request(url, data=body, method="POST")
        try:
            with urllib.request.urlopen(req, timeout=600) as resp:
                doc = json.loads(resp.read())
                results[i] = {"status": 200,
                              "tokens": len(doc.get("tokens", []))}
        except urllib.error.HTTPError as e:
            results[i] = {"status": e.code,
                          "retry_after": e.headers.get("Retry-After")}
            e.read()
        except Exception as e:  # noqa: BLE001 — a drop must be visible
            results[i] = {"status": -1, "error": str(e)}

    threads = [threading.Thread(target=_one, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
    served = [r for r in results if r and r["status"] == 200]
    rejected = [r for r in results if r and r["status"] == 503]
    other = [r for r in results
             if r is None or r["status"] not in (200, 503)]
    retry_after_ok = all(r.get("retry_after") not in (None, "")
                         for r in rejected)
    tokens_ok = all(r["tokens"] == MAX_NEW for r in served)
    server.stop()
    engine.stop()
    serve.install(None)
    store.close()
    kv_after = engine.pool.describe()
    return {
        "requests": n,
        "served_200": len(served),
        "rejected_503": len(rejected),
        "silent_drops": len(other),
        "retry_after_on_every_503": retry_after_ok,
        "served_complete": tokens_ok,
        "kv_blocks_in_use_after": kv_after["in_use_blocks"],
        "overflow_ok": bool(
            len(other) == 0 and len(rejected) > 0 and retry_after_ok
            and tokens_ok and kv_after["in_use_blocks"] == 0),
    }


def main() -> int:
    params, cfg = _build()
    legs = _throughput_legs(params, cfg)
    with tempfile.TemporaryDirectory() as td:
        overflow = _overflow_leg(params, cfg, Path(td))

    kv_ok = (legs.pop("kv_blocks_in_use_after") == 0
             and legs.pop("kv_budget_in_use_after") == 0
             and overflow["kv_blocks_in_use_after"] == 0)
    result = {
        "metric": "gen_decode_tokens_per_s",
        "value": legs["decode_tok_s"],
        "unit": "tokens/s",
        "vs_baseline": 0.0,  # first tokens/s datapoint — no prior anchor
        "smoke": SMOKE,
        "model": {
            "layers": cfg.num_hidden_layers, "hidden": cfg.hidden_size,
            "heads": cfg.num_attention_heads,
            "kv_heads": cfg.num_key_value_heads,
            "vocab": cfg.vocab_size},
        "serving": legs,
        "overflow": overflow,
        "kv_accounting_zero": kv_ok,
    }
    print(json.dumps(result))
    if not legs["batching_ok"]:
        print("[bench_generate] BATCHING CONTRACT VIOLATED "
              f"(ratio {legs['batching_ratio']} < 1.5)", file=sys.stderr)
        return 1
    if not overflow["overflow_ok"]:
        print("[bench_generate] OVERFLOW CONTRACT VIOLATED", file=sys.stderr)
        return 1
    if not kv_ok:
        print("[bench_generate] KV ACCOUNTING LEAK", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
