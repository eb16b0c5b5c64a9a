"""Cold boot to ``/generate`` on the TPU, once, with every answer checked.

The quickest proof that the system still starts on the chip. One process
(a chip belongs to one process), no network, nothing read from disk but
this checkout:

  build   TinyLlama-1.1B-Chat-v1.0's published ``config.json`` — every
          width as published, all 22 layers, bf16 — with seeded random
          weights, written as a multi-shard safetensors repo and served
          by the in-process fake hub on loopback;
  boot    ``serve.load_model``: registry walk → store → streaming sink →
          ``model_from_pull`` → ``GenEngine``, over every local device;
  serve   ``RestoreServer`` ``/generate`` over HTTP as a client would:
          one synchronous request, one streamed, then ``max_batch + 2``
          concurrent requests of two prompt lengths;
  check   every response, the engine's prefill and decode logits against
          a float32 ``llama.forward`` of the same weights, the KV pool
          and admission ledger back at zero;
  kernels flash attention's forward variants compiled by Mosaic, each
          against its reference.

Any failed check is an exception and a non-zero exit. Without a TPU the
script exits 1 before doing anything. On success the last line of stdout
is ``{"ok": true, "device": {...}}`` with the device as JAX reports it;
the ``[smoke]`` lines before it are information, not metrics.
"""

from __future__ import annotations

import contextlib
import dataclasses
import http.client
import json
import sys
import tempfile
import threading
import time
from http.server import ThreadingHTTPServer
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO))  # tests/ holds the fake-hub fixture

MODEL = "smoke/tinyllama-1.1b"

#: TinyLlama/TinyLlama-1.1B-Chat-v1.0 ``config.json`` as published
TINYLLAMA = {
    "architectures": ["LlamaForCausalLM"],
    "model_type": "llama",
    "hidden_size": 2048,
    "intermediate_size": 5632,
    "num_hidden_layers": 22,
    "num_attention_heads": 32,
    "num_key_value_heads": 4,
    "vocab_size": 32000,
    "max_position_embeddings": 2048,
    "hidden_act": "silu",
    "rms_norm_eps": 1e-05,
    "rope_theta": 10000.0,
    "rope_scaling": None,
    "attention_bias": False,
    "tie_word_embeddings": False,
    "torch_dtype": "bfloat16",
}

#: engine logits vs the float32 reference: largest difference as a share
#: of the reference logits' standard deviation. A wrong mask, RoPE or
#: cache position moves logits by about one standard deviation; bf16
#: weights and activations through 22 layers measured 0.061 (prefill) and
#: 0.060 (decode) on one v5e chip, 0.078 and 0.074 tensor-parallel over
#: four (PR 21)
LOGIT_TOL = 0.15


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


@contextlib.contextmanager
def timed(what: str):
    t0 = time.perf_counter()
    yield
    say(f"{what}: {time.perf_counter() - t0:.2f} s")


def build_checkpoint(config: dict, n_shards: int = 4,
                     seed: int = 0) -> dict[str, bytes]:
    """filename → bytes: a sharded safetensors repo for ``config`` in HF
    tensor names and ``[out, in]`` layout, weights ~ N(0, 1/fan_in), each
    tensor from its own generator seeded ``[seed, index]``."""
    from concurrent.futures import ThreadPoolExecutor

    import ml_dtypes  # noqa: F401 — registers bfloat16 with numpy

    from demodel_tpu.formats import safetensors as st

    dt = np.dtype(config["torch_dtype"])
    D, I = config["hidden_size"], config["intermediate_size"]
    V, L = config["vocab_size"], config["num_hidden_layers"]
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = D // H
    shapes = {"model.embed_tokens.weight": (V, D)}
    for i in range(L):
        p = f"model.layers.{i}."
        shapes.update({
            p + "input_layernorm.weight": (D,),
            p + "self_attn.q_proj.weight": (H * hd, D),
            p + "self_attn.k_proj.weight": (Hkv * hd, D),
            p + "self_attn.v_proj.weight": (Hkv * hd, D),
            p + "self_attn.o_proj.weight": (D, H * hd),
            p + "post_attention_layernorm.weight": (D,),
            p + "mlp.gate_proj.weight": (I, D),
            p + "mlp.up_proj.weight": (I, D),
            p + "mlp.down_proj.weight": (D, I),
        })
    shapes.update({"model.norm.weight": (D,), "lm_head.weight": (V, D)})

    def make(item) -> np.ndarray:
        index, shape = item
        if len(shape) == 1:  # a norm
            return np.ones(shape, dt)
        rng = np.random.default_rng([seed, index])
        w = rng.standard_normal(shape, np.float32) / np.sqrt(shape[1])
        return w.astype(dt)

    total = sum(int(np.prod(sh)) for sh in shapes.values()) * dt.itemsize
    files = {"config.json": json.dumps(config).encode()}
    weight_map: dict[str, str] = {}
    shard: dict[str, np.ndarray] = {}
    held = 0

    def flush() -> None:
        fname = f"model-{len(files):05d}-of-{n_shards:05d}.safetensors"
        files[fname] = st.serialize(shard)
        weight_map.update(dict.fromkeys(shard, fname))
        shard.clear()

    # numpy's generators and casts release the GIL: one core alone took
    # 35 s over the 1.1 B weights on the chip machine, the pool 13 s
    with ThreadPoolExecutor() as pool:
        for name, arr in zip(shapes, pool.map(make,
                                              enumerate(shapes.values()))):
            shard[name] = arr
            held += arr.nbytes
            if held >= total * len(files) / n_shards \
                    and len(files) < n_shards:
                flush()
    if shard:
        flush()
    files["model.safetensors.index.json"] = json.dumps(
        {"metadata": {"total_size": total}, "weight_map": weight_map}
    ).encode()
    return files


# ------------------------------------------------------------- HTTP client


def post_generate(port: int, prompt: list[int], max_new: int,
                  stream: bool = False) -> dict:
    """One ``/generate`` call as a client makes it. Returns ``{"status",
    "tokens", "first_token_s", ...}``; a streamed call reads the NDJSON
    lines as they arrive."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
    try:
        t0 = time.perf_counter()
        conn.request("POST", "/generate", body=json.dumps(
            {"prompt": prompt, "max_new_tokens": max_new, "stream": stream,
             "timeout": 900}), headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if not stream or resp.status != 200:
            body = json.loads(resp.read())
            return {"status": resp.status, **body}
        tokens, first, done = [], None, None
        for line in iter(resp.readline, b""):
            rec = json.loads(line)
            if "token" in rec:
                if first is None:
                    first = time.perf_counter() - t0
                tokens.append(rec["token"])
            elif "error" in rec:
                return {"status": 500, "error": rec["error"]}
            else:
                done = rec
        if done is None or done["tokens"] != tokens:
            raise AssertionError(f"stream ended without its done record "
                                 f"or disagreeing with it: {done}")
        return {"status": 200, "tokens": tokens, "first_token_s": first}
    finally:
        conn.close()


def check_response(resp: dict, max_new: int, vocab: int) -> list[int]:
    if resp["status"] != 200:
        raise AssertionError(f"/generate answered {resp}")
    toks = resp["tokens"]
    if len(toks) != max_new or not all(0 <= t < vocab for t in toks):
        raise AssertionError(
            f"asked {max_new} tokens in [0, {vocab}), got {toks}")
    return toks


# ------------------------------------------------------------ the main path


def check_against_reference(engine, prompt: list[int]) -> dict:
    """The engine's own jitted prefill and one decode step for ``prompt``
    against ``llama.forward`` over float32 copies of the same weights at
    "highest" matmul precision. Logits are compared, not greedy tokens:
    a bf16 near-tie flips an argmax without anything being wrong."""
    import jax
    import jax.numpy as jnp

    from demodel_tpu.models import llama
    from demodel_tpu.serve.scheduler import _Seq

    # the two programs exactly as GenEngine runs them, over its own pool
    # (the engine thread is idle: every request has been answered)
    T = len(prompt)
    pool = engine.pool
    lease = pool.alloc(pool.blocks_for(T + 1))
    try:
        ids_p, (logits_p, *_stats) = engine._prefill(prompt, lease)
        tok0 = int(ids_p[0])
        _width, rows = engine._decode_inputs([_Seq(None, lease, T, tok0)])
        ids_d, (logits_d, *_stats) = pool.apply(
            engine._jdecode, engine.params,
            jax.device_put(rows, pool.replicated), engine._prev_ids)
    finally:
        lease.free()

    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), engine.params)
    cfg32 = dataclasses.replace(engine.cfg, dtype="float32")
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, t: llama.forward(p, t, cfg32,
                                                 mesh=engine.mesh))(
            params32, jnp.asarray([prompt + [tok0]], jnp.int32))
    ref = np.asarray(ref[0], np.float32)
    out = {}
    for name, got, want, chose in (
            ("prefill", logits_p[0], ref[T - 1], tok0),
            ("decode", logits_d[0], ref[T], int(ids_d[0]))):
        got = np.asarray(got, np.float32)
        if chose != int(got.argmax()):
            raise AssertionError(f"{name} chose token {chose}, its logits' "
                                 f"first maximum is {int(got.argmax())}")
        if got.shape != want.shape or not np.isfinite(got).all():
            raise AssertionError(f"{name} logits: shape {got.shape}, "
                                 f"finite={np.isfinite(got).all()}")
        err = float(np.abs(got - want).max() / want.std())
        out[name] = round(err, 4)
        if err > LOGIT_TOL:
            raise AssertionError(
                f"{name} logits differ from the float32 reference by "
                f"{err:.3f} of its std (tolerance {LOGIT_TOL})")
    return out


def boot_and_serve(config: dict, workdir: Path, mesh=None, *,
                   n_shards: int = 4, max_batch: int = 3, max_new: int = 8,
                   prompt_lens: tuple[int, int] = (17, 24),
                   kv_mb: int | None = None) -> dict:
    """Build → boot through ``serve.load_model`` → drive ``/generate`` →
    check → stop. Raises on the first failed check; returns the tokens of
    every request, the kinds of sharding the parameters have and the
    engine's token counts. ``tests/test_load_model.py`` runs this same
    function at a tiny config on the CPU."""
    import jax
    from jax.sharding import NamedSharding

    from demodel_tpu import serve
    from demodel_tpu.config import ProxyConfig
    from demodel_tpu.restore.server import RestoreRegistry, RestoreServer
    from demodel_tpu.store import Store
    from tests.fake_registries import make_hf_handler

    info: dict = {}
    vocab = config["vocab_size"]
    with timed("build checkpoint"):
        files = build_checkpoint(config, n_shards=n_shards)
    hub = ThreadingHTTPServer(("127.0.0.1", 0),
                              make_hf_handler({MODEL: files}))
    threading.Thread(target=hub.serve_forever, daemon=True).start()
    engine = srv = None
    try:
        pcfg = ProxyConfig(
            host="127.0.0.1", port=0, mitm_hosts=[], no_mitm=True,
            cache_dir=workdir / "cache", data_dir=workdir / "data",
            use_ecdsa=True)
        with timed("load_model (pull, place, load)"):
            engine = serve.load_model(
                MODEL, pcfg, endpoint=f"http://127.0.0.1:{hub.server_port}",
                mesh=mesh, max_batch=max_batch, queue_limit=16,
                max_new_tokens=max_new, kv_mb=kv_mb)
        leaves = jax.tree.leaves(engine.params)
        jax.block_until_ready(leaves)
        n_dev = engine.mesh.devices.size
        info["param_shardings"] = sorted(
            {type(a.sharding).__name__ for a in leaves})
        for a in leaves:
            if a.ndim == 2 and not (isinstance(a.sharding, NamedSharding)
                                    and len(a.sharding.device_set) == n_dev):
                raise AssertionError(
                    f"a {a.shape} weight sits on {a.sharding}, not on the "
                    f"{n_dev}-device mesh")
        stats = [d.memory_stats() for d in engine.mesh.devices.flat]
        if all(stats):
            in_use = [s["bytes_in_use"] for s in stats]
            say(f"bytes in use per device after boot: {in_use}, peak "
                f"during boot: {[s['peak_bytes_in_use'] for s in stats]}")
            if max(in_use) > 1.2 * min(in_use):
                raise AssertionError(
                    f"weights are not balanced over the mesh: {in_use}")
        elif jax.default_backend() == "tpu":
            raise AssertionError("the TPU reported no memory_stats()")

        srv = RestoreServer(RestoreRegistry(Store(workdir / "restore")),
                            host="127.0.0.1").start()
        rng = np.random.default_rng(1)
        short, long_ = ([int(t) for t in rng.integers(0, vocab, n)]
                        for n in prompt_lens)
        with timed("first request (sync, compiles)"):
            sync = check_response(post_generate(srv.port, short, max_new),
                                  max_new, vocab)
        with timed("streamed request"):
            resp = post_generate(srv.port, short, max_new, stream=True)
            streamed = check_response(resp, max_new, vocab)
        say(f"first streamed token after {resp['first_token_s']:.3f} s")
        if streamed != sync:
            raise AssertionError(
                f"same prompt, different tokens: sync {sync}, "
                f"streamed {streamed}")

        # max_batch + 2 at once: the batch fills (an odd max_batch pads
        # its decode bucket), the rest wait in admission behind it
        prompts = [short if i % 2 else long_ for i in range(max_batch + 2)]
        answers: list = [None] * len(prompts)

        def client(i: int) -> None:
            try:
                answers[i] = post_generate(srv.port, prompts[i], max_new)
            except Exception as exc:  # noqa: BLE001 — re-raised below
                answers[i] = exc

        with timed(f"{len(prompts)} concurrent requests"):
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=900)
        for a in answers:
            if isinstance(a, Exception):
                raise a
        info["tokens"] = [sync, streamed] + [
            check_response(a, max_new, vocab) for a in answers]
        say("queue ms per concurrent request: "
            f"{[a['queue_ms'] for a in answers]}")

        with timed("reference check"):
            say("logit error vs float32 reference (share of its std): "
                f"{check_against_reference(engine, short)}")
        info["engine_tokens"] = engine.describe()["tokens"]
    finally:
        if srv is not None:
            srv.stop()
        if engine is not None:
            engine.stop()
            serve.install(None)
        hub.shutdown()
        hub.server_close()
    kv, adm = engine.pool.describe(), engine.admission.describe()
    if kv["in_use_blocks"] or adm["outstanding"]:
        raise AssertionError(f"leaked after stop: kv {kv}, admission {adm}")
    return info


# ------------------------------------------------------------- the kernels


def check_kernels(config: dict, prompt_len: int) -> dict:
    """The Pallas kernels the repo keeps — flash attention's three forward
    variants — compiled by Mosaic (never the interpreter) at the smoke
    model's head shapes, each against the einsum reference. Returns each
    variant's largest error."""
    import jax
    import jax.numpy as jnp

    from demodel_tpu.ops.flash_attention import (
        _interpret,
        flash_attention,
        reference_attention,
        reference_attention_lse,
    )

    if _interpret():
        raise AssertionError("Pallas would run interpreted here")
    H, G = config["num_attention_heads"], config["num_key_value_heads"]
    D = config["hidden_size"] // H
    rng = np.random.default_rng(2)
    out: dict = {}

    def qkv(sq: int, sk: int):
        return (jnp.asarray(rng.standard_normal((1, s, h, D), np.float32),
                            jnp.bfloat16)
                for s, h in ((sq, H), (sk, G), (sk, G)))

    def close(name: str, got, want) -> None:
        err = float(jnp.abs(got.astype(jnp.float32)
                            - want.astype(jnp.float32)).max())
        out[name] = round(err, 4)
        if not err < 0.05:  # bf16 outputs of O(1): 2 ulp is 0.016
            raise AssertionError(f"flash {name}: max error {err}")

    q, k, v = qkv(prompt_len, prompt_len)
    want, want_lse = reference_attention_lse(q, k, v, causal=True)
    close("prefill", flash_attention(q, k, v, causal=True), want)
    got, lse = flash_attention(q, k, v, causal=True, return_lse=True)
    close("prefill_with_lse", got, want)
    close("lse", lse, want_lse)
    # decode: one query against a 32-slot cache holding prompt_len keys,
    # the filled length a traced value as forward_with_cache passes it
    q, k, v = qkv(1, 32)
    n = jnp.int32(prompt_len)
    close("decode",
          jax.jit(lambda q, k, v, n: flash_attention(
              q, k, v, kv_len=n, causal=True))(q, k, v, n),
          reference_attention(q, k, v, causal=True, kv_len=n))
    return out


def main() -> int:
    import jax

    from demodel_tpu.utils import compile_cache

    t0 = time.perf_counter()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU — jax reports platform "
              f"{dev.platform!r}; this check only means something on the "
              "chip", file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say(f"device: {device}")
    cache_dir = compile_cache.place()
    from demodel_tpu import native

    with timed("native plane (make -C native unless built)"):
        native.lib()

    from demodel_tpu.parallel.mesh import make_mesh

    n = len(jax.local_devices())
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as td:
        info = boot_and_serve(TINYLLAMA, Path(td),
                              mesh=make_mesh(n) if n > 1 else None)
    say(f"tokens generated: {info['engine_tokens']}")
    kernels = check_kernels(TINYLLAMA, prompt_len=17)
    say(f"flash kernels, max error vs reference: {kernels}")
    # what place() has heard since: every program this process made ready
    made = compile_cache.programs()
    ready = list(made["ready"].values())
    say(f"compilations: {sum(sum(by.values()) for by in ready)} taking "
        f"{made['seconds']['load'] + made['seconds']['compile']:.1f} s in "
        f"all, {sum(by['loaded'] for by in ready)} served from {cache_dir}")
    for d in jax.local_devices():
        say(f"peak bytes in use on {d}: "
            f"{d.memory_stats()['peak_bytes_in_use']}")
    if not any(cache_dir.iterdir()):
        raise AssertionError(f"compile cache {cache_dir} is empty")
    say(f"total: {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
