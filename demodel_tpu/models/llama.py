"""Llama family — the flagship model of the delivery stack.

TPU-first design, not a port: pure-functional params pytree, static shapes
everywhere (jit/pjit-safe), GQA attention with HF's rotate-half RoPE
convention (checkpoint parity is tested against ``transformers``' reference
implementation in tests/test_hf_models.py), sharding expressed as
``NamedSharding`` trees over a ``Mesh`` — tensor parallel on the hidden
axes, sequence/context parallel attention as an exact ``ppermute`` ring
(:mod:`demodel_tpu.ops.ring_attention`) when the mesh carries an ``sp``
axis. The train step is jit-compiled once; XLA inserts the ICI collectives
implied by the shardings.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from demodel_tpu.models.common import (attend, refuse_unsupported,
                                       rms_norm,
                                       use_flash_attention as _use_flash)
from demodel_tpu.models.hf_loader import Weights, lay
from demodel_tpu.ops.ring_attention import (
    dense_attention,
    ring_attention_sharded,
)


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    dtype: str = "float32"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def tiny(cls) -> "LlamaConfig":
        """Test/driver-sized config: real GQA (4 q heads per kv head)."""
        return cls(vocab_size=256, hidden_size=64, intermediate_size=128,
                   num_hidden_layers=2, num_attention_heads=8,
                   num_key_value_heads=2)

    @classmethod
    def from_hf(cls, config: dict) -> "LlamaConfig":
        refuse_unsupported(config)
        return cls(
            vocab_size=config.get("vocab_size", 32000),
            hidden_size=config.get("hidden_size", 4096),
            intermediate_size=config.get("intermediate_size", 11008),
            num_hidden_layers=config.get("num_hidden_layers", 32),
            num_attention_heads=config.get("num_attention_heads", 32),
            num_key_value_heads=config.get(
                "num_key_value_heads", config.get("num_attention_heads", 32)),
            rope_theta=config.get("rope_theta", 10000.0),
            rms_norm_eps=config.get("rms_norm_eps", 1e-6),
            # the checkpoint's dtype (transformers writes ``torch_dtype``
            # up to 4.55 and ``dtype`` after): caches and the KV pool are
            # built in it, so they agree with the weights
            dtype=(config.get("torch_dtype") or config.get("dtype")
                   or "float32"),
        )


# ------------------------------------------------------------------ params


def init_params(key, cfg: LlamaConfig) -> dict:
    dt = jnp.dtype(cfg.dtype)
    D, I, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    hd = cfg.head_dim
    H, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    keys = jax.random.split(key, cfg.num_hidden_layers + 2)

    def dense(k, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                / np.sqrt(shape[0])).astype(dt)

    layers = []
    for i in range(cfg.num_hidden_layers):
        ks = jax.random.split(keys[i], 7)
        layers.append({
            "attn_norm": jnp.ones((D,), dt),
            "q_proj": dense(ks[0], (D, H * hd)),
            "k_proj": dense(ks[1], (D, Hkv * hd)),
            "v_proj": dense(ks[2], (D, Hkv * hd)),
            "o_proj": dense(ks[3], (H * hd, D)),
            "mlp_norm": jnp.ones((D,), dt),
            "gate_proj": dense(ks[4], (D, I)),
            "up_proj": dense(ks[5], (D, I)),
            "down_proj": dense(ks[6], (I, D)),
        })
    return {
        "embed": (jax.random.normal(keys[-2], (V, D), jnp.float32)
                  * 0.02).astype(dt),
        "layers": layers,
        "final_norm": jnp.ones((D,), dt),
        "lm_head": dense(keys[-1], (D, V)),
    }


def param_shardings(cfg: LlamaConfig, mesh: Mesh) -> dict:
    """NamedSharding tree matching :func:`init_params`: column-parallel
    in-projections, row-parallel out-projections over ``tp``; norms
    replicated; embeddings vocab-sharded when divisible."""
    tp = int(mesh.shape.get("tp", 1))

    def sh(*spec):
        return NamedSharding(mesh, P(*spec))

    col = sh(None, "tp")   # [D, out] split on out
    row = sh("tp", None)   # [in, D] split on in
    rep1 = sh(None)
    layer = {
        "attn_norm": rep1,
        "q_proj": col if (cfg.num_attention_heads * cfg.head_dim) % tp == 0 else sh(None, None),
        "k_proj": col if (cfg.num_key_value_heads * cfg.head_dim) % tp == 0 else sh(None, None),
        "v_proj": col if (cfg.num_key_value_heads * cfg.head_dim) % tp == 0 else sh(None, None),
        "o_proj": row if (cfg.num_attention_heads * cfg.head_dim) % tp == 0 else sh(None, None),
        "mlp_norm": rep1,
        "gate_proj": col if cfg.intermediate_size % tp == 0 else sh(None, None),
        "up_proj": col if cfg.intermediate_size % tp == 0 else sh(None, None),
        "down_proj": row if cfg.intermediate_size % tp == 0 else sh(None, None),
    }
    return {
        "embed": sh("tp", None) if cfg.vocab_size % tp == 0 else sh(None, None),
        "layers": [dict(layer) for _ in range(cfg.num_hidden_layers)],
        "final_norm": rep1,
        "lm_head": sh(None, "tp") if cfg.vocab_size % tp == 0 else sh(None, None),
    }


from_hf = LlamaConfig.from_hf


def load_params(weights: dict, cfg: LlamaConfig, mesh=None) -> dict:
    """The tree of :func:`init_params` from a checkpoint. ``mesh`` lays every leaf out as :func:`param_shardings`
    wants it (column/row-parallel over ``tp``): the delivery plan's
    leading-axis shards are re-laid on the mesh's devices."""
    w = Weights(weights)
    sh = param_shardings(cfg, mesh) if mesh is not None else {}
    layers = []
    for i in range(cfg.num_hidden_layers):
        pre = f"layers.{i}."
        lsh = sh["layers"][i] if sh else {}

        def lin(name, leaf):
            return w.get(pre + name, transpose=True, sharding=lsh.get(leaf))

        layers.append({
            "attn_norm": w.get(pre + "input_layernorm.weight",
                               sharding=lsh.get("attn_norm")),
            "q_proj": lin("self_attn.q_proj.weight", "q_proj"),
            "k_proj": lin("self_attn.k_proj.weight", "k_proj"),
            "v_proj": lin("self_attn.v_proj.weight", "v_proj"),
            "o_proj": lin("self_attn.o_proj.weight", "o_proj"),
            "mlp_norm": w.get(pre + "post_attention_layernorm.weight",
                              sharding=lsh.get("mlp_norm")),
            "gate_proj": lin("mlp.gate_proj.weight", "gate_proj"),
            "up_proj": lin("mlp.up_proj.weight", "up_proj"),
            "down_proj": lin("mlp.down_proj.weight", "down_proj"),
        })
    embed = w.get("embed_tokens.weight", sharding=sh.get("embed"))
    if w.has("lm_head.weight"):
        head = w.get("lm_head.weight", transpose=True,
                     sharding=sh.get("lm_head"))
    else:  # tied embeddings
        head = lay(embed, True, sh.get("lm_head"))
    return {
        "embed": embed,
        "layers": layers,
        "final_norm": w.get("norm.weight", sharding=sh.get("final_norm")),
        "lm_head": head,
    }


# ------------------------------------------------------------------- rope


def _rope(x, positions, theta: float):
    """HF rotate-half convention: pairs are (i, i + hd/2)."""
    B, T, H, hd = x.shape
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = positions[..., None].astype(jnp.float32) * inv  # [B,T,hd/2]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


# ----------------------------------------------------------------- forward


def _head_align(x, mesh: Mesh | None):
    """Constrain [B,T,H,hd] to a HEAD-aligned tp sharding (or replicate
    when the head count doesn't divide tp). Without this, a column-sharded
    projection reshape can leave each shard holding *part of a head*, and
    the rotate-half slice+concat inside :func:`_rope` then crosses the
    shard boundary. A layout choice, not a correctness fix: jax 0.4.37's
    XLA-CPU miscompiled that combination under multi-axis meshes, the
    installed 0.9.0 gives the same logits with and without the constraint
    (sp, tp and dp×tp meshes, odd prompt — checked in PR 21). Head-aligned
    shards are the layout TP attention wants: every later op in the cache
    path is per-head."""
    if mesh is None:
        return x
    tp = int(mesh.shape.get("tp", 1))
    if tp <= 1:
        return x
    H = x.shape[2]
    spec = P(None, None, "tp", None) if H % tp == 0 else P(None, None, None, None)
    return lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _attn(layer, x, cfg: LlamaConfig, positions, mesh: Mesh | None,
          kv_cache=None, cache_pos=None):
    B, T, D = x.shape
    hd = cfg.head_dim
    H, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    q = (x @ layer["q_proj"]).reshape(B, T, H, hd)
    k = (x @ layer["k_proj"]).reshape(B, T, Hkv, hd)
    v = (x @ layer["v_proj"]).reshape(B, T, Hkv, hd)
    if kv_cache is not None:
        # cached decode/prefill: re-align shards on the head axis BEFORE
        # the rotate-half slicing (see _head_align). The ring branch
        # manages its own sequence sharding and must not be re-constrained.
        q = _head_align(q, mesh)
        k = _head_align(k, mesh)
        v = _head_align(v, mesh)
    q = _rope(q, positions, cfg.rope_theta)
    k = _rope(k, positions, cfg.rope_theta)

    new_cache = None
    if kv_cache is not None:
        ck, cv = kv_cache
        ck = lax.dynamic_update_slice(ck, k, (0, cache_pos, 0, 0))
        cv = lax.dynamic_update_slice(cv, v, (0, cache_pos, 0, 0))
        new_cache = (ck, cv)
        if _use_flash():
            # fused decode: no repeat of the whole cache across query
            # heads, and K blocks past the filled prefix are skipped —
            # cost scales with cache_pos + T, not the cache capacity
            from demodel_tpu.ops.flash_attention import flash_attention

            out = flash_attention(q, ck, cv, kv_len=cache_pos + T,
                                  causal=True)
        else:
            S = ck.shape[1]
            rep = H // Hkv
            kk = jnp.repeat(ck, rep, axis=2)
            vv = jnp.repeat(cv, rep, axis=2)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * hd ** -0.5
            kpos = jnp.arange(S)
            qpos = cache_pos + jnp.arange(T)
            mask = kpos[None, :] <= qpos[:, None]
            scores = jnp.where(mask[None, None], scores, -1e30)
            probs = jax.nn.softmax(scores.astype(jnp.float32),
                                   axis=-1).astype(q.dtype)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs, vv)
    elif mesh is not None and int(mesh.shape.get("sp", 1)) > 1:
        out = ring_attention_sharded(q, k, v, mesh, causal=True)
    elif _use_flash():
        # fused pallas path: no (B,H,T,T) score tensor in HBM, no
        # materialized GQA repeat (ops/flash_attention.py); backward
        # recomputes the reference, so training still differentiates
        from demodel_tpu.ops.flash_attention import flash_attention

        out = flash_attention(q, k, v, causal=True)
    else:
        out = dense_attention(q, k, v, causal=True)
    out = out.reshape(B, T, H * hd) @ layer["o_proj"]
    return out, new_cache


def _block(layer, x, cfg, positions, mesh, kv_cache=None, cache_pos=None):
    h, new_cache = _attn(layer, rms_norm(x, layer["attn_norm"],
                                         cfg.rms_norm_eps),
                         cfg, positions, mesh, kv_cache, cache_pos)
    x = x + h
    y = rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
    y = (jax.nn.silu(y @ layer["gate_proj"]) * (y @ layer["up_proj"])) \
        @ layer["down_proj"]
    return x + y, new_cache


def _seq_constraint(x, mesh: Mesh | None):
    if mesh is not None and int(mesh.shape.get("sp", 1)) > 1:
        return lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("dp", "sp", None)))
    return x


def forward(params, tokens, cfg: LlamaConfig, mesh: Mesh | None = None):
    """tokens [B, T] int32 → logits [B, T, V]."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    x = params["embed"][tokens]
    x = _seq_constraint(x, mesh)
    for layer in params["layers"]:
        x, _ = _block(layer, x, cfg, positions, mesh)
        x = _seq_constraint(x, mesh)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return x @ params["lm_head"]


# ------------------------------------------------------------ decode path


def init_cache(cfg: LlamaConfig, batch: int, max_len: int, dtype=None):
    dt = jnp.dtype(dtype or cfg.dtype)
    hd = cfg.head_dim
    return [
        (jnp.zeros((batch, max_len, cfg.num_key_value_heads, hd), dt),
         jnp.zeros((batch, max_len, cfg.num_key_value_heads, hd), dt))
        for _ in range(cfg.num_hidden_layers)
    ]


def forward_with_cache(params, tokens, cfg: LlamaConfig, cache, pos,
                       mesh: Mesh | None = None):
    """Incremental forward: ``tokens`` [B, T] appended at ``pos`` (prefill
    with T>1, decode with T=1). Returns (logits, new_cache). ``mesh``
    (when the params are sharded over one) keeps the projection shards
    head-aligned through RoPE — see :func:`_head_align`."""
    B, T = tokens.shape
    positions = pos + jnp.broadcast_to(jnp.arange(T), (B, T))
    x = params["embed"][tokens]
    new_cache = []
    for layer, kv in zip(params["layers"], cache):
        x, nkv = _block(layer, x, cfg, positions, mesh, kv_cache=kv,
                        cache_pos=pos)
        new_cache.append(nkv)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return x @ params["lm_head"], new_cache


def cache_spec(cfg: LlamaConfig):
    """What the serving engine keeps for a sequence: every layer pages K
    and V, nothing of fixed size."""
    from demodel_tpu.serve.kvcache import CacheSpec

    return CacheSpec(cfg.num_hidden_layers, cfg.num_key_value_heads,
                     cfg.head_dim)


def step_prefill(params, tokens, cfg: LlamaConfig, mesh: Mesh | None = None):
    """Prefill leg of the serving plane: ``tokens`` [B, T] (one sequence,
    or a few of EQUAL length) → ``(last_logits [B, V], kv)`` where ``kv``
    is the per-layer ``(k, v)`` pair, each [B, T, Hkv, hd] — exactly the
    prompt's keys/values, which the engine's prefill program writes into
    the lease's blocks of the pool on the device, in the same program
    (``kvcache.put_blocks``). The cache is sized to the prompt, so this
    is :func:`forward_with_cache` with nothing left over."""
    B, T = tokens.shape
    cache = init_cache(cfg, B, T)
    logits, kv = forward_with_cache(params, tokens, cfg, cache, 0, mesh=mesh)
    return logits[:, -1], kv


def step_decode(params, tokens, cfg: LlamaConfig, cache, lengths,
                mesh: Mesh | None = None):
    """One continuous-batching decode step over a RAGGED batch.

    ``tokens`` [B] int32 — the last sampled token of each running
    sequence; ``cache`` the engine's pool with the batch's block table
    (``kvcache.Paged``: ``table`` [B, n], ``block_tokens``, ``read(layer,
    ids)``), whose blocks every layer reads where they lie (slots at or
    past ``lengths[b]`` are stale pool bytes and are masked out here);
    ``lengths`` [B] int32 — filled prefix per sequence, so the fed token
    sits at position ``lengths[b]`` (positions need not agree across the
    batch — that is the whole point). Returns ``(logits [B, V], new_kv)``
    with ``new_kv`` per-layer ``(k, v)`` each [B, 1, Hkv, hd], which the
    engine's program writes into the pool at ``lengths``
    (``kvcache.put_positions``). Rows padded up to a jit bucket ride along
    with ``lengths[b] == 0`` (they attend only to themselves) and are
    dropped by the caller."""
    B = cache.table.shape[0]
    hd = cfg.head_dim
    H, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    positions = lengths[:, None]                      # [B, 1]
    filled = cache.filled(lengths)
    x = params["embed"][tokens[:, None]]              # [B, 1, D]
    new_kv = []
    for li, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
        q = (h @ layer["q_proj"]).reshape(B, 1, H, hd)
        k = (h @ layer["k_proj"]).reshape(B, 1, Hkv, hd)
        v = (h @ layer["v_proj"]).reshape(B, 1, Hkv, hd)
        q = _head_align(q, mesh)
        k = _head_align(k, mesh)
        v = _head_align(v, mesh)
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
        new_kv.append((k, v))
        out = attend(q, k, v, positions, past=cache.past(li, filled))
        x = x + out @ layer["o_proj"]
        y = rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
        y = (jax.nn.silu(y @ layer["gate_proj"]) * (y @ layer["up_proj"])) \
            @ layer["down_proj"]
        x = x + y
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return (x @ params["lm_head"])[:, 0], new_kv


def generate(params, cfg: LlamaConfig, prompt, max_new_tokens: int,
             temperature: float = 0.0, key=None, mesh: Mesh | None = None):
    """Autoregressive decode: prefill the prompt once, then one cached
    step per token (jitted, static shapes). temperature 0 → greedy."""
    prompt = jnp.asarray(prompt)
    if prompt.ndim == 1:
        prompt = prompt[None]
    B, T0 = prompt.shape
    max_len = T0 + max_new_tokens
    cache = init_cache(cfg, B, max_len)
    if key is None:
        key = jax.random.key(0)

    prefill = jax.jit(
        lambda p, t, c: forward_with_cache(p, t, cfg, c, 0, mesh=mesh))
    logits, cache = prefill(params, prompt, cache)
    last = logits[:, -1]

    @jax.jit
    def step(carry, _):
        last, cache, pos, k = carry
        k, sub = jax.random.split(k)
        if temperature > 0:
            tok = jax.random.categorical(sub, last / temperature, axis=-1)
        else:
            tok = jnp.argmax(last, axis=-1)
        tok = tok.astype(jnp.int32)
        logits, cache = forward_with_cache(params, tok[:, None], cfg, cache,
                                           pos, mesh=mesh)
        return (logits[:, -1], cache, pos + 1, k), tok

    carry = (last, cache, jnp.int32(T0), key)
    out_toks = []
    for _ in range(max_new_tokens):
        carry, tok = step(carry, None)
        out_toks.append(tok)
    return jnp.stack(out_toks, axis=1)


# -------------------------------------------------------------- train step


def loss_fn(params, tokens, cfg: LlamaConfig, mesh: Mesh | None = None):
    """Next-token cross entropy (fp32 logits for the softmax)."""
    logits = forward(params, tokens[:, :-1], cfg, mesh).astype(jnp.float32)
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -ll.mean()


def make_train_step(cfg: LlamaConfig, mesh: Mesh | None = None,
                    lr: float = 1e-3, momentum: float = 0.9):
    """(init_opt, train_step) with a momentum-SGD state that mirrors the
    params tree leaf-for-leaf — the same sharding tree places both."""

    def init_opt(params):
        return jax.tree.map(jnp.zeros_like, params)

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, cfg, mesh)
        new_opt = jax.tree.map(lambda m, g: momentum * m + g, opt_state, grads)
        new_params = jax.tree.map(lambda p, m: p - lr * m, params, new_opt)
        return new_params, new_opt, loss

    return init_opt, jax.jit(train_step)
