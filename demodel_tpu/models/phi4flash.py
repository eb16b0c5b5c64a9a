"""Phi-4-mini-flash (``model_type`` ``phi4flash``, the SambaY decoder): a
self-decoder of Mamba layers alternating with window attention, one
full-attention layer, and a cross-decoder of gated memory units alternating
with cross-attention that has no keys of its own.

For layer input ``x`` [T, D], LayerNorm with weight and bias (statistics in
float32), no positional encoding anywhere: ``x += mixer(LN1(x))``, ``x +=
mlp(LN2(x))``; a final LayerNorm; the head is the embedding transposed. With
``half = num_hidden_layers / 2`` the mixer of layer ``i`` is

- ``i <= half``, even: **Mamba**. ``[x, z] = in_proj(u)``; ``x ← silu(causal
  depthwise convolution, kernel 4, with bias)``; ``[δ, B, C] = x_proj(x)``;
  ``Δ = softplus(dt_proj(δ) + dt_bias)``; ``A = −exp(A_log)`` [d_inner, N];
  ``h_t = exp(Δ_t A) ⊙ h_{t−1} + (Δ_t x_t) ⊗ B_t``; ``y_t = h_t C_t + D ⊙
  x_t`` (``Δ``, ``A``, ``h`` in float32); ``out_proj(y ⊙ silu(z))``. Layer
  ``half``, the last to scan, also hands ``y`` (before the gate) on as the
  step's **memory** ``M``.
- ``i < half``, odd: **window attention**, a key ``sliding_window`` or more
  behind is not seen; ``i == half + 1``: **full attention**. Both
  *differential*: adjacent heads pair (queries ``2j, 2j + 1`` are ``q1, q2``
  of pair ``j``, keys and values likewise; query pair ``j`` reads KV pair
  ``j // (H / Hkv)``). ``a_s = softmax(q_s k_sᵀ / sqrt(hd)) [v1 | v2]`` for
  ``s`` 1, 2; ``λ = exp(λ_q1 · λ_k1) − exp(λ_q2 · λ_k2) + λ_init``, ``λ_init
  = 0.8 − 0.6 exp(−0.3 i)``; ``o = RMSNorm_w(a_1 − λ a_2) · (1 − λ_init)``
  over the ``2 hd`` of a pair; ``out_proj`` with bias over the pairs.
- ``i > half + 1``, even: **gated memory unit**, ``out_proj(silu(in_proj(u))
  ⊙ M)``, ``M`` layer ``half``'s ``y`` at the same position.
- ``i > half + 1``, odd: **cross-attention**: its own query projection,
  ``λ``s, sub-norm and ``out_proj``; its keys and values are layer ``half +
  1``'s, for every cached position and the new one.
- MLP, every layer: ``[gate, up] = fc1(x)``; ``fc2(up ⊙ silu(gate))``.

**Differential attention is grouped attention over pairs.** A KV pair's keys
``[k1 | k2]`` and values ``[v1 | v2]`` are one head of ``2 hd``; a query
``q_s`` padded with zeros over the half it does not read scores ``q_s · k_s``
exactly, and its probabilities over the pair's values are the ``2 hd``-wide
``a_s``. So the module attends through :func:`common.attend` like every other
family, with ``Hkv / 2`` heads of ``2 hd``, and its pages and rings hold
pairs.

**The cache** (:func:`cache_spec`). One layer pages: the full-attention
layer's pairs, which it and the cross-attention layers read. A window layer
keeps the last ``sliding_window`` positions as a ring in the sequence's slot;
a Mamba layer its state (float32) and the convolution's last ``kernel − 1``
inputs. Layers past ``half + 1`` write no cache of any kind, and of that
layer only the keys and values are read again: a prefill computes them for
the whole prompt and everything from that layer's query on for the
prompt's last position only (exact: nothing later reads what the other
positions would have given).

``tp`` shards nothing of this family: under a mesh everything is replicated.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from demodel_tpu.models.common import attend, layer_norm
from demodel_tpu.models.hf_loader import Weights, lay
from demodel_tpu.utils.metrics import HUB

#: positions a chunk of the prefill's scan holds
CHUNK = 64
#: positions a block of a ring holds (or the largest divisor of the window)
RING_BLOCK = 16
#: the state-space state is carried and kept in float32
STATE_DTYPE = "float32"
#: eps of the sub-norm over a pair's ``2 hd``
SUBLN_EPS = 1e-5

HUB.inc("gen_shared_kv_bytes_total", 0)
HUB.inc("gen_state_bytes_total", 0)


@dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    dtype: str = "float32"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def half(self) -> int:
        return self.num_hidden_layers // 2

    @property
    def kinds(self) -> tuple[str, ...]:
        """Each layer's mixer: ``mamba``, ``window``, ``full``, ``gmu`` or
        ``cross``."""
        return tuple(
            ("mamba" if i % 2 == 0 else "window") if i <= self.half
            else "full" if i == self.half + 1
            else "gmu" if i % 2 == 0 else "cross"
            for i in range(self.num_hidden_layers))

    @property
    def ring_block(self) -> int:
        return math.gcd(self.sliding_window, RING_BLOCK)

    def lambda_init(self, layer: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * layer)

    @classmethod
    def tiny(cls, **over) -> "Phi4FlashConfig":
        """Test-sized: 8 layers (three Mamba, two window, the full one, a
        memory unit, a cross-attention), 4 query / 2 KV heads of 16."""
        kw = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                  num_hidden_layers=8, num_attention_heads=4,
                  num_key_value_heads=2, sliding_window=12,
                  mamba_d_state=8, mamba_dt_rank=4)
        kw.update(over)
        return cls(**kw)

    @classmethod
    def from_hf(cls, config: dict) -> "Phi4FlashConfig":
        """From a ``config.json``; what this module does not implement is
        refused. The Mamba sizes are the family's where the file does not
        state them (``d_state`` 16, ``d_conv`` 4, ``expand`` 2, ``dt_rank``
        ``ceil(hidden_size / 16)``)."""
        for key, only in (("mb_per_layer", 2), ("hidden_act", "silu"),
                          ("tie_word_embeddings", True),
                          ("mlp_bias", False), ("lm_head_bias", False),
                          ("embd_pdrop", 0), ("resid_pdrop", 0),
                          ("attention_dropout", 0),
                          ("mamba_conv_bias", True),
                          ("mamba_proj_bias", False),
                          ("rope_scaling", None)):
            if config.get(key, only) != only:
                raise ValueError(f"config field {key}={config[key]!r} is "
                                 "not supported by this stack")
        L = int(config["num_hidden_layers"])
        if L % 4 or L < 8:
            raise ValueError(f"config field num_hidden_layers={L} is not "
                             "supported by this stack: the layer pattern "
                             "needs a multiple of 4, at least 8")
        D, H = int(config["hidden_size"]), int(config["num_attention_heads"])
        Hkv = int(config.get("num_key_value_heads", H))
        if H % 2 or Hkv % 2 or H % Hkv or D % H:
            raise ValueError(f"differential attention pairs heads: {H} "
                             f"query and {Hkv} KV heads over {D} do not")
        window = config.get("sliding_window")
        if not isinstance(window, int) or window <= 0:
            raise ValueError(f"config field sliding_window={window!r} is "
                             "not supported by this stack")
        rank = config.get("mamba_dt_rank", "auto")
        return cls(
            vocab_size=int(config["vocab_size"]),
            hidden_size=D,
            intermediate_size=int(config["intermediate_size"]),
            num_hidden_layers=L,
            num_attention_heads=H,
            num_key_value_heads=Hkv,
            sliding_window=window,
            layer_norm_eps=float(config.get("layer_norm_eps", 1e-5)),
            mamba_d_state=int(config.get("mamba_d_state", 16)),
            mamba_d_conv=int(config.get("mamba_d_conv", 4)),
            mamba_expand=int(config.get("mamba_expand", 2)),
            mamba_dt_rank=-(-D // 16) if rank == "auto" else int(rank),
            dtype=(config.get("torch_dtype") or config.get("dtype")
                   or "float32"),
        )


def cache_spec(cfg: Phi4FlashConfig):
    """What the serving engine keeps for a sequence: the full-attention
    layer pages its pairs' keys and values (``Hkv / 2`` heads of ``2 hd``);
    the slot holds each window layer's ring of the last ``sliding_window``
    positions, and each Mamba layer's state and convolution tail."""
    from demodel_tpu.serve.kvcache import CacheSpec

    kinds = cfg.kinds
    pairs, wide = cfg.num_key_value_heads // 2, 2 * cfg.head_dim
    ring = (kinds.count("window"), cfg.sliding_window // cfg.ring_block,
            pairs, cfg.ring_block, wide)
    mamba = kinds.count("mamba")
    return CacheSpec(
        kinds.count("full"), pairs, wide,
        state=(("ring_k", ring, cfg.dtype), ("ring_v", ring, cfg.dtype),
               ("ssm_state", (mamba, cfg.d_inner, cfg.mamba_d_state),
                STATE_DTYPE),
               ("ssm_conv", (mamba, cfg.mamba_d_conv - 1, cfg.d_inner),
                cfg.dtype)))


# ------------------------------------------------------------------ params


def stack_layers(layers: list[dict], cfg: Phi4FlashConfig) -> dict:
    """The layers' trees, in the model's order, as the steps run them: the
    first Mamba layer; the (window, Mamba) pairs that follow, each kind's
    leaves stacked over the pairs (one ``lax.scan`` runs them: a program
    holds one pair, not ``half / 2``); the full-attention layer; the
    (memory unit, cross-attention) pairs, stacked likewise. ``layers`` is
    consumed: a leaf leaves its layer's tree as it enters a stack, so that
    a model being loaded is held about once, not twice."""
    half = cfg.half

    def stack(trees):
        return {name: jnp.stack([tree.pop(name) for tree in trees])
                for name in list(trees[0])}

    return {"first": layers[0],
            "pairs": {"window": stack(layers[1:half:2]),
                      "mamba": stack(layers[2:half + 1:2])},
            "full": layers[half + 1],
            "cross": {"gmu": stack(layers[half + 2::2]),
                      "cross": stack(layers[half + 3::2])}}


def init_layers(key, cfg: Phi4FlashConfig) -> tuple[jax.Array, list[dict]]:
    """``(embedding, one tree a layer)``: seeded N(0, 1/fan_in) matrices
    (the embedding's fan-in is its width: it is the head), ``λ`` vectors
    N(0, 0.1²); norm weights, sub-norms and ``D`` ones;
    biases, ``dt_bias`` and ``A_log`` zeros."""
    dt = jnp.dtype(cfg.dtype)
    D, I, hd = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    H, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    Dn, N, R, K = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank, \
        cfg.mamba_d_conv
    keys = iter(jax.random.split(key, 12 * cfg.num_hidden_layers + 1))

    def dense(*shape, fan_in=None):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in or shape[-2])).astype(dt)

    def zeros(*shape):
        return jnp.zeros(shape, dt)

    def ones(*shape):
        return jnp.ones(shape, dt)

    layers = []
    for kind in cfg.kinds:
        layer = {"ln1_w": ones(D), "ln1_b": zeros(D), "ln2_w": ones(D),
                 "ln2_b": zeros(D), "fc1": dense(D, 2 * I),
                 "fc2": dense(I, D)}
        if kind == "mamba":
            layer.update({
                "in_proj": dense(D, 2 * Dn),
                "conv_w": dense(K, Dn, fan_in=K), "conv_b": zeros(Dn),
                "x_proj": dense(Dn, R + 2 * N), "dt_proj": dense(R, Dn),
                "dt_bias": zeros(Dn), "A_log": zeros(Dn, N), "D": ones(Dn),
                "out_proj": dense(Dn, D)})
        elif kind == "gmu":
            layer.update({"in_proj": dense(D, Dn), "out_proj": dense(Dn, D)})
        else:
            if kind != "cross":     # Wqkv's key and value columns
                layer.update({"wkv": dense(D, 2 * Hkv * hd),
                              "bkv": zeros(2 * Hkv * hd)})
            layer.update({
                "wq": dense(D, H * hd), "bq": zeros(H * hd),
                "out_proj": dense(H * hd, D), "out_bias": zeros(D),
                "subln": ones(2 * hd),
                **{f"lambda_{x}": dense(hd, fan_in=100)
                   for x in ("q1", "k1", "q2", "k2")}})
        layers.append(layer)
    return dense(cfg.vocab_size, D, fan_in=D), layers


def init_params(key, cfg: Phi4FlashConfig) -> dict:
    """Seeded weights as the tree :func:`load_params`
    builds: the embedding (which is the head), the final norm, and the
    layers as :func:`stack_layers` groups them."""
    dt = jnp.dtype(cfg.dtype)
    embed, layers = init_layers(key, cfg)
    return {"embed": embed, "final_ln_w": jnp.ones((cfg.hidden_size,), dt),
            "final_ln_b": jnp.zeros((cfg.hidden_size,), dt),
            **stack_layers(layers, cfg)}


from_hf = Phi4FlashConfig.from_hf
#: served through its step functions only
forward = None


def load_params(weights: dict, cfg: Phi4FlashConfig, mesh=None) -> dict:
    """The tree of :func:`init_params` (the layers grouped and stacked by
    :func:`stack_layers`). Every layer's mixer is
    ``attn`` in the checkpoint, whatever its kind. An attention layer's
    ``Wqkv`` (queries, then keys, then values) enters as the query columns
    and the key and value columns apart, so that a prefill can make keys
    for a whole prompt and a query for its last position; the head is the
    embedding, which the tree holds once."""
    w = Weights(weights)
    # tp shards nothing of this family: every leaf is laid replicated
    rep = NamedSharding(mesh, PartitionSpec()) if mesh is not None else None
    nq = cfg.num_attention_heads * cfg.head_dim
    layers = []
    for i, kind in enumerate(cfg.kinds):
        pre = f"layers.{i}."

        def lin(name):
            return w.get(pre + name, transpose=True, sharding=rep)

        def vec(name):
            return w.get(pre + name, sharding=rep)

        layer = {
            "ln1_w": vec("input_layernorm.weight"),
            "ln1_b": vec("input_layernorm.bias"),
            "ln2_w": vec("post_attention_layernorm.weight"),
            "ln2_b": vec("post_attention_layernorm.bias"),
            "fc1": lin("mlp.fc1.weight"),
            "fc2": lin("mlp.fc2.weight"),
        }
        if kind == "mamba":
            conv = w.get(pre + "attn.conv1d.weight")        # [Dn, 1, K]
            layer.update({
                "in_proj": lin("attn.in_proj.weight"),
                "conv_w": lay(conv.reshape(conv.shape[0], conv.shape[-1]),
                               True, rep),
                "conv_b": vec("attn.conv1d.bias"),
                "x_proj": lin("attn.x_proj.weight"),
                "dt_proj": lin("attn.dt_proj.weight"),
                "dt_bias": vec("attn.dt_proj.bias"),
                "A_log": vec("attn.A_log"),
                "D": vec("attn.D"),
                "out_proj": lin("attn.out_proj.weight"),
            })
        elif kind == "gmu":
            layer.update({
                "in_proj": lin("attn.in_proj.weight"),
                "out_proj": lin("attn.out_proj.weight"),
            })
        else:
            wqkv = w.get(pre + "attn.Wqkv.weight")
            bqkv = w.get(pre + "attn.Wqkv.bias")
            layer.update({"wq": lay(wqkv[:nq], True, rep),
                          "bq": lay(bqkv[:nq], sharding=rep)})
            if kind != "cross":
                layer.update({"wkv": lay(wqkv[nq:], True, rep),
                              "bkv": lay(bqkv[nq:], sharding=rep)})
            layer.update({
                "out_proj": lin("attn.out_proj.weight"),
                "out_bias": vec("attn.out_proj.bias"),
                "subln": vec("attn.inner_cross_attn.subln.weight"),
                **{f"lambda_{x}": vec(f"attn.inner_cross_attn.lambda_{x}")
                   for x in ("q1", "k1", "q2", "k2")}})
        layers.append(layer)
    return {
        "embed": w.get("embed_tokens.weight", sharding=rep),
        "final_ln_w": w.get("final_layernorm.weight", sharding=rep),
        "final_ln_b": w.get("final_layernorm.bias", sharding=rep),
        **stack_layers(layers, cfg),
    }


# ------------------------------------------------------ the selective scan


def selective_scan_chunks(x, dt, A, Bm, Cm, state=None, chunk: int = CHUNK):
    """The recurrence ``h_t = exp(Δ_t A) ⊙ h_{t−1} + (Δ_t x_t) ⊗ B_t``, ``y_t
    = h_t C_t`` over ``T`` positions, chunk-wise. ``x``, ``dt`` [B, T, Dn],
    ``A`` [Dn, N] (< 0), ``Bm``, ``Cm`` [B, T, N], all float32; ``state``
    [B, Dn, N] or None for zeros. Returns ``(y [B, T, Dn], final state)``.

    The decay differs by channel and by state, so nothing inside a chunk is
    a matrix product. Every chunk runs the recurrence from a zero state at
    once, ``chunk`` steps over ``[B, T / chunk, Dn, N]``, giving its part of
    ``y`` and what it adds to the state; one carry of the state a chunk (a
    ``lax.scan``) gives the state each chunk starts from; that state, decayed
    by ``exp(A ΣΔ)`` up to each position, adds the rest of ``y``. Positions
    past ``T`` up to a whole chunk ride along with ``Δ = 0``: they leave the
    state as it is."""
    B, T, Dn = x.shape
    N = A.shape[1]
    C = chunk
    n = -(-T // C)
    pad = n * C - T

    def split(a):       # [B, T, w] -> [C, B, n, w]
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        return jnp.moveaxis(a.reshape(B, n, C, a.shape[-1]), 2, 0)

    x, dt, Bm, Cm = split(x), split(dt), split(Bm), split(Cm)

    def position(h, t):         # h [B, n, Dn, N]
        x_t, dt_t, B_t, C_t = t
        h = h * jnp.exp(dt_t[..., None] * A) \
            + (dt_t * x_t)[..., None] * B_t[:, :, None, :]
        return h, (h * C_t[:, :, None, :]).sum(axis=-1)

    added, y = lax.scan(position, jnp.zeros((B, n, Dn, N), jnp.float32),
                        (x, dt, Bm, Cm))
    total = jnp.cumsum(dt, axis=0)                      # [C, B, n, Dn]

    def carry(h, c):            # h [B, Dn, N]
        decay_c, added_c = c
        return h * decay_c + added_c, h

    if state is None:
        state = jnp.zeros((B, Dn, N), jnp.float32)
    state, starts = lax.scan(carry, state, (
        jnp.moveaxis(jnp.exp(total[-1][..., None] * A), 1, 0),
        jnp.moveaxis(added, 1, 0)))                     # starts [n, B, Dn, N]
    starts = jnp.moveaxis(starts, 0, 1)
    y = y + (jnp.exp(total[..., None] * A) * starts[None]
             * Cm[:, :, :, None, :]).sum(axis=-1)
    return jnp.moveaxis(y, 0, 2).reshape(B, n * C, Dn)[:, :T], state


def selective_step(x, dt, A, Bm, Cm, state):
    """One position of the recurrence: ``x``, ``dt`` [B, Dn], ``Bm``, ``Cm``
    [B, N], ``state`` [B, Dn, N] → ``(y [B, Dn], state)``; a row's state is
    read once and written once."""
    state = state * jnp.exp(dt[..., None] * A) \
        + (dt * x)[..., None] * Bm[:, None, :]
    return (state * Cm[:, None, :]).sum(axis=-1), state


# ------------------------------------------------------------ the mixers


def _ssm(layer, u, cfg: Phi4FlashConfig, past=None):
    """``u`` [B, T, D] → ``(out [B, T, D], y [B, T, Dn], (state [B, Dn, N],
    tail [B, K − 1, Dn]))``, ``y`` the scan's output before the gate.
    ``past`` None: a prompt from its start, the scan chunk-wise. ``past``
    ``(state, tail)``: one new position a row (T = 1) on the slot's state
    and the convolution's last inputs."""
    B, T, _D = u.shape
    Dn, N, R, K = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank, \
        cfg.mamba_d_conv
    xz = u @ layer["in_proj"]
    x, z = xz[..., :Dn], xz[..., Dn:]
    with jax.named_scope("ssm.conv"):
        before = jnp.zeros((B, K - 1, Dn), x.dtype) if past is None \
            else past[1]
        window = jnp.concatenate([before, x], axis=1)       # [B, K-1+T, Dn]
        taps = layer["conv_w"].astype(jnp.float32)
        conv = sum(window[:, i:i + T].astype(jnp.float32) * taps[i]
                   for i in range(K)) + layer["conv_b"].astype(jnp.float32)
        x = jax.nn.silu(conv).astype(u.dtype)
        tail = window[:, T:]                                # the last K - 1
    dbc = x @ layer["x_proj"]
    dt = jax.nn.softplus(
        (dbc[..., :R] @ layer["dt_proj"]).astype(jnp.float32)
        + layer["dt_bias"].astype(jnp.float32))
    Bm = dbc[..., R:R + N].astype(jnp.float32)
    Cm = dbc[..., R + N:].astype(jnp.float32)
    A = -jnp.exp(layer["A_log"].astype(jnp.float32))
    xf = x.astype(jnp.float32)
    if past is None:
        with jax.named_scope("ssm.scan"):
            y, state = selective_scan_chunks(xf, dt, A, Bm, Cm)
    else:
        with jax.named_scope("ssm.step"):
            y, state = selective_step(xf[:, 0], dt[:, 0], A, Bm[:, 0],
                                      Cm[:, 0], past[0])
            y = y[:, None]
    y = y + layer["D"].astype(jnp.float32) * xf
    out = (y * jax.nn.silu(z.astype(jnp.float32))).astype(u.dtype)
    return out @ layer["out_proj"], y.astype(u.dtype), (state, tail)


def _gmu(layer, u, memory):
    """The gated memory unit: an element-wise gate of the memory."""
    return (jax.nn.silu(u @ layer["in_proj"]) * memory) @ layer["out_proj"]


def _mlp(layer, x):
    h = x @ layer["fc1"]
    gate, up = jnp.split(h, 2, axis=-1)
    return (up * jax.nn.silu(gate)) @ layer["fc2"]


def _pairs(a, cfg: Phi4FlashConfig):
    """Keys or values [B, T, Hkv * hd] as the pairs' heads: [B, T, Hkv / 2,
    2 hd], ``[k1 | k2]`` of a pair side by side as the projection has them."""
    B, T, _w = a.shape
    return a.reshape(B, T, cfg.num_key_value_heads // 2, 2 * cfg.head_dim)


def _padded_queries(q, cfg: Phi4FlashConfig):
    """Queries [B, T, H * hd] → [B, T, H, 2 hd]: ``q1`` of a pair over the
    first half and zeros over the second, ``q2`` the other way, in the
    order :func:`common.attend` groups them by KV pair (a KV pair's query
    pairs, each ``q1`` then ``q2``)."""
    B, T, _w = q.shape
    hd = cfg.head_dim
    q = q.reshape(B, T, cfg.num_attention_heads // 2, 2, hd)
    none = jnp.zeros_like(q[:, :, :, 0])
    q = jnp.stack([jnp.concatenate([q[:, :, :, 0], none], axis=-1),
                   jnp.concatenate([none, q[:, :, :, 1]], axis=-1)], axis=3)
    return q.reshape(B, T, cfg.num_attention_heads, 2 * hd)


def _differential(layer, a, cfg: Phi4FlashConfig, init):
    """What :func:`common.attend` gave for the padded queries, [B, T, H * 2
    hd], → the layer's output: ``a_1 − λ a_2`` a pair, the sub-norm over
    its ``2 hd``, ``1 − λ_init`` (``init``, the layer's), ``out_proj``."""
    B, T, _w = a.shape
    hd = cfg.head_dim
    a = a.reshape(B, T, cfg.num_attention_heads // 2, 2, 2 * hd).astype(
        jnp.float32)

    def dot(x, y):
        return jnp.exp((layer[f"lambda_{x}"].astype(jnp.float32)
                        * layer[f"lambda_{y}"].astype(jnp.float32)).sum())

    lam = dot("q1", "k1") - dot("q2", "k2") + init
    o = a[:, :, :, 0] - lam * a[:, :, :, 1]
    o = o * lax.rsqrt((o * o).mean(axis=-1, keepdims=True) + SUBLN_EPS)
    o = o * layer["subln"].astype(jnp.float32) * (1.0 - init)
    return o.reshape(B, T, -1).astype(layer["out_proj"].dtype) \
        @ layer["out_proj"] + layer["out_bias"]


def _blocks(a, W: int):
    """[B, n * W, Hp, w] → [B, n, Hp, W, w], as attention takes cached
    blocks."""
    B, T, Hp, w = a.shape
    return a.reshape(B, T // W, W, Hp, w).transpose(0, 1, 3, 2, 4)


def _attend_band(q, k, v, cfg: Phi4FlashConfig):
    """A window layer over a prompt from its start: the queries in blocks
    of ``sliding_window`` positions, each block over the block before it
    and its own, all blocks at once as rows of a batch, so that the scores
    are a band and no ``[T, T]`` array exists. q [B, T, H, w], k, v [B, T,
    Hp, w] → [B, T, H * w]."""
    B, T, H, w = q.shape
    W = cfg.sliding_window
    n = -(-T // W)
    q, k, v = (jnp.pad(a, ((0, 0), (0, n * W - T), (0, 0), (0, 0)))
               for a in (q, k, v))

    def rows(a):            # [B, n * W, ...] -> [B * n, W, ...]
        return a.reshape(B * n, W, *a.shape[2:])

    def before(a):          # the block before each, zeros before the first
        a = jnp.pad(a, ((0, 0), (W, 0), (0, 0), (0, 0)))[:, :n * W]
        return _blocks(rows(a), W)

    pos = jnp.tile(jnp.arange(n * W).reshape(n, W), (B, 1))
    out = attend(rows(q), rows(k), rows(v), pos, window=W,
                 scale=cfg.head_dim ** -0.5,
                 past=(before(k), before(v), pos - W, pos >= W))
    return out.reshape(B, n * W, H * w)[:, :T]


def _kv(layer, h, cfg: Phi4FlashConfig):
    """A layer's keys and values for ``h`` [B, T, D], as pairs."""
    kv = h @ layer["wkv"] + layer["bkv"]
    return tuple(_pairs(a, cfg) for a in jnp.split(kv, 2, axis=-1))


def _diff_attn(layer, h, cfg: Phi4FlashConfig, init, kv, positions, past,
               window: int = 0):
    """Differential attention of ``h``'s queries [B, T, D] at ``positions``
    over the new pairs ``kv`` and the cached ``past`` ``(k, v, kpos,
    live)`` (pairs in blocks: the pages, a ring, a prompt's earlier
    positions). ``positions`` None: a window layer over a prompt from its
    start, in a band."""
    q = _padded_queries(h @ layer["wq"] + layer["bq"], cfg)
    with jax.named_scope("attn.diff"):
        if positions is None:
            a = _attend_band(q, *kv, cfg)
        else:
            a = attend(q, *kv, positions, window=window,
                       scale=cfg.head_dim ** -0.5, past=past)
        return _differential(layer, a, cfg, init)


def _norm1(layer, x, cfg):
    return layer_norm(x, layer["ln1_w"], layer["ln1_b"], cfg.layer_norm_eps)


def _residual(layer, x, a, cfg: Phi4FlashConfig):
    """``x`` plus its mixer's output ``a``, then the layer's MLP."""
    x = x + a
    return x + _mlp(layer, layer_norm(x, layer["ln2_w"], layer["ln2_b"],
                                      cfg.layer_norm_eps))


def _mamba_layer(layer, x, cfg: Phi4FlashConfig, past):
    with jax.named_scope("ssm"):
        a, y, kept = _ssm(layer, _norm1(layer, x, cfg), cfg, past)
    return _residual(layer, x, a, cfg), y, kept


def _forward(params, x, cfg: Phi4FlashConfig, cache=None, lengths=None):
    """Every layer over ``x`` [B, T, D] → ``(x [B, 1, D], the full layer's
    (k, v), the window layers' (k, v) stacked [pairs, ...], the Mamba
    layers' (states, tails) stacked [1 + pairs, ...])``. ``cache`` None: a
    prompt from its start; from the full layer's query on, only its last
    position is computed. Else a step of one position a row at ``lengths``
    over ``cache`` (``kvcache.Paged``). The (window, Mamba) pairs run under
    one ``lax.scan`` and the (memory unit, cross-attention) pairs under
    another, each reading its layer of the cache by the pair's index."""
    from demodel_tpu.serve import kvcache

    step = cache is not None
    B, T, _D = x.shape
    W, half, L = cfg.sliding_window, cfg.half, cfg.num_hidden_layers
    positions = pages = None
    if step:
        positions = lengths[:, None]
        pages = cache.past(0, cache.filled(lengths))
        rpos = kvcache.ring_positions(lengths, W)

    def ssm_past(i):
        return (cache.read_state("ssm_state", i),
                cache.read_state("ssm_conv", i)) if step else None

    def ring_past(i):
        return (cache.read_state("ring_k", i), cache.read_state("ring_v", i),
                rpos, rpos >= 0) if step else None

    x, memory, (state0, tail0) = _mamba_layer(params["first"], x, cfg,
                                              ssm_past(0))

    def pair(carry, inp):
        x, _memory = carry
        layers, i, init = inp
        window, mamba = layers["window"], layers["mamba"]
        with jax.named_scope("attn.window"):
            h = _norm1(window, x, cfg)
            kv = _kv(window, h, cfg)
            a = _diff_attn(window, h, cfg, init, kv, positions, ring_past(i),
                           window=W)
        x = _residual(window, x, a, cfg)
        x, memory, kept = _mamba_layer(mamba, x, cfg, ssm_past(i + 1))
        return (x, memory), (kv, kept)

    inits = [cfg.lambda_init(i) for i in range(L)]
    (x, memory), (rings, (states, tails)) = lax.scan(
        pair, (x, memory), (params["pairs"], jnp.arange(half // 2),
                            jnp.asarray(inits[1:half:2], jnp.float32)))

    full = params["full"]
    with jax.named_scope("attn.full"):
        h = _norm1(full, x, cfg)
        shared = new = _kv(full, h, cfg)
        if not step:
            # nothing past these keys and values is read again: the rest
            # of the prompt's work is its last position's, over them all
            k, v = shared
            positions = jnp.full((B, 1), T - 1)
            pages = (_blocks(k[:, :-1], T - 1), _blocks(v[:, :-1], T - 1),
                     jnp.broadcast_to(jnp.arange(T - 1), (B, T - 1)),
                     jnp.ones((B, T - 1), bool)) if T > 1 else None
            new = (k[:, -1:], v[:, -1:])
            x, h, memory = x[:, -1:], h[:, -1:], memory[:, -1:]
        a = _diff_attn(full, h, cfg, inits[half + 1], new, positions, pages)
    x = _residual(full, x, a, cfg)

    def tail(x, inp):
        layers, init = inp
        gmu, cross = layers["gmu"], layers["cross"]
        with jax.named_scope("gmu"):
            a = _gmu(gmu, _norm1(gmu, x, cfg), memory)
        x = _residual(gmu, x, a, cfg)
        with jax.named_scope("attn.cross"):
            a = _diff_attn(cross, _norm1(cross, x, cfg), cfg, init, new,
                           positions, pages)
        return _residual(cross, x, a, cfg), None

    x, _ = lax.scan(tail, x, (params["cross"], jnp.asarray(
        inits[half + 3::2], jnp.float32)))
    return x, shared, rings, (
        jnp.concatenate([state0[None], states]),
        jnp.concatenate([tail0[None], tails]))


def _head(params, x, cfg: Phi4FlashConfig):
    return layer_norm(x, params["final_ln_w"], params["final_ln_b"],
                      cfg.layer_norm_eps) @ params["embed"].T


# ------------------------------------------------------ the engine's steps


def step_prefill(params, tokens, cfg: Phi4FlashConfig,
                 mesh: Mesh | None = None):
    """``tokens`` [B, T] (equal lengths) → ``(last_logits [B, V], written,
    counts)``: ``written`` (``kvcache.Written``) the full-attention layer's
    ``(k, v)`` [B, T, Hkv / 2, 2 hd] for the caller to page into the pool,
    and what goes into the slot, each array whole and for all its layers
    at once: the window layers' last ``sliding_window`` keys and values in
    ring order, the Mamba layers' final states and convolution tails.
    ``counts`` int32 [3] for :func:`observe`: the cached positions the
    shared pages hold, those the rings hold, the layers that ran on the
    last position only."""
    from demodel_tpu.serve import kvcache

    B, T = tokens.shape
    W, c = cfg.sliding_window, cfg.ring_block
    x, full, (ring_k, ring_v), (states, tails) = _forward(
        params, params["embed"][tokens], cfg)

    def ring(a):            # [pairs, B, T, Hp, w] -> the rings, whole
        filled = kvcache.ring_fill(a.reshape(-1, *a.shape[2:]), W, c)
        return kvcache.Placed(filled.reshape(-1, B, *filled.shape[1:]))

    state = {"ring_k": ring(ring_k), "ring_v": ring(ring_v),
             "ssm_state": kvcache.Placed(states),
             "ssm_conv": kvcache.Placed(tails)}
    counts = jnp.asarray([T, min(T, W),
                          cfg.num_hidden_layers - cfg.half - 2], jnp.int32)
    return _head(params, x[:, 0], cfg), kvcache.Written([full], state), counts


def step_decode(params, tokens, cfg: Phi4FlashConfig, cache, lengths,
                mesh: Mesh | None = None):
    """One decode step over a ragged batch: ``tokens`` [B], ``lengths`` [B]
    the filled prefix of each row (0 for a pad row of the bucket), ``cache``
    the engine's pool (``kvcache.Paged``) with the batch's block table and
    slots. A window layer reads its rows' rings, a Mamba layer their states
    and tails; the full-attention layer's pages (all ``n`` table slots of
    a row, gathered once, or the tiles the rows have filled of a wide
    table, a chunk at a time by each reader) are what that layer and the
    cross-attention layers attend over, with its new pair. Returns ``(logits
    [B, V], written, counts)`` like :func:`step_prefill`: the full layer's
    new ``(k, v)`` [B, 1, Hkv / 2, 2 hd] for the caller to write at
    ``lengths``; each ring's one new position at its place (``lengths mod
    sliding_window``), the states and the tails, every array's layers in
    one slice update a row."""
    from demodel_tpu.serve import kvcache

    W, c = cfg.sliding_window, cfg.ring_block
    x, full, (ring_k, ring_v), (states, tails) = _forward(
        params, params["embed"][tokens][:, None], cfg, cache, lengths)
    state = {"ring_k": kvcache.ring_put(ring_k, lengths, W, c),
             "ring_v": kvcache.ring_put(ring_v, lengths, W, c),
             "ssm_state": kvcache.Placed(states),
             "ssm_conv": kvcache.Placed(tails)}
    counts = jnp.stack([lengths.sum(), jnp.minimum(lengths, W).sum(),
                        jnp.zeros((), lengths.dtype)]).astype(jnp.int32)
    return _head(params, x[:, 0], cfg), kvcache.Written([full], state), counts


def observe(counts, tokens: int, cfg: Phi4FlashConfig,
            platform: str = "cpu", rows: int = 0) -> dict:
    """A step's ``counts`` (on the host) and the tokens it ran → the span's
    attributes, bytes from shapes: ``shared_kv_bytes`` the filled positions
    of the full layer's pages times the layers that read them (a prefill:
    the prompt's, written once and read by as many); ``window_bytes`` the
    rings' positions read and the one written a row (a prefill: the rings
    written whole); ``state_bytes`` those and each row's Mamba states and
    tails read and written (a prefill: written); ``tail_layers`` the layers
    a prefill ran on its last position only. The counters are counted
    here. What the step's program was lowered for (``platform``) and its
    ``rows`` choose nothing in this family's programs."""
    shared, ring, tail = (int(n) for n in np.asarray(counts))
    itemsize = jnp.dtype(cfg.dtype).itemsize
    kinds = cfg.kinds
    position = 2 * cfg.num_key_value_heads * cfg.head_dim * itemsize
    readers = kinds.count("full") + kinds.count("cross")
    slot = kinds.count("mamba") * cfg.d_inner * (
        cfg.mamba_d_state * jnp.dtype(STATE_DTYPE).itemsize
        + (cfg.mamba_d_conv - 1) * itemsize)
    windows = kinds.count("window") * position
    if tail:    # a prefill: the rings and the slot written whole
        window_bytes, slots = cfg.sliding_window * windows, slot
    else:
        window_bytes, slots = (ring + tokens) * windows, 2 * tokens * slot
    attrs = {"shared_kv_bytes": shared * position * readers,
             "window_bytes": window_bytes,
             "state_bytes": window_bytes + slots}
    if tail:
        attrs["tail_layers"] = tail
    HUB.inc("gen_shared_kv_bytes_total", attrs["shared_kv_bytes"])
    HUB.inc("gen_state_bytes_total", attrs["state_bytes"])
    return attrs
