"""Qwen3-Next (``model_type`` ``qwen3_next``): three Gated-DeltaNet layers to
one gated full-attention layer, sparse experts on every layer.

For layer input ``x`` [T, D] (every norm but one *zero-centred*: ``x̂ · (1 +
w)``, statistics and the product in float32):

- ``x += mixer(norm(x))``, ``x += moe(norm(x))``; a final norm, an untied
  head. Layer ``i`` is full attention when ``(i + 1) % full_attention_interval
  == 0``, else Gated DeltaNet (GDN).
- **GDN**, ``Hk`` key heads and ``Hv`` value heads of ``dk`` / ``dv``, value
  head ``h`` served by key head ``h // (Hv / Hk)``: ``[q, k, v, z] = x
  W_qkvz``, ``[b, a] = x W_ba``; ``[q, k, v] ← silu(causal depthwise
  convolution, kernel 4, no bias)``; ``β = sigmoid(b)``, ``g = −exp(A_log) ·
  softplus(a + dt_bias)`` in float32; ``q``, ``k`` L2-normalised a head, ``q``
  scaled by ``dk^-0.5``. A value head's state ``S`` [dk, dv]: ``S ← e^{g_t}
  S``; ``S ← S + k_t ⊗ β_t (v_t − Sᵀ k_t)``; ``o_t = Sᵀ q_t``. Output
  ``(RMSNorm_w(o_t) ⊙ silu(z_t)) W_o`` (this norm over the ``dv`` of a head,
  a plain weight).
- **Gated attention**: ``q_proj`` gives a head its query and its gate; q and k
  get a zero-centred RMSNorm over the head, rotary on the first
  ``partial_rotary_factor`` of it; causal softmax (:func:`common.attend`);
  ``o_proj(attn ⊙ sigmoid(gate))``.
- **Experts**, every layer: ``p = softmax(x W_r)`` over the whole router in
  float32, top-k, renormalised; ``Σ w_e E_e(x) + sigmoid(x · w_sg) S(x)``.
  The held experts' part is :mod:`demodel_tpu.models.experts`' (``num_experts``
  counts the experts *held*, ``ep_size`` shares make the layer, this is share
  ``ep_rank``), as for EXAONE-MoE.

**The cache.** Only the full-attention layers page keys and values. A GDN
layer keeps, whatever the length, its heads' states (float32) and the last
``kernel − 1`` columns of the convolution's input: one slot of the engine's
pool a sequence (:func:`cache_spec`). A prefill runs the recurrence
chunk-wise (:func:`gated_delta_chunks`: products inside a chunk of 64, one
state carry a chunk) and leaves the final state and tail; a decode step
updates its rows' slots by one token.

The multi-token-prediction layer of the published model drafts tokens for
self-speculation and changes no next-token logit: it is not built here, and
its tensors are left where the loader found them. ``tp`` shards nothing of
this family: under a mesh everything but the held experts (``ep``) is
replicated.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from demodel_tpu.models import experts
from demodel_tpu.models.common import attend
from demodel_tpu.models.hf_loader import Weights, lay, regrouper
from demodel_tpu.models.llama import _rope

#: positions a chunk of the prefill's recurrence holds
CHUNK = 64
#: the recurrent state is carried and kept in float32
STATE_DTYPE = "float32"


@dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512          # held here
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    ep_size: int = 1                # shares that make a layer
    ep_rank: int = 0                # which of them this is
    rms_norm_eps: float = 1e-6
    #: whether a layer is full attention (else Gated DeltaNet)
    full: tuple[bool, ...] = ()
    dtype: str = "float32"

    @property
    def router_width(self) -> int:
        return self.num_experts * self.ep_size

    @property
    def conv_channels(self) -> int:
        return 2 * self.linear_num_key_heads * self.linear_key_head_dim \
            + self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def value_width(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @classmethod
    def tiny(cls, **over) -> "Qwen3NextConfig":
        """Test-sized: one period ``G G G F``, 2 key and 4 value heads of
        16, a quarter of 16 experts held, 4 a token."""
        kw = dict(vocab_size=256, hidden_size=64, num_hidden_layers=4,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                  linear_num_key_heads=2, linear_num_value_heads=4,
                  linear_key_head_dim=16, linear_value_head_dim=16,
                  moe_intermediate_size=32,
                  shared_expert_intermediate_size=32, num_experts=4,
                  num_experts_per_tok=4, ep_size=4,
                  full=(False, False, False, True))
        kw.update(over)
        return cls(**kw)

    @classmethod
    def from_hf(cls, config: dict) -> "Qwen3NextConfig":
        """From a ``config.json``; what this stack does not implement is
        refused. ``layer_types``, where written out, may be longer than
        ``num_hidden_layers`` (a checkpoint cut in depth keeps the published
        list): the first ``num_hidden_layers`` entries count."""
        for key, only in (("rope_scaling", None), ("mlp_only_layers", []),
                          ("decoder_sparse_step", 1),
                          ("use_sliding_window", False),
                          ("hidden_act", "silu")):
            if (config.get(key, only) or only) != only:
                raise ValueError(f"config field {key}={config[key]!r} is "
                                 "not supported by this stack")
        L = int(config["num_hidden_layers"])
        every = int(config.get("full_attention_interval", 4))
        kinds = list(config.get("layer_types") or [
            "full_attention" if (i + 1) % every == 0 else "linear_attention"
            for i in range(L)])[:L]
        if len(kinds) != L:
            raise ValueError(f"layer_types must cover {L} layers")
        H = int(config["num_attention_heads"])
        return cls(
            vocab_size=int(config["vocab_size"]),
            hidden_size=int(config["hidden_size"]),
            num_hidden_layers=L,
            num_attention_heads=H,
            num_key_value_heads=int(config.get("num_key_value_heads", H)),
            head_dim=int(config.get("head_dim")
                         or config["hidden_size"] // H),
            partial_rotary_factor=float(
                config.get("partial_rotary_factor", 1.0)),
            rope_theta=float(config.get("rope_theta", 1e7)),
            linear_num_key_heads=int(config["linear_num_key_heads"]),
            linear_num_value_heads=int(config["linear_num_value_heads"]),
            linear_key_head_dim=int(config["linear_key_head_dim"]),
            linear_value_head_dim=int(config["linear_value_head_dim"]),
            linear_conv_kernel_dim=int(config["linear_conv_kernel_dim"]),
            moe_intermediate_size=int(config["moe_intermediate_size"]),
            shared_expert_intermediate_size=int(
                config["shared_expert_intermediate_size"]),
            num_experts=int(config["num_experts"]),
            num_experts_per_tok=int(config["num_experts_per_tok"]),
            norm_topk_prob=bool(config.get("norm_topk_prob", True)),
            ep_size=int(config.get("ep_size", 1)),
            ep_rank=int(config.get("ep_rank", 0)),
            rms_norm_eps=float(config.get("rms_norm_eps", 1e-6)),
            full=tuple(kind == "full_attention" for kind in kinds),
            dtype=(config.get("torch_dtype") or config.get("dtype")
                   or "float32"),
        )


def cache_spec(cfg: Qwen3NextConfig):
    """What the serving engine keeps for a sequence: the full-attention
    layers page K and V; each GDN layer keeps its heads' states and the
    convolution's tail, one slot a sequence."""
    from demodel_tpu.serve.kvcache import CacheSpec

    gdn = len(cfg.full) - sum(cfg.full)
    return CacheSpec(
        sum(cfg.full), cfg.num_key_value_heads, cfg.head_dim,
        state=(("gdn_state", (gdn, cfg.linear_num_value_heads,
                              cfg.linear_key_head_dim,
                              cfg.linear_value_head_dim), STATE_DTYPE),
               ("gdn_conv", (gdn, cfg.linear_conv_kernel_dim - 1,
                             cfg.conv_channels), cfg.dtype)))


# ------------------------------------------------------------------ params


def init_params(key, cfg: Qwen3NextConfig) -> dict:
    """Seeded N(0, 1/fan_in) matrices; the zero-centred norms and ``A_log``
    zeros, ``dt_bias`` and the gated norm ones: the tree
    :func:`load_params` builds. A GDN layer's
    ``in_proj_qkvz`` holds ``q | k | v | z`` side by side (heads in order
    inside each) and ``in_proj_ba`` ``b | a``; an attention layer's
    ``q_proj`` the heads' queries, then their gates."""
    dt = jnp.dtype(cfg.dtype)
    D, hd = cfg.hidden_size, cfg.head_dim
    H, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    F, Fs, E = cfg.moe_intermediate_size, \
        cfg.shared_expert_intermediate_size, cfg.num_experts
    C, Z, Hv = cfg.conv_channels, cfg.value_width, cfg.linear_num_value_heads
    keys = iter(jax.random.split(key, 16 * cfg.num_hidden_layers + 2))

    def dense(*shape, fan_in=None):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in or shape[-2])).astype(dt)

    layers = []
    for full in cfg.full:
        layer = {
            "in_norm": jnp.zeros((D,), dt), "post_norm": jnp.zeros((D,), dt),
            "router": dense(D, cfg.router_width),
            "experts_gate_up": dense(E, D, 2 * F),
            "experts_down": dense(E, F, D),
            "shared_gate_proj": dense(D, Fs), "shared_up_proj": dense(D, Fs),
            "shared_down_proj": dense(Fs, D), "shared_gate": dense(D, 1),
        }
        if full:
            layer.update({
                "q_proj": dense(D, 2 * H * hd), "k_proj": dense(D, Hkv * hd),
                "v_proj": dense(D, Hkv * hd), "o_proj": dense(H * hd, D),
                "q_norm": jnp.zeros((hd,), dt),
                "k_norm": jnp.zeros((hd,), dt),
            })
        else:
            layer.update({
                "in_proj_qkvz": dense(D, C + Z),
                "in_proj_ba": dense(D, 2 * Hv),
                "conv": dense(cfg.linear_conv_kernel_dim, C,
                              fan_in=cfg.linear_conv_kernel_dim),
                "A_log": jnp.zeros((Hv,), dt), "dt_bias": jnp.ones((Hv,), dt),
                "gdn_norm": jnp.ones((cfg.linear_value_head_dim,), dt),
                "out_proj": dense(Z, D),
            })
        layers.append(layer)
    return {
        "embed": dense(cfg.vocab_size, D, fan_in=1),
        "layers": layers,
        "final_norm": jnp.zeros((D,), dt),
        "lm_head": dense(D, cfg.vocab_size),
    }


def param_shardings(cfg: Qwen3NextConfig, mesh: Mesh) -> dict:
    """NamedSharding tree matching :func:`init_params`: the held experts
    split over ``ep`` (when they divide), everything else replicated."""
    rep = NamedSharding(mesh, P())
    shapes = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    return experts.held_shardings(jax.tree.map(lambda _leaf: rep, shapes),
                                  cfg.num_experts, mesh)


from_hf = Qwen3NextConfig.from_hf
#: served through its step functions only
forward = None


def load_params(weights: dict, cfg: Qwen3NextConfig, mesh=None) -> dict:
    """The tree of :func:`init_params` from a checkpoint that
    holds one share of the experts under their global indices. Hugging
    Face lays ``in_proj_qkvz`` and ``in_proj_ba`` out a key head at a time
    (``q | k | v | z`` of one head, then the next) and ``q_proj`` an
    attention head at a time (its query, then its gate): they are regrouped
    so that each part is one run of columns. Tensors of the
    multi-token-prediction layer stay in ``weights``."""
    w = Weights(weights)
    sh = param_shardings(cfg, mesh) if mesh is not None else {}
    Hk, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
    r = cfg.linear_num_value_heads // Hk
    rdv = r * cfg.linear_value_head_dim
    layers = []
    for i, full in enumerate(cfg.full):
        pre = f"layers.{i}."
        lsh = sh["layers"][i] if sh else {}

        def lin(name, leaf):
            return w.get(pre + name, transpose=True, sharding=lsh.get(leaf))

        def vec(name, leaf):
            return w.get(pre + name, sharding=lsh.get(leaf))

        def regrouped(name, leaf, groups, widths):
            return regrouper(groups, widths, lsh.get(leaf))(
                w.get(pre + name))

        def held(projs, leaf):
            return experts.stack_experts(w, pre, projs, cfg, lsh.get(leaf))

        layer = {
            "in_norm": vec("input_layernorm.weight", "in_norm"),
            "post_norm": vec("post_attention_layernorm.weight", "post_norm"),
            "router": lin("mlp.gate.weight", "router"),
            "experts_gate_up": held(("gate", "up"), "experts_gate_up"),
            "experts_down": held(("down",), "experts_down"),
            "shared_gate_proj": lin("mlp.shared_expert.gate_proj.weight",
                                    "shared_gate_proj"),
            "shared_up_proj": lin("mlp.shared_expert.up_proj.weight",
                                  "shared_up_proj"),
            "shared_down_proj": lin("mlp.shared_expert.down_proj.weight",
                                    "shared_down_proj"),
            "shared_gate": lin("mlp.shared_expert_gate.weight",
                               "shared_gate"),
        }
        if full:
            layer.update({
                "q_proj": regrouped("self_attn.q_proj.weight", "q_proj",
                                    cfg.num_attention_heads,
                                    (cfg.head_dim, cfg.head_dim)),
                "k_proj": lin("self_attn.k_proj.weight", "k_proj"),
                "v_proj": lin("self_attn.v_proj.weight", "v_proj"),
                "o_proj": lin("self_attn.o_proj.weight", "o_proj"),
                "q_norm": vec("self_attn.q_norm.weight", "q_norm"),
                "k_norm": vec("self_attn.k_norm.weight", "k_norm"),
            })
        else:
            conv = w.get(pre + "linear_attn.conv1d.weight")     # [C, 1, K]
            layer.update({
                "in_proj_qkvz": regrouped(
                    "linear_attn.in_proj_qkvz.weight", "in_proj_qkvz", Hk,
                    (dk, dk, rdv, rdv)),
                "in_proj_ba": regrouped("linear_attn.in_proj_ba.weight",
                                        "in_proj_ba", Hk, (r, r)),
                "conv": lay(conv.reshape(conv.shape[0], conv.shape[-1]),
                             True, lsh.get("conv")),
                "A_log": vec("linear_attn.A_log", "A_log"),
                "dt_bias": vec("linear_attn.dt_bias", "dt_bias"),
                "gdn_norm": vec("linear_attn.norm.weight", "gdn_norm"),
                "out_proj": lin("linear_attn.out_proj.weight", "out_proj"),
            })
        layers.append(layer)
    return {
        "embed": w.get("embed_tokens.weight", sharding=sh.get("embed")),
        "layers": layers,
        "final_norm": w.get("norm.weight", sharding=sh.get("final_norm")),
        "lm_head": w.get("lm_head.weight", transpose=True,
                         sharding=sh.get("lm_head")),
    }


# ------------------------------------------------------------------ norms


def _norm(x, weight, eps: float):
    """Zero-centred RMSNorm: ``x̂ · (1 + w)``, all in float32."""
    xf = x.astype(jnp.float32)
    scale = lax.rsqrt((xf * xf).mean(axis=-1, keepdims=True) + eps)
    return (xf * scale * (1.0 + weight.astype(jnp.float32))).astype(x.dtype)


def _l2(x):
    return x * lax.rsqrt((x * x).sum(axis=-1, keepdims=True) + 1e-6)


# ------------------------------------------------- the gated delta rule


def gated_delta_chunks(q, k, v, g, beta, state=None, chunk: int = CHUNK):
    """The recurrence ``S ← e^{g_t} S; S ← S + k_t ⊗ β_t (v_t − Sᵀ k_t); o_t
    = Sᵀ q_t`` over ``T`` positions, chunk-wise. ``q``, ``k`` [B, T, Hk, dk]
    (normalised, ``q`` scaled), ``v`` [B, T, Hk, r, dv] (``r`` value heads a
    key head), ``g`` (≤ 0) and ``beta`` [B, T, Hk, r], all float32;
    ``state`` [B, Hk, r, dk, dv] or None for zeros. Returns ``(o [B, T, Hk,
    r, dv], final state)``.

    Inside a chunk of ``C`` positions with ``G`` the running sum of ``g``:
    ``A[i, j] = β_i (k_i · k_j) e^{G_i − G_j}`` for ``j < i``; ``(I + A)^-1``
    (``A`` is nilpotent, so the inverse is the product of ``I + (−A)^(2^m)``)
    turns ``β v`` and ``β k e^G`` into what the chunk adds given the state it
    starts from; one carry of the state a chunk (a ``lax.scan``). Positions
    past ``T`` up to a whole chunk ride along with ``β = 0``, ``g = 0`` and
    zero ``k``: they leave the state as it is."""
    B, T, Hk, dk = q.shape
    r, dv = v.shape[3], v.shape[4]
    C = chunk
    n = -(-T // C)
    pad = n * C - T

    # positions innermost but for the head width: q, k [n, B, Hk, C, dk],
    # v [n, B, Hk, r, C, dv], g, beta [n, B, Hk, r, C]
    def split(a, trailing: int):
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape(B, n, C, *a.shape[2:])
        a = jnp.moveaxis(a, 1, 0)                   # [n, B, C, ...]
        return jnp.moveaxis(a, 2, a.ndim - 1 - trailing)

    q, k = split(q, 1), split(k, 1)
    v, g, beta = split(v, 1), split(g, 0), split(beta, 0)

    G = jnp.cumsum(g, axis=-1)                              # [n,B,Hk,r,C]
    lower = jnp.tril(jnp.ones((C, C), bool))
    decay = jnp.exp(jnp.where(lower, G[..., :, None] - G[..., None, :],
                              -jnp.inf))                    # i >= j
    kk = jnp.einsum("nbhid,nbhjd->nbhij", k, k)[:, :, :, None]
    N = -jnp.where(jnp.tril(lower, -1), beta[..., :, None] * kk * decay, 0.0)
    inv = jnp.eye(C, dtype=N.dtype) + N
    power = N
    for _ in range(max(0, (C - 1).bit_length() - 1)):
        power = power @ power
        inv = inv + inv @ power
    kb = k[:, :, :, None] * (beta * jnp.exp(G))[..., None]  # [n,B,Hk,r,C,dk]
    u = inv @ (v * beta[..., None])                         # [.., C, dv]
    w = inv @ kb                                            # [.., C, dk]
    qk = jnp.einsum("nbhid,nbhjd->nbhij", q, k)[:, :, :, None] * decay
    qd = q[:, :, :, None] * jnp.exp(G)[..., None]           # [n,B,Hk,r,C,dk]
    last = G[..., -1:]                                      # [n,B,Hk,r,1]
    kd = k[:, :, :, None] * jnp.exp(last - G)[..., None]

    def carry(S, c):
        u_c, w_c, qk_c, qd_c, kd_c, last_c = c
        v_new = u_c - w_c @ S                               # [B,Hk,r,C,dv]
        o = qd_c @ S + qk_c @ v_new
        S = S * jnp.exp(last_c)[..., None] \
            + jnp.swapaxes(kd_c, -1, -2) @ v_new
        return S, o

    if state is None:
        state = jnp.zeros((B, Hk, r, dk, dv), jnp.float32)
    state, o = lax.scan(carry, state, (u, w, qk, qd, kd, last))
    # [n, B, Hk, r, C, dv] -> [B, T, Hk, r, dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 4, 2), 0, 1).reshape(
        B, n * C, Hk, r, dv)
    return o[:, :T], state


def gated_delta_step(q, k, v, g, beta, state):
    """One position of the recurrence, in place of a chunk: ``q``, ``k`` [B,
    Hk, dk], ``v`` [B, Hk, r, dv], ``g``, ``beta`` [B, Hk, r], ``state`` [B,
    Hk, r, dk, dv] → ``(o [B, Hk, r, dv], state)``. Sums over ``dk`` as
    float32 multiply-adds: a row's state is read once and written once."""
    kx = k[:, :, None, :, None]
    state = state * jnp.exp(g)[..., None, None]
    delta = (v - (state * kx).sum(axis=-2)) * beta[..., None]
    state = state + kx * delta[..., None, :]
    return (state * q[:, :, None, :, None]).sum(axis=-2), state


# ------------------------------------------------------------ the mixers


def _gdn(layer, x, cfg: Qwen3NextConfig, past=None):
    """``x`` [B, T, D] → ``(out [B, T, D], (state [B, Hv, dk, dv], tail [B,
    K − 1, C]))``. ``past`` None: a prompt from its start, the recurrence
    chunk-wise. ``past`` ``(state, tail)``: one new position a row (T = 1)
    on the slot's state and the convolution's last inputs."""
    B, T, _D = x.shape
    Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    r, K, C = Hv // Hk, cfg.linear_conv_kernel_dim, cfg.conv_channels
    qkvz = x @ layer["in_proj_qkvz"]
    mixed, z = qkvz[..., :C], qkvz[..., C:]
    ba = (x @ layer["in_proj_ba"]).astype(jnp.float32)
    beta = jax.nn.sigmoid(ba[..., :Hv]).reshape(B, T, Hk, r)
    g = (-jnp.exp(layer["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[..., Hv:] + layer["dt_bias"].astype(jnp.float32))).reshape(
        B, T, Hk, r)
    with jax.named_scope("gdn.conv"):
        before = jnp.zeros((B, K - 1, C), mixed.dtype) if past is None \
            else past[1]
        window = jnp.concatenate([before, mixed], axis=1)   # [B, K-1+T, C]
        taps = layer["conv"].astype(jnp.float32)
        conv = sum(window[:, i:i + T].astype(jnp.float32) * taps[i]
                   for i in range(K))
        act = jax.nn.silu(conv)
        tail = window[:, T:]                                # the last K - 1
    q = _l2(act[..., :Hk * dk].reshape(B, T, Hk, dk)) * dk ** -0.5
    k = _l2(act[..., Hk * dk:2 * Hk * dk].reshape(B, T, Hk, dk))
    v = act[..., 2 * Hk * dk:].reshape(B, T, Hk, r, dv)
    if past is None:
        with jax.named_scope("gdn.scan"):
            o, state = gated_delta_chunks(q, k, v, g, beta)
    else:
        with jax.named_scope("gdn.step"):
            o, state = gated_delta_step(
                q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                past[0].reshape(B, Hk, r, dk, dv))
            o = o[:, None]
    # the gated norm: over a head's dv, a plain weight, times silu(z)
    o = o * lax.rsqrt((o * o).mean(axis=-1, keepdims=True)
                      + cfg.rms_norm_eps)
    o = (o * layer["gdn_norm"].astype(jnp.float32)).astype(x.dtype)
    o = o.reshape(B, T, Hv * dv).astype(jnp.float32) \
        * jax.nn.silu(z.astype(jnp.float32))
    return o.astype(x.dtype) @ layer["out_proj"], \
        (state.reshape(B, Hv, dk, dv), tail)


def _attn(layer, x, cfg: Qwen3NextConfig, positions, past=None):
    """``x`` [B, T, D] at ``positions`` [B, T] → ``(out, (k, v))`` with the
    new keys and values [B, T, Hkv, hd]: a head's query and gate from one
    projection, zero-centred norms on q and k, rotary on the head's first
    part, :func:`common.attend`, the sigmoid gate before ``o_proj``."""
    B, T, _D = x.shape
    hd, H, Hkv = cfg.head_dim, cfg.num_attention_heads, \
        cfg.num_key_value_heads
    qg = (x @ layer["q_proj"]).reshape(B, T, 2, H, hd)
    q = _norm(qg[:, :, 0], layer["q_norm"], cfg.rms_norm_eps)
    k = _norm((x @ layer["k_proj"]).reshape(B, T, Hkv, hd),
              layer["k_norm"], cfg.rms_norm_eps)
    v = (x @ layer["v_proj"]).reshape(B, T, Hkv, hd)
    rot = int(hd * cfg.partial_rotary_factor)

    def rotated(a):
        return jnp.concatenate(
            [_rope(a[..., :rot], positions, cfg.rope_theta), a[..., rot:]],
            axis=-1)

    k = rotated(k)
    out = attend(rotated(q), k, v, positions, past=past)
    gate = jax.nn.sigmoid(qg[:, :, 1].astype(jnp.float32)).reshape(
        B, T, H * hd)
    return (out * gate.astype(out.dtype)) @ layer["o_proj"], (k, v)


def _moe(layer, x, live, cfg: Qwen3NextConfig, mesh: Mesh | None):
    """``x`` [N, D] → ``(moe(x) [N, D], tokens per held expert [E])``: this
    family's scoring (softmax over the whole router, renormalised) and its
    gated shared expert around :func:`experts.routed`."""
    with jax.named_scope("moe.route"):
        p = jax.nn.softmax(jnp.dot(
            x.astype(jnp.float32), layer["router"].astype(jnp.float32),
            precision=lax.Precision.HIGHEST), axis=-1)
        weights, chosen = lax.top_k(p, cfg.num_experts_per_tok)
        if cfg.norm_topk_prob:
            weights = weights / weights.sum(axis=1, keepdims=True)
        y, tokens = experts.routed(
            x, live, chosen, weights, layer["experts_gate_up"],
            layer["experts_down"], cfg.ep_rank * cfg.num_experts, mesh)
    shared = experts.swiglu(x, layer["shared_gate_proj"],
                            layer["shared_up_proj"],
                            layer["shared_down_proj"])
    gate = jax.nn.sigmoid((x @ layer["shared_gate"]).astype(jnp.float32))
    return y.astype(x.dtype) + (shared * gate).astype(x.dtype), tokens


def _forward(params, tokens, cfg, positions, live, pasts, mesh):
    """Every layer over ``tokens`` [B, T] → ``(x, new kv of the attention
    layers, (states, tails) of the GDN layers, expert tokens [L, E])``;
    ``pasts`` is a layer's ``past`` or None."""
    B, T = tokens.shape
    x = params["embed"][tokens]
    new_kv, states, tails, counts = [], [], [], []
    for layer, full, past in zip(params["layers"], cfg.full, pasts):
        h = _norm(x, layer["in_norm"], cfg.rms_norm_eps)
        if full:
            with jax.named_scope("attn.gated"):
                a, kv = _attn(layer, h, cfg, positions, past)
            new_kv.append(kv)
        else:
            with jax.named_scope("gdn"):
                a, (state, tail) = _gdn(layer, h, cfg, past)
            states.append(state)
            tails.append(tail)
        x = x + a
        h = _norm(x, layer["post_norm"], cfg.rms_norm_eps)
        with jax.named_scope("moe"):
            m, n = _moe(layer, h.reshape(B * T, -1), live.reshape(B * T),
                        cfg, mesh)
        counts.append(n)
        x = x + m.reshape(B, T, -1)
    return x, new_kv, {"gdn_state": states, "gdn_conv": tails}, \
        jnp.stack(counts)


def _head(params, x, cfg):
    return _norm(x, params["final_norm"], cfg.rms_norm_eps) \
        @ params["lm_head"]


# ------------------------------------------------------ the engine's steps


def step_prefill(params, tokens, cfg: Qwen3NextConfig,
                 mesh: Mesh | None = None):
    """``tokens`` [B, T] (equal lengths) → ``(last_logits [B, V], written,
    expert_tokens)``: ``written`` (``kvcache.Written``) the attention
    layers' ``(k, v)``, each [B, T, Hkv, hd], for the caller to page into
    the pool, and what each GDN layer leaves in the slot (its final state
    and the convolution's tail); ``expert_tokens`` [layers, held experts]
    int32, the assignments each held expert got."""
    from demodel_tpu.serve.kvcache import Written

    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    x, kv, state, counts = _forward(
        params, tokens, cfg, positions, jnp.ones((B, T), bool),
        [None] * cfg.num_hidden_layers, mesh)
    return _head(params, x[:, -1], cfg), Written(kv, state), counts


def step_decode(params, tokens, cfg: Qwen3NextConfig, cache, lengths,
                mesh: Mesh | None = None):
    """One decode step over a ragged batch: ``tokens`` [B], ``lengths`` [B]
    the filled prefix of each row (0 for a pad row of the bucket, which
    then chooses no expert), ``cache`` the engine's pool (``kvcache.Paged``)
    with the batch's block table and slots. An attention layer reads all
    ``n`` table slots of a row (of a wide table the tiles its rows have
    filled, ``Paged.past``); a GDN layer its rows' slots of the state
    arrays. Returns ``(logits [B, V], written, expert_tokens)`` like
    :func:`step_prefill`, the new keys and values each [B, 1, Hkv, hd] for
    the caller to write at ``lengths``, the states and tails for it to
    write back into the rows' slots."""
    from demodel_tpu.serve.kvcache import Written

    filled = cache.filled(lengths)
    pasts, paged, kept = [], 0, 0
    for full in cfg.full:
        if full:
            pasts.append(cache.past(paged, filled))
            paged += 1
        else:
            pasts.append((cache.read_state("gdn_state", kept),
                          cache.read_state("gdn_conv", kept)))
            kept += 1
    x, kv, state, counts = _forward(
        params, tokens[:, None], cfg, lengths[:, None],
        (lengths > 0)[:, None], pasts, mesh)
    return _head(params, x[:, 0], cfg), Written(kv, state), counts


def observe(expert_tokens, tokens: int, cfg: Qwen3NextConfig,
            platform: str = "cpu", rows: int = 0) -> dict:
    """A step's ``expert_tokens`` (on the host) and the tokens it ran (of
    the program's ``rows``) → the span's attributes; the counters are
    counted here."""
    return experts.observe(
        expert_tokens,
        tokens * cfg.num_experts_per_tok * cfg.num_hidden_layers,
        platform=platform, call_rows=rows * cfg.num_experts_per_tok)
