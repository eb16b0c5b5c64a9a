"""LongCat-Flash (``model_type`` ``longcat_flash``, the language model of
LongCat-Flash-Omni): two latent-attention sublayers a layer, a
shortcut-connected expert layer beside the dense path, identity experts
that cost nothing.

The layer, for a row ``x`` of the residual (every norm an RMSNorm with a
learned weight; ``N_i``, ``P_i`` the input and post-attention norms of
sublayer ``i``)::

    a = x + Attn_0(N_0(x))          m = MoE(P_0(a))
    b = a + FFN_0(P_0(a))
    c = b + Attn_1(N_1(b))
    y = c + FFN_1(P_1(c)) + m

- **Two latent attentions**, each with its own weights, as
  :mod:`demodel_tpu.models.latent` tells them: plain rotary frequencies
  ``theta ** (-2j / rope)``, scores scaled by ``(nope + rope) ** -0.5``.
  ``mla_scale_q_lora`` multiplies the normalised ``c_q`` by ``(hidden /
  q_lora_rank) ** 0.5`` and ``mla_scale_kv_lora`` the normalised ``c_kv`` by
  ``(hidden / kv_lora_rank) ** 0.5``: each the output of a norm ahead of a
  linear map without bias, so the loader folds it into that norm's weight
  (:func:`fold`) and a step pays nothing for it. The cached vector is then
  the scaled ``[c_kv | k_r]``.
- **Two dense SwiGLU blocks** of ``ffn_hidden_size``.
- **One expert layer** that reads what the first dense block reads and is
  added an attention and a dense block later (on many chips that hides its
  exchange; on one it is an order the compiler may choose). ``p =
  softmax(W_r u)`` in float32 over the whole router, whose first
  ``n_routed_experts * ep_size`` outputs name routed experts (SwiGLUs of
  ``expert_ffn_hidden_size``) and whose last ``zero_expert_num`` name
  **identity experts**; the ``moe_topk`` largest of ``p + bias`` are chosen
  (the bias enters the choice only), ``w_e = routed_scaling_factor * p_e``
  for the chosen, not renormalised; ``MoE(u) = sum(chosen, routed) w_e
  E_e(u) + u * sum(chosen, identity) w_e``. The identity part needs no
  weight and no exchange: it is computed whole where the token lives
  (:func:`_moe`, scope ``moe.zero``). A token takes 0 to ``moe_topk``
  routed experts.

**The cache** (:func:`cache_spec`): every *sublayer* pages one vector a
position, so the pool has ``2 * num_layers`` paging layers, sublayer ``i``
of layer ``l`` at ``2 l + i``: the model's layer count is not the pool's.

**One chip's share of an expert-parallel replica**, as
:mod:`demodel_tpu.models.exaone_moe` tells it: ``n_routed_experts`` counts
the experts held, ``first = ep_rank * n_routed_experts``, and the layer
computes the held experts' part (:mod:`demodel_tpu.models.experts`); the
identity part is every chip's own.

**The layers are unrolled**, as every family's but Phi-4-flash's are: a
scan over stacked weights was tried and the chip's compiler copies a
layer's experts out of the stack for the grouped products (0.8 + 0.4 GB a
layer a step, seen in the step compiled for the chip), while the unrolled
step is made ready in about the time of the scanned one.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from demodel_tpu.models import experts, latent
from demodel_tpu.models.common import refuse_unsupported, rms_norm
from demodel_tpu.models.hf_loader import Weights, fold, zeros
from demodel_tpu.utils.metrics import HUB, labeled

HUB.inc(labeled("gen_moe_assignments_total", held="zero"), 0)


@dataclass(frozen=True)
class LongcatFlashConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    n_routed_experts: int = 512     # held here
    zero_expert_num: int = 256
    moe_topk: int = 12
    ep_size: int = 1                # shares that make a layer
    ep_rank: int = 0                # which of them this is
    routed_scaling_factor: float = 6.0
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    dtype: str = "float32"

    @property
    def routed_experts(self) -> int:
        """The routed experts of the whole layer: the router's outputs
        from here on name identity experts."""
        return self.n_routed_experts * self.ep_size

    @property
    def router_width(self) -> int:
        return self.routed_experts + self.zero_expert_num

    @property
    def num_experts(self) -> int:
        """The experts held, under the name the loader that stacks them
        (``experts.stack_experts``) shares with the other families."""
        return self.n_routed_experts

    @property
    def moe_intermediate_size(self) -> int:
        """:attr:`expert_ffn_hidden_size`, under that loader's name."""
        return self.expert_ffn_hidden_size

    @property
    def latent(self) -> latent.Geometry:
        """This family's latent attention: plain rotary, scores scaled by
        ``(nope + rope) ** -0.5``."""
        r = self.qk_rope_head_dim
        inv = 1.0 / self.rope_theta ** (np.arange(0, r, 2,
                                                  dtype=np.float32) / r)
        return latent.Geometry(
            self.num_attention_heads, self.kv_lora_rank, r,
            tuple(map(float, inv.astype(np.float32))), 1.0,
            (self.qk_nope_head_dim + r) ** -0.5, self.rms_norm_eps)

    @property
    def latent_scales(self) -> tuple[float, float]:
        """``(on the normalised c_q, on the normalised c_kv)``."""
        D = self.hidden_size
        return ((D / self.q_lora_rank) ** 0.5 if self.mla_scale_q_lora
                else 1.0,
                (D / self.kv_lora_rank) ** 0.5 if self.mla_scale_kv_lora
                else 1.0)

    @classmethod
    def tiny(cls, **over) -> "LongcatFlashConfig":
        """Test-sized: two double layers, 4 heads of 16 | 8 and values of
        16 over a latent of 32 | 8, a router of 16 routed and 8 identity
        experts of which 6 are chosen, a quarter of the routed ones
        held."""
        kw = dict(vocab_size=256, hidden_size=64, ffn_hidden_size=128,
                  expert_ffn_hidden_size=32, num_layers=2,
                  num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
                  qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                  n_routed_experts=4, zero_expert_num=8, moe_topk=6,
                  ep_size=4, rope_theta=10000.0)
        kw.update(over)
        return cls(**kw)

    @classmethod
    def from_hf(cls, config: dict) -> "LongcatFlashConfig":
        """From a ``config.json`` under the published keys; a key whose
        value this module does not implement is refused by name."""
        refuse_unsupported(
            config, fields=("attention_bias", "sliding_window",
                            "rope_scaling"),
            only={"attention_method": "MLA", "zero_expert_type": "identity",
                  "hidden_act": "silu"})
        if not config.get("q_lora_rank"):       # uncompressed queries
            raise ValueError(f"config field q_lora_rank="
                             f"{config.get('q_lora_rank')!r} is not "
                             "supported by this stack")
        return cls(
            vocab_size=int(config["vocab_size"]),
            hidden_size=int(config["hidden_size"]),
            ffn_hidden_size=int(config["ffn_hidden_size"]),
            expert_ffn_hidden_size=int(config["expert_ffn_hidden_size"]),
            num_layers=int(config["num_layers"]),
            num_attention_heads=int(config["num_attention_heads"]),
            q_lora_rank=int(config["q_lora_rank"]),
            kv_lora_rank=int(config["kv_lora_rank"]),
            qk_nope_head_dim=int(config["qk_nope_head_dim"]),
            qk_rope_head_dim=int(config["qk_rope_head_dim"]),
            v_head_dim=int(config["v_head_dim"]),
            mla_scale_q_lora=bool(config.get("mla_scale_q_lora", False)),
            mla_scale_kv_lora=bool(config.get("mla_scale_kv_lora", False)),
            n_routed_experts=int(config["n_routed_experts"]),
            zero_expert_num=int(config.get("zero_expert_num", 0)),
            moe_topk=int(config["moe_topk"]),
            ep_size=int(config.get("ep_size", 1)),
            ep_rank=int(config.get("ep_rank", 0)),
            routed_scaling_factor=float(
                config.get("routed_scaling_factor", 1.0)),
            rms_norm_eps=float(config.get("rms_norm_eps", 1e-5)),
            rope_theta=float(config.get("rope_theta", 10000.0)),
            dtype=(config.get("torch_dtype") or config.get("dtype")
                   or "float32"),
        )


# ------------------------------------------------------------------ params


def init_params(key, cfg: LongcatFlashConfig) -> dict:
    """Seeded N(0, 1/fan_in) matrices, norms of ones (the two latent norms
    with their scales folded in), a zero selection bias: the tree
    :func:`load_params` builds."""
    dt = jnp.dtype(cfg.dtype)
    D, I = cfg.hidden_size, cfg.ffn_hidden_size
    C, Q = cfg.kv_lora_rank, cfg.q_lora_rank
    F, E = cfg.expert_ffn_hidden_size, cfg.n_routed_experts
    keys = iter(jax.random.split(key, 24 * cfg.num_layers + 2))

    def dense(*shape, fan_in=None):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in or shape[-2])).astype(dt)

    s_q, s_kv = cfg.latent_scales

    def sublayer() -> dict:
        return {
            "attn": {
                **latent.matrices(dense, D, Q, cfg.latent,
                                  cfg.qk_nope_head_dim, cfg.v_head_dim),
                "q_a_norm": fold(jnp.ones((Q,), dt), s_q),
                "kv_a_norm": fold(jnp.ones((C,), dt), s_kv)},
            "attn_norm": jnp.ones((D,), dt), "mlp_norm": jnp.ones((D,), dt),
            "gate_proj": dense(D, I), "up_proj": dense(D, I),
            "down_proj": dense(I, D),
        }

    def layer() -> dict:
        return {
            "sub": [sublayer(), sublayer()],
            "router": dense(D, cfg.router_width),
            "router_bias": jnp.zeros((cfg.router_width,), jnp.float32),
            "experts_gate_up": dense(E, D, 2 * F),
            "experts_down": dense(E, F, D),
        }

    return {
        "embed": dense(cfg.vocab_size, D, fan_in=1),
        "layers": [layer() for _ in range(cfg.num_layers)],
        "final_norm": jnp.ones((D,), dt),
        "lm_head": dense(D, cfg.vocab_size),
    }


def param_shardings(cfg: LongcatFlashConfig, mesh: Mesh) -> dict:
    """NamedSharding tree matching :func:`init_params`: the held experts
    split over ``ep`` (when they divide), everything else replicated, as
    in the deployment (data-parallel attention over the latent cache, the
    dense blocks and the router on every chip)."""
    rep = NamedSharding(mesh, P())
    shapes = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    return experts.held_shardings(jax.tree.map(lambda _leaf: rep, shapes),
                                  cfg.n_routed_experts, mesh)


from_hf = LongcatFlashConfig.from_hf
#: served through its step functions only
forward = None


def load_params(weights: dict, cfg: LongcatFlashConfig, mesh=None) -> dict:
    """The tree of :func:`init_params` from a checkpoint of
    the LongCat-Flash style of names (a layer's two sublayers under
    ``self_attn.<i>``, ``input_layernorm.<i>``,
    ``post_attention_layernorm.<i>`` and ``mlps.<i>``, its expert layer
    under ``mlp``), holding one share of the routed experts under their
    global indices. Each attention is :func:`latent.load_attention`'s, with the
    two scales of the latent norms' outputs (``mla_scale_q_lora``,
    ``mla_scale_kv_lora``) folded into ``q_a_layernorm`` and
    ``kv_a_layernorm``. The router
    keeps its whole width, identity experts included; a selection bias is
    taken where the checkpoint has one and is zero where not."""
    w = Weights(weights)
    sh = param_shardings(cfg, mesh) if mesh is not None else {}
    layers = []
    for li in range(cfg.num_layers):
        pre = f"layers.{li}."
        lsh = sh["layers"][li] if sh else {}

        def sublayer(i: int) -> dict:
            ssh = lsh["sub"][i] if lsh else {}
            return {
                "attn": latent.load_attention(
                    w, f"{pre}self_attn.{i}.", cfg.num_attention_heads,
                    cfg.qk_nope_head_dim, ssh.get("attn", {}),
                    cfg.latent_scales),
                "attn_norm": w.get(f"{pre}input_layernorm.{i}.weight",
                                   sharding=ssh.get("attn_norm")),
                "mlp_norm": w.get(
                    f"{pre}post_attention_layernorm.{i}.weight",
                    sharding=ssh.get("mlp_norm")),
                **{f"{x}_proj": w.get(f"{pre}mlps.{i}.{x}_proj.weight",
                                      transpose=True,
                                      sharding=ssh.get(f"{x}_proj"))
                   for x in ("gate", "up", "down")},
            }

        bias = pre + "mlp.router.e_score_correction_bias"
        layers.append({
            "sub": [sublayer(0), sublayer(1)],
            "router": w.get(pre + "mlp.router.classifier.weight",
                            transpose=True, sharding=lsh.get("router")),
            "router_bias": w.get(
                bias, sharding=lsh.get("router_bias")).astype(jnp.float32)
            if w.has(bias) else zeros((cfg.router_width,), "float32",
                                       lsh.get("router_bias"))(),
            "experts_gate_up": experts.stack_experts(
                w, pre, ("gate", "up"), cfg, lsh.get("experts_gate_up")),
            "experts_down": experts.stack_experts(
                w, pre, ("down",), cfg, lsh.get("experts_down")),
        })
    return {
        "embed": w.get("embed_tokens.weight", sharding=sh.get("embed")),
        "layers": layers,
        "final_norm": w.get("norm.weight", sharding=sh.get("final_norm")),
        "lm_head": w.get("lm_head.weight", transpose=True,
                         sharding=sh.get("lm_head")),
    }


# ---------------------------------------------------------- expert layer


def route(logits, bias, cfg: LongcatFlashConfig):
    """The router's ``logits`` [N, router] (float32) → ``(chosen [N, K]
    ids over the whole router, weights [N, K])``: a softmax over routed
    and identity experts alike; the K largest of ``p + bias`` (a tie to
    the lower index); the weights the chosen ``p`` times the scaling
    factor, not renormalised."""
    p = jax.nn.softmax(logits, axis=-1)
    _best, chosen = lax.top_k(p + bias, cfg.moe_topk)
    return chosen, jnp.take_along_axis(p, chosen, axis=1) \
        * cfg.routed_scaling_factor


def _moe(layer, x, live, cfg: LongcatFlashConfig, mesh: Mesh | None):
    """``x`` [N, D] → ``(MoE(x) [N, D], tokens per held expert [E],
    identity assignments of live rows [])``: this family's scoring and
    choice around :func:`experts.routed`, and the identity experts' part,
    ``x`` times the sum of its chosen identity weights."""
    with jax.named_scope("moe.route"):
        chosen, weights = route(jnp.dot(
            x.astype(jnp.float32), layer["router"].astype(jnp.float32),
            precision=lax.Precision.HIGHEST), layer["router_bias"], cfg)
        y, tokens = experts.routed(
            x, live, chosen, weights, layer["experts_gate_up"],
            layer["experts_down"], cfg.ep_rank * cfg.n_routed_experts, mesh)
    with jax.named_scope("moe.zero"):
        free = chosen >= cfg.routed_experts
        y = y + x.astype(jnp.float32) * jnp.where(
            free, weights, 0.0).sum(axis=1, keepdims=True)
        zeros = (free & live[:, None]).sum(dtype=jnp.int32)
    return y.astype(x.dtype), tokens, zeros


def _layer(layer, x, cfg: LongcatFlashConfig, positions, live, pasts, mesh):
    """One double layer over ``x`` [B, T, D] → ``(y, the two sublayers'
    latents, tokens per held expert, identity assignments)``; ``pasts`` the
    two sublayers' pages (a step) or None each (a prompt)."""
    B, T, D = x.shape
    eps = cfg.rms_norm_eps

    def attn(i, x):
        sub = layer["sub"][i]
        return latent.attention(
            sub["attn"], rms_norm(x, sub["attn_norm"], eps), cfg.latent,
            positions, pasts[i])

    def ffn(i, u):
        sub = layer["sub"][i]
        with jax.named_scope("ffn.dense"):
            return experts.swiglu(u, sub["gate_proj"], sub["up_proj"],
                                  sub["down_proj"])

    a, new0 = attn(0, x)
    x = x + a
    u = rms_norm(x, layer["sub"][0]["mlp_norm"], eps)
    m, tokens, zeros = _moe(layer, u.reshape(B * T, D), live.reshape(B * T),
                            cfg, mesh)
    x = x + ffn(0, u)
    a, new1 = attn(1, x)
    x = x + a
    # the shortcut: the expert layer's result enters an attention and a
    # dense block after it was asked for
    x = x + ffn(1, rms_norm(x, layer["sub"][1]["mlp_norm"], eps)) \
        + m.reshape(B, T, D)
    return x, [new0, new1], tokens, zeros


def _forward(params, tokens, cfg, positions, live, past, mesh):
    """Every layer over ``tokens`` [B, T] → ``(x, each sublayer's latent
    (2 a layer, in the pool's order), expert tokens [layers, E], identity
    assignments [layers])``; ``past(i)`` is paging layer ``i``'s pages (a
    step, absorbed) or None (a prompt, expanded)."""

    x = params["embed"][tokens]
    latents, counts, zeros = [], [], []
    for li, layer in enumerate(params["layers"]):
        x, news, tokens_held, free = _layer(
            layer, x, cfg, positions, live,
            (past(2 * li), past(2 * li + 1)), mesh)
        latents += news
        counts.append(tokens_held)
        zeros.append(free)
    return x, latents, jnp.stack(counts), jnp.stack(zeros)


def _head(params, x, cfg):
    return rms_norm(x, params["final_norm"], cfg.rms_norm_eps) \
        @ params["lm_head"]


# ------------------------------------------------------ the engine's steps


def cache_spec(cfg: LongcatFlashConfig):
    """What the serving engine keeps for a sequence: every sublayer pages
    one vector a position, ``[c_kv | k_rope]`` and zeros up to the lanes,
    whose first ``kv_lora_rank`` columns are also its values: a page of one
    array with two paging layers a layer of the model, each read whole by
    its own attention."""
    from demodel_tpu.serve.kvcache import CacheSpec

    geo = cfg.latent
    return CacheSpec(2 * cfg.num_layers, 1, geo.page_dim, values=geo.rank)


def step_prefill(params, tokens, cfg: LongcatFlashConfig,
                 mesh: Mesh | None = None):
    """``tokens`` [B, T] (equal lengths) → ``(last_logits [B, V], latents,
    expert_tokens, zero_tokens, positions)``: ``latents`` each sublayer's
    [B, T, 1, 640] for the caller to page into the pool; ``expert_tokens``
    [layers, held experts] and ``zero_tokens`` [layers] int32;
    ``positions`` the latent positions the step wrote."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    x, latents, counts, zeros = _forward(
        params, tokens, cfg, positions, jnp.ones((B, T), bool),
        lambda _i: None, mesh)
    return _head(params, x[:, -1], cfg), latents, counts, zeros, \
        jnp.int32(B * T)


def step_decode(params, tokens, cfg: LongcatFlashConfig, cache, lengths,
                mesh: Mesh | None = None):
    """One decode step over a ragged batch: ``tokens`` [B], ``lengths`` [B]
    the filled prefix of each row (0 for a pad row of the bucket, which
    then chooses no expert and counts no identity assignment), ``cache``
    the engine's pool with the batch's block table (``kvcache.Paged`` with
    no ``v``). Every sublayer reads all of its rows' pages: the rectangle
    up to two tiles a row, the tiles the rows have filled beyond. Returns
    ``(logits [B, V], latents, expert_tokens, zero_tokens, positions)``
    like :func:`step_prefill`, ``latents`` each [B, 1, 1, 640] for the
    caller to write at ``lengths``, ``positions`` the cached positions the
    step's rows read of a paging layer."""
    filled = cache.filled(lengths)
    x, latents, counts, zeros = _forward(
        params, tokens[:, None], cfg, lengths[:, None],
        (lengths > 0)[:, None], lambda i: cache.past(i, filled), mesh)
    return _head(params, x[:, 0], cfg), latents, counts, zeros, \
        lengths.sum(dtype=jnp.int32)


def observe(expert_tokens, zero_tokens, positions, tokens: int,
            cfg: LongcatFlashConfig, platform: str = "cpu",
            rows: int = 0) -> dict:
    """A step's stats (on the host) and the tokens it ran → the span's
    attributes: ``assignments`` (every choice its tokens made, over all
    layers), ``zero_tokens`` (those that fell on an identity expert: held
    by nobody, absent from nobody, counted ``held="zero"``), the held
    experts' as :func:`experts.observe` names them, and ``latent_bytes`` as
    :func:`latent.observe` does. The counters are counted here."""
    assignments = tokens * cfg.moe_topk * cfg.num_layers
    free = int(zero_tokens.sum())
    HUB.inc(labeled("gen_moe_assignments_total", held="zero"), free)
    return {"assignments": assignments, "zero_tokens": free,
            **experts.observe(expert_tokens, assignments, free=free,
                              platform=platform, call_rows=rows * cfg.moe_topk),
            **latent.observe(positions, cache_spec(cfg), cfg.latent,
                             cfg.dtype)}
