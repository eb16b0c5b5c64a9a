"""A.X-K1 (``model_type`` ``axk1``): latent attention (MLA) over one cached
vector a position, sparse experts chosen within the best groups.

The layer, for a row ``x`` of the residual (every norm an RMSNorm with a
learned weight, ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``):

- **Latent attention.** ``c_q = RMSNorm(W_qa x)``; head ``i`` of ``H`` has
  ``[q_nope_i | q_rope_i] = W_qb c_q`` (128 | 64). ``[c_kv | k_r] = W_kva
  x`` (512 | 64), ``c_kv <- RMSNorm(c_kv)``, and ``[k_nope_i | v_i] = W_kvb
  c_kv`` (128 | 128). ``q_rope_i`` and ``k_r``, which every head shares, are
  rotated (YaRN frequencies, adjacent columns paired). ``s_i(t, u) = scale
  (q_nope_i(t) k_nope_i(u) + q_rope_i(t) k_r(u))``, a causal softmax in
  float32, ``o_i = sum p_i v_i``, ``Attn = W_o [o_1 .. o_H]``.
- **Sparse FFN** (layers from ``first_k_dense_replace`` on): ``s =
  sigmoid(W_r x)`` in float32 over the whole router; the experts lie in
  ``n_group`` groups of consecutive ones, a group scores its best expert,
  the ``topk_group`` best groups are kept and the ``num_experts_per_tok``
  largest ``s`` inside them chosen; ``w = scale * s / sum(chosen s)``;
  ``sum_e w_e E_e(x) + S(x)`` with ``S`` the shared expert. The layers
  before are a dense SwiGLU. (``topk_method`` ``none``: no selection bias
  exists, none is created or loaded.)

**Two attention paths for one set of weights**
(:mod:`demodel_tpu.models.latent`, which LongCat-Flash calls too; what is
this family's is :attr:`AxK1Config.latent`: YaRN's frequencies, ``mscale``
squared in the scores' scale). ``W_kvb`` is held split by
head, ``w_uk`` and ``w_uv`` ``[H, 512, 128]`` each (and ``W_qb`` as its
unrotated and its rotary columns, ``q_b_nope`` and ``q_b_rope``: held as
one matrix, the step's compiler transposes all of it every step to get at
the two). A prefill *expands*:
keys of 192 and values of 128 a head from ``c_kv``, ``H`` heads, and hands
the prompt's ``[c_kv | k_r]`` (one vector of 576 a position, no head of its
own) to the pool. A decode step *absorbs*: ``q~_i = w_uk_i^T q_nope_i``
(512), ``s_i(u) = scale (q~_i c_kv(u) + q_rope_i k_r(u))``, ``o~_i = sum p_i
c_kv``, ``o_i = w_uv_i o~_i``: multi-query attention of ``H`` heads over
the one cached vector, whose first 512 columns are also the values. No
key or value of a head is ever written.

**The cache** (:func:`cache_spec`) is a page of one array, ``[layers,
blocks + 1, 1, block_tokens, 640]`` (``kvcache.CacheSpec.values``): the
pool leases and donates it as it does K and V, a step reads a row's blocks
once (``Paged.past``) and ``common.attend`` takes the values as the leading
columns of the keys it gathered. The 576 columns of ``[c_kv | k_r]`` are
followed by 64 of zeros (:data:`latent.LANES`): the TPU holds an array whose
innermost dimension is no multiple of its 128 lanes with another
dimension innermost (here the blocks), and every program that took the
pool would first copy all of it into the order it reads (2.4 GB a step at
the benchmark's size, seen in the compiled step). In the order it is read
the tiles pad 576 to 640 anyway; the page says so and the queries carry
zeros there.

**One chip's share of an expert-parallel replica**, as
:mod:`demodel_tpu.models.exaone_moe` tells it: ``n_routed_experts`` counts
the experts held, the router is ``n_routed_experts * ep_size`` wide with
its ``n_group`` groups, and the layer computes the held experts' part
(:mod:`demodel_tpu.models.experts`).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from demodel_tpu.models import experts, latent
from demodel_tpu.models.common import refuse_unsupported, rms_norm
from demodel_tpu.models.hf_loader import Weights


@dataclass(frozen=True)
class AxK1Config:
    vocab_size: int = 163840
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 192     # held here
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    first_k_dense_replace: int = 1
    ep_size: int = 1                # shares that make a layer
    ep_rank: int = 0                # which of them this is
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    #: YaRN (``rope_scaling``): factor, the positions the model was first
    #: trained on, the two rotation counts between which a frequency is
    #: blended, and the two ``mscale`` settings
    rope_factor: float = 32.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    dtype: str = "float32"

    @property
    def router_width(self) -> int:
        return self.n_routed_experts * self.ep_size

    @property
    def num_experts(self) -> int:
        """The experts held, under the name the loader that stacks them
        (``experts.stack_experts``) shares with the other families."""
        return self.n_routed_experts

    @property
    def sparse_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def latent(self) -> latent.Geometry:
        """This family's latent attention: YaRN frequencies and, in the
        scores' scale, ``(nope + rope) ** -0.5`` times ``mscale(factor,
        mscale_all_dim) ** 2``, YaRN's correction of their temperature."""
        inv, factor = yarn_frequencies(self)
        return latent.Geometry(
            self.num_attention_heads, self.kv_lora_rank,
            self.qk_rope_head_dim, tuple(map(float, inv)), factor,
            (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
            * _mscale(self.rope_factor, self.rope_mscale_all_dim) ** 2,
            self.rms_norm_eps)

    @property
    def latent_dim(self) -> int:
        return self.latent.latent_dim

    @property
    def page_dim(self) -> int:
        return self.latent.page_dim

    @property
    def softmax_scale(self) -> float:
        return self.latent.scale

    @classmethod
    def tiny(cls, **over) -> "AxK1Config":
        """Test-sized: layer 0 dense and three sparse ones, 4 heads of 16 |
        8 and values of 16 over a latent of 32 | 8, 16 experts in 4 groups
        of which 2 are chosen, 4 a token, a quarter of them held."""
        kw = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                  moe_intermediate_size=32, num_hidden_layers=4,
                  num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
                  qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                  n_routed_experts=4, num_experts_per_tok=4, n_group=4,
                  topk_group=2, ep_size=4, rope_factor=4.0,
                  rope_original_max=16)
        kw.update(over)
        return cls(**kw)

    @classmethod
    def from_hf(cls, config: dict) -> "AxK1Config":
        """From a ``config.json``; a key whose value this module does not
        implement is refused by name."""
        refuse_unsupported(
            config, fields=("attention_bias", "sliding_window"),
            only={"n_shared_experts": 1, "moe_layer_freq": 1,
                  "scoring_func": "sigmoid", "hidden_act": "silu",
                  "topk_method": "none", "rope_scaling.type": "yarn"})
        rope = config.get("rope_scaling")
        for key in ("rope_scaling", "q_lora_rank"):
            if not config.get(key):     # plain rotary, uncompressed queries
                raise ValueError(f"config field {key}={config.get(key)!r} "
                                 "is not supported by this stack")
        held, ep = int(config["n_routed_experts"]), int(
            config.get("ep_size", 1))
        groups = int(config.get("n_group", 1))
        if (held * ep) % groups:
            raise ValueError(f"config field n_group={groups} does not "
                             f"divide the router's {held * ep} outputs")
        return cls(
            vocab_size=int(config["vocab_size"]),
            hidden_size=int(config["hidden_size"]),
            intermediate_size=int(config["intermediate_size"]),
            moe_intermediate_size=int(config["moe_intermediate_size"]),
            num_hidden_layers=int(config["num_hidden_layers"]),
            num_attention_heads=int(config["num_attention_heads"]),
            q_lora_rank=int(config["q_lora_rank"]),
            kv_lora_rank=int(config["kv_lora_rank"]),
            qk_nope_head_dim=int(config["qk_nope_head_dim"]),
            qk_rope_head_dim=int(config["qk_rope_head_dim"]),
            v_head_dim=int(config["v_head_dim"]),
            n_routed_experts=held,
            num_experts_per_tok=int(config["num_experts_per_tok"]),
            n_group=groups,
            topk_group=int(config.get("topk_group", 1)),
            first_k_dense_replace=int(config.get("first_k_dense_replace",
                                                 0)),
            ep_size=ep,
            ep_rank=int(config.get("ep_rank", 0)),
            routed_scaling_factor=float(
                config.get("routed_scaling_factor", 1.0)),
            norm_topk_prob=bool(config.get("norm_topk_prob", True)),
            rms_norm_eps=float(config.get("rms_norm_eps", 1e-6)),
            rope_theta=float(config.get("rope_theta", 10000.0)),
            rope_factor=float(rope["factor"]),
            rope_original_max=int(rope["original_max_position_embeddings"]),
            rope_beta_fast=float(rope.get("beta_fast", 32)),
            rope_beta_slow=float(rope.get("beta_slow", 1)),
            rope_mscale=float(rope.get("mscale", 1)),
            rope_mscale_all_dim=float(rope.get("mscale_all_dim", 0)),
            dtype=(config.get("torch_dtype") or config.get("dtype")
                   or "float32"),
        )


# ------------------------------------------------------------------ params


def init_params(key, cfg: AxK1Config) -> dict:
    """Seeded N(0, 1/fan_in) matrices and norms of ones: the tree
    :func:`load_params` builds."""
    dt = jnp.dtype(cfg.dtype)
    D = cfg.hidden_size
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    C, Q = cfg.kv_lora_rank, cfg.q_lora_rank
    F, E = cfg.moe_intermediate_size, cfg.n_routed_experts
    keys = iter(jax.random.split(key, 16 * cfg.num_hidden_layers + 2))

    def dense(*shape, fan_in=None):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in or shape[-2])).astype(dt)

    layers = []
    for i in range(cfg.num_hidden_layers):
        layer = {
            **latent.matrices(dense, D, Q, cfg.latent, nope, vd),
            "q_a_norm": jnp.ones((Q,), dt), "kv_a_norm": jnp.ones((C,), dt),
            "attn_norm": jnp.ones((D,), dt), "mlp_norm": jnp.ones((D,), dt),
        }
        if i >= cfg.first_k_dense_replace:
            layer.update({
                "router": dense(D, cfg.router_width),
                "experts_gate_up": dense(E, D, 2 * F),
                "experts_down": dense(E, F, D),
                "shared_gate_proj": dense(D, F),
                "shared_up_proj": dense(D, F),
                "shared_down_proj": dense(F, D),
            })
        else:
            I = cfg.intermediate_size
            layer.update({"gate_proj": dense(D, I), "up_proj": dense(D, I),
                          "down_proj": dense(I, D)})
        layers.append(layer)
    return {
        "embed": dense(cfg.vocab_size, D, fan_in=1),
        "layers": layers,
        "final_norm": jnp.ones((D,), dt),
        "lm_head": dense(D, cfg.vocab_size),
    }


def param_shardings(cfg: AxK1Config, mesh: Mesh) -> dict:
    """NamedSharding tree matching :func:`init_params`: the held experts
    split over ``ep`` (when they divide), everything else replicated, as
    in the deployment (data-parallel attention over the latent cache, the
    shared expert and the router on every chip)."""
    rep = NamedSharding(mesh, P())
    shapes = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    return experts.held_shardings(jax.tree.map(lambda _leaf: rep, shapes),
                                  cfg.n_routed_experts, mesh)


from_hf = AxK1Config.from_hf
#: served through its step functions only
forward = None


def load_params(weights: dict, cfg: AxK1Config, mesh=None) -> dict:
    """The tree of :func:`init_params` from a checkpoint of the
    DeepSeek-V3 style of names, holding one share of the experts under
    their global indices. The attention is :func:`latent.load_attention`'s
    (``w_uk`` and ``w_uv`` by head, which the expanded prefill and the
    absorbed decode both read); the experts are stacked as
    :func:`experts.stack_experts` stacks them. A selection
    bias in the checkpoint is refused: the module implements
    ``topk_method`` ``none``, which has none."""
    w = Weights(weights)
    sh = param_shardings(cfg, mesh) if mesh is not None else {}
    layers = []
    for i in range(cfg.num_hidden_layers):
        pre = f"layers.{i}."
        lsh = sh["layers"][i] if sh else {}

        def lin(name, leaf):
            return w.get(pre + name, transpose=True, sharding=lsh.get(leaf))

        def vec(name, leaf):
            return w.get(pre + name, sharding=lsh.get(leaf))

        def held(projs, leaf):
            return experts.stack_experts(w, pre, projs, cfg, lsh.get(leaf))

        if w.has(pre + "mlp.gate.e_score_correction_bias"):
            raise ValueError(
                f"checkpoint tensor {pre}mlp.gate.e_score_correction_bias: "
                "a selection bias is not supported by this stack "
                "(topk_method none)")
        layer = {
            **latent.load_attention(w, pre + "self_attn.",
                                cfg.num_attention_heads,
                                cfg.qk_nope_head_dim, lsh),
            "attn_norm": vec("input_layernorm.weight", "attn_norm"),
            "mlp_norm": vec("post_attention_layernorm.weight", "mlp_norm"),
        }
        if i >= cfg.first_k_dense_replace:
            layer.update({
                "router": lin("mlp.gate.weight", "router"),
                "experts_gate_up": held(("gate", "up"), "experts_gate_up"),
                "experts_down": held(("down",), "experts_down"),
                "shared_gate_proj": lin("mlp.shared_experts.gate_proj.weight",
                                        "shared_gate_proj"),
                "shared_up_proj": lin("mlp.shared_experts.up_proj.weight",
                                      "shared_up_proj"),
                "shared_down_proj": lin("mlp.shared_experts.down_proj.weight",
                                        "shared_down_proj"),
            })
        else:
            layer.update({
                "gate_proj": lin("mlp.gate_proj.weight", "gate_proj"),
                "up_proj": lin("mlp.up_proj.weight", "up_proj"),
                "down_proj": lin("mlp.down_proj.weight", "down_proj"),
            })
        layers.append(layer)
    return {
        "embed": w.get("embed_tokens.weight", sharding=sh.get("embed")),
        "layers": layers,
        "final_norm": w.get("norm.weight", sharding=sh.get("final_norm")),
        "lm_head": w.get("lm_head.weight", transpose=True,
                         sharding=sh.get("lm_head")),
    }


# ----------------------------------------------------------------- rotary


def _mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(cfg: AxK1Config) -> tuple[np.ndarray, float]:
    """``(inverse frequencies [rope / 2], the factor on cos and sin)``.
    Column pair ``j`` turns ``theta ** (-2j / rope)`` a position unscaled
    and ``factor`` times slower interpolated; it is interpolated wholly
    where it makes fewer than ``beta_slow`` turns over the original
    context, left as it is where it makes more than ``beta_fast``, and
    blended along a linear ramp between the two correction dimensions."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta

    def correction(turns: float) -> float:
        return dim * math.log(cfg.rope_original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction(cfg.rope_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    unscaled = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    inv = unscaled / cfg.rope_factor * ramp + unscaled * (1.0 - ramp)
    return inv.astype(np.float32), _mscale(
        cfg.rope_factor, cfg.rope_mscale) / _mscale(
        cfg.rope_factor, cfg.rope_mscale_all_dim)


# ---------------------------------------------------------- expert layer


def choose(s, cfg: AxK1Config):
    """The group-limited choice: ``s`` [N, router] scores → expert ids [N,
    K]. A group (consecutive experts) scores its best expert; the
    ``topk_group`` best groups are kept; the K largest scores inside them
    are chosen."""
    N, R = s.shape
    G = cfg.n_group
    _best, kept = lax.top_k(s.reshape(N, G, R // G).max(axis=-1),
                            cfg.topk_group)
    inside = (kept[:, :, None] == jnp.arange(G)[None, None, :]).any(axis=1)
    # scores are sigmoids, above 0: what lies outside reads below them all
    masked = jnp.where(jnp.repeat(inside, R // G, axis=1), s, -1.0)
    return lax.top_k(masked, cfg.num_experts_per_tok)[1]


def _moe(layer, x, live, cfg: AxK1Config, mesh: Mesh | None):
    """``x`` [N, D] → ``(mlp(x) [N, D], tokens per held expert [E])``:
    this family's scoring and choice and its shared expert around
    :func:`experts.routed`."""
    with jax.named_scope("moe.route"):
        s = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), layer["router"].astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        chosen = choose(s, cfg)
        weights = jnp.take_along_axis(s, chosen, axis=1)
        if cfg.norm_topk_prob:
            weights = weights / weights.sum(axis=1, keepdims=True)
        weights = weights * cfg.routed_scaling_factor
        y, tokens = experts.routed(
            x, live, chosen, weights, layer["experts_gate_up"],
            layer["experts_down"], cfg.ep_rank * cfg.n_routed_experts, mesh)
    return y.astype(x.dtype) + experts.swiglu(
        x, layer["shared_gate_proj"], layer["shared_up_proj"],
        layer["shared_down_proj"]), tokens


def _mlp(layer, x, live, cfg, mesh):
    """``x`` [B, T, D] → ``(mlp(x), tokens per held expert or None)``."""
    if "router" not in layer:
        return experts.swiglu(x, layer["gate_proj"], layer["up_proj"],
                              layer["down_proj"]), None
    B, T, D = x.shape
    y, tokens = _moe(layer, x.reshape(B * T, D), live.reshape(B * T), cfg,
                     mesh)
    return y.reshape(B, T, D), tokens


def _forward(params, tokens, cfg, positions, live, pasts, mesh):
    """Every layer over ``tokens`` [B, T] → ``(x, each layer's latent,
    expert tokens [sparse layers, E])``; a layer's ``past`` is its pages
    (a step, absorbed) or None (a prompt, expanded)."""
    x = params["embed"][tokens]
    latents, counts = [], []
    for layer, past in zip(params["layers"], pasts):
        a, new = latent.attention(
            layer, rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps),
            cfg.latent, positions, past)
        latents.append(new)
        x = x + a
        m, n = _mlp(layer, rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps),
                    live, cfg, mesh)
        if n is not None:
            counts.append(n)
        x = x + m
    return x, latents, jnp.stack(counts) if counts else jnp.zeros(
        (0, cfg.n_routed_experts), jnp.int32)


def _head(params, x, cfg):
    return rms_norm(x, params["final_norm"], cfg.rms_norm_eps) \
        @ params["lm_head"]


# ------------------------------------------------------ the engine's steps


def cache_spec(cfg: AxK1Config):
    """What the serving engine keeps for a sequence: every layer pages one
    vector a position, ``[c_kv | k_rope]`` and zeros up to the lanes, whose
    first ``kv_lora_rank`` columns are also its values: a page of one
    array."""
    from demodel_tpu.serve.kvcache import CacheSpec

    return CacheSpec(cfg.num_hidden_layers, 1, cfg.page_dim,
                     values=cfg.kv_lora_rank)


def step_prefill(params, tokens, cfg: AxK1Config, mesh: Mesh | None = None):
    """``tokens`` [B, T] (equal lengths) → ``(last_logits [B, V], latents,
    expert_tokens, positions)``: ``latents`` each layer's [B, T, 1, 640]
    for the caller to page into the pool; ``expert_tokens`` [sparse layers,
    held experts] int32; ``positions`` the latent positions the step
    wrote."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    x, latents, counts = _forward(params, tokens, cfg, positions,
                                  jnp.ones((B, T), bool),
                                  [None] * cfg.num_hidden_layers, mesh)
    return _head(params, x[:, -1], cfg), latents, counts, jnp.int32(B * T)


def step_decode(params, tokens, cfg: AxK1Config, cache, lengths,
                mesh: Mesh | None = None):
    """One decode step over a ragged batch: ``tokens`` [B], ``lengths`` [B]
    the filled prefix of each row (0 for a pad row of the bucket, which
    then chooses no expert), ``cache`` the engine's pool with the batch's
    block table (``kvcache.Paged`` with no ``v``). Every layer reads all of
    its rows' pages: the rectangle up to two tiles a row, the tiles the
    rows have filled beyond. Returns ``(logits [B, V], latents,
    expert_tokens, positions)`` like :func:`step_prefill`, ``latents`` each
    [B, 1, 1, 640] for the caller to write at ``lengths``, ``positions``
    the cached positions the step's rows read of a layer."""
    filled = cache.filled(lengths)
    x, latents, counts = _forward(
        params, tokens[:, None], cfg, lengths[:, None],
        (lengths > 0)[:, None],
        [cache.past(li, filled) for li in range(cfg.num_hidden_layers)],
        mesh)
    return _head(params, x[:, 0], cfg), latents, counts, \
        lengths.sum(dtype=jnp.int32)


def observe(expert_tokens, positions, tokens: int, cfg: AxK1Config,
            platform: str = "cpu", rows: int = 0) -> dict:
    """A step's stats (on the host) and the tokens it ran → the span's
    attributes: the experts' as :func:`experts.observe` names them, and
    ``latent_bytes``, the positions of the latent page the step's rows read
    (a prefill: wrote) times the ``[c_kv | k_rope]`` every layer keeps of
    one (the page's zeros are not the latent's bytes). The counters are
    counted here."""
    attrs = experts.observe(
        expert_tokens, tokens * cfg.num_experts_per_tok * cfg.sparse_layers,
        platform=platform, call_rows=rows * cfg.num_experts_per_tok)
    attrs.update(latent.observe(positions, cache_spec(cfg), cfg.latent,
                                cfg.dtype))
    return attrs
