"""EXAONE-MoE (``model_type`` ``exaone_moe``): window and full attention
layers side by side, sparse experts with token-choice top-k routing.

The layer, for input ``x`` [T, D] (every norm an RMSNorm with a learned
weight; each sub-layer's *output* is normalised before the residual add,
the EXAONE 4.0 convention):

- ``x += RMSNorm(attn(x))``: ``q``/``k`` get an RMSNorm over the head width
  (one weight a layer); a ``sliding_attention`` layer rotates them (RoPE,
  whole head) and position ``i`` sees ``j`` with ``0 <= i - j < window``; a
  ``full_attention`` layer rotates nothing and sees every ``j <= i``.
- ``x += RMSNorm(mlp(x))``: a dense SwiGLU (``mlp_layer_types`` ``dense``),
  or ``s = sigmoid(x @ Wr)`` in float32, the ``num_experts_per_tok`` experts
  with the largest ``s + b`` chosen, ``w_e = scale * s_e / sum(chosen s)``,
  ``sum_e w_e E_e(x) + S(x)`` with ``S`` the shared expert.

**One chip's share of an expert-parallel replica.** ``num_experts`` counts
the experts this checkpoint *holds*; ``ep_size`` shares of that size make
the layer, and this is share ``ep_rank``: the router is ``num_experts *
ep_size`` wide and every token chooses among all of them, the held experts
are ``[ep_rank * num_experts, (ep_rank + 1) * num_experts)``, and the layer
computes their part of the result (:mod:`demodel_tpu.models.experts`, which
the other expert-parallel family shares).

The multi-token-prediction layer of the published model drafts tokens for
self-speculation and changes no next-token logit: it is not built here, and
its tensors are left where the loader found them.

**The cache.** The serving engine's pool holds every position of every
layer in one geometry; a window layer's decode *reads* only the table
slots that cover its last ``window - 1`` positions (:func:`step_decode`).
A bounded ring for window layers, which would also stop *holding* what no
step reads again, only shows at contexts of thousands and is not built.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from demodel_tpu.models import experts
from demodel_tpu.models.common import attend, rms_norm
from demodel_tpu.models.hf_loader import Weights
from demodel_tpu.models.llama import _rope


@dataclass(frozen=True)
class ExaoneMoeConfig:
    vocab_size: int = 153600
    hidden_size: int = 6144
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    num_experts: int = 128          # held here
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    ep_size: int = 1                # shares that make a layer
    ep_rank: int = 0                # which of them this is
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    #: a layer's window, 0 for a full-attention layer (never rotated)
    sliding_windows: tuple[int, ...] = ()
    #: whether a layer's MLP is the expert layer
    sparse: tuple[bool, ...] = ()
    dtype: str = "float32"

    @property
    def router_width(self) -> int:
        return self.num_experts * self.ep_size

    @property
    def sparse_layers(self) -> int:
        return sum(self.sparse)

    @classmethod
    def tiny(cls, **over) -> "ExaoneMoeConfig":
        """Test-sized: two periods ``LLLG``, layer 0 dense, window 8, a
        quarter of 16 experts held, 4 a token."""
        kw = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                  moe_intermediate_size=32, num_hidden_layers=8,
                  num_attention_heads=8, num_key_value_heads=2, head_dim=16,
                  num_experts=4, num_experts_per_tok=4, ep_size=4,
                  sliding_windows=(8, 8, 8, 0) * 2,
                  sparse=(False,) + (True,) * 7)
        kw.update(over)
        return cls(**kw)

    @classmethod
    def from_hf(cls, config: dict) -> "ExaoneMoeConfig":
        """From a ``config.json``. ``layer_types``, ``sliding_windows`` and
        ``mlp_layer_types`` may be longer than ``num_hidden_layers`` (a
        checkpoint cut in depth keeps the published lists): the first
        ``num_hidden_layers`` entries count."""
        for key, only in (("n_group", 1), ("topk_group", 1),
                          ("scoring_func", "sigmoid"),
                          ("hidden_act", "silu")):
            if config.get(key, only) != only:
                raise ValueError(f"config field {key}={config[key]!r} is "
                                 "not supported by this stack")
        rope = config.get("rope_parameters") or {}
        if rope.get("rope_type", "default") != "default":
            raise ValueError(f"rope_type {rope['rope_type']!r} is not "
                             "supported by this stack")
        L = int(config["num_hidden_layers"])
        kinds = list(config["layer_types"])[:L]
        windows = list(config.get("sliding_windows")
                       or [config["sliding_window"]] * L)[:L]
        mlps = list(config.get("mlp_layer_types") or [
            "dense" if i < config.get("first_k_dense_replace", 0)
            else "sparse" for i in range(L)])[:L]
        if len(kinds) != L or len(windows) != L or len(mlps) != L:
            raise ValueError(f"layer_types, sliding_windows and "
                             f"mlp_layer_types must cover {L} layers")
        H = int(config["num_attention_heads"])
        return cls(
            vocab_size=int(config["vocab_size"]),
            hidden_size=int(config["hidden_size"]),
            intermediate_size=int(config["intermediate_size"]),
            moe_intermediate_size=int(config["moe_intermediate_size"]),
            num_hidden_layers=L,
            num_attention_heads=H,
            num_key_value_heads=int(config.get("num_key_value_heads", H)),
            head_dim=int(config.get("head_dim")
                         or config["hidden_size"] // H),
            num_experts=int(config["num_experts"]),
            num_experts_per_tok=int(config["num_experts_per_tok"]),
            num_shared_experts=int(config.get("num_shared_experts", 0)),
            ep_size=int(config.get("ep_size", 1)),
            ep_rank=int(config.get("ep_rank", 0)),
            routed_scaling_factor=float(
                config.get("routed_scaling_factor", 1.0)),
            norm_topk_prob=bool(config.get("norm_topk_prob", True)),
            rope_theta=float(rope.get("rope_theta",
                                      config.get("rope_theta", 1e6))),
            rms_norm_eps=float(config.get("rms_norm_eps", 1e-5)),
            sliding_windows=tuple(
                int(w) if kind == "sliding_attention" else 0
                for kind, w in zip(kinds, windows)),
            sparse=tuple(m == "sparse" for m in mlps),
            dtype=(config.get("torch_dtype") or config.get("dtype")
                   or "float32"),
        )


# ------------------------------------------------------------------ params


def init_params(key, cfg: ExaoneMoeConfig) -> dict:
    """Seeded N(0, 1/fan_in) matrices, norms of ones, a zero selection
    bias: the tree :func:`load_params` builds."""
    dt = jnp.dtype(cfg.dtype)
    D, hd = cfg.hidden_size, cfg.head_dim
    H, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    F, E = cfg.moe_intermediate_size, cfg.num_experts
    keys = iter(jax.random.split(key, 16 * cfg.num_hidden_layers + 2))

    def dense(*shape, fan_in=None):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in or shape[-2])).astype(dt)

    layers = []
    for sparse in cfg.sparse:
        layer = {
            "q_proj": dense(D, H * hd), "k_proj": dense(D, Hkv * hd),
            "v_proj": dense(D, Hkv * hd), "o_proj": dense(H * hd, D),
            "q_norm": jnp.ones((hd,), dt), "k_norm": jnp.ones((hd,), dt),
            "attn_norm": jnp.ones((D,), dt), "mlp_norm": jnp.ones((D,), dt),
        }
        if sparse:
            Fs = F * cfg.num_shared_experts
            layer.update({
                "router": dense(D, cfg.router_width),
                "router_bias": jnp.zeros((cfg.router_width,), jnp.float32),
                "experts_gate_up": dense(E, D, 2 * F),
                "experts_down": dense(E, F, D),
                "shared_gate_proj": dense(D, Fs),
                "shared_up_proj": dense(D, Fs),
                "shared_down_proj": dense(Fs, D),
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


def param_shardings(cfg: ExaoneMoeConfig, mesh: Mesh) -> dict:
    """NamedSharding tree matching :func:`init_params`: the held experts
    split over ``ep`` (when they divide), everything else replicated, as
    in the deployment the configuration stands for (attention, the shared
    expert and the router on every chip)."""
    rep = NamedSharding(mesh, P())
    shapes = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    return experts.held_shardings(jax.tree.map(lambda _leaf: rep, shapes),
                                  cfg.num_experts, mesh)


from_hf = ExaoneMoeConfig.from_hf
#: served through its step functions only
forward = None


def load_params(weights: dict, cfg: ExaoneMoeConfig, mesh=None) -> dict:
    """The tree of :func:`init_params` from a checkpoint that
    holds one share of the experts under their global indices
    (``mlp.experts.<ep_rank * num_experts + j>``). Per-expert matrices are
    stacked, gate beside up, so that a projection is one grouped product;
    the router keeps its whole width. Tensors of the multi-token-prediction
    layer stay in ``weights``."""
    w = Weights(weights)
    sh = param_shardings(cfg, mesh) if mesh is not None else {}
    layers = []
    for i, sparse in enumerate(cfg.sparse):
        pre = f"layers.{i}."
        lsh = sh["layers"][i] if sh else {}

        def lin(name, leaf):
            return w.get(pre + name, transpose=True, sharding=lsh.get(leaf))

        def vec(name, leaf):
            return w.get(pre + name, sharding=lsh.get(leaf))

        def held(projs, leaf):
            return experts.stack_experts(w, pre, projs, cfg, lsh.get(leaf))

        layer = {
            "q_proj": lin("self_attn.q_proj.weight", "q_proj"),
            "k_proj": lin("self_attn.k_proj.weight", "k_proj"),
            "v_proj": lin("self_attn.v_proj.weight", "v_proj"),
            "o_proj": lin("self_attn.o_proj.weight", "o_proj"),
            "q_norm": vec("self_attn.q_norm.weight", "q_norm"),
            "k_norm": vec("self_attn.k_norm.weight", "k_norm"),
            "attn_norm": vec("post_attn_layernorm.weight", "attn_norm"),
            "mlp_norm": vec("post_feedforward_layernorm.weight", "mlp_norm"),
        }
        if sparse:
            layer.update({
                "router": lin("mlp.gate.weight", "router"),
                "router_bias": vec("mlp.gate.e_score_correction_bias",
                                   "router_bias").astype(jnp.float32),
                "experts_gate_up": held(("gate", "up"),
                                           "experts_gate_up"),
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


# -------------------------------------------------------------- attention


def _attn(layer, x, cfg: ExaoneMoeConfig, positions, *, window: int,
          past=None):
    """``x`` [B, T, D] at ``positions`` [B, T] → ``(out, (k, v))`` with the
    new keys and values [B, T, Hkv, hd]. What is this family's own: the
    projections, the q/k norms, rotation on window layers only (``window``
    0 is a full layer: nothing rotated). The attention itself, ``window``
    and ``past`` (a decode step's cached blocks) are
    :func:`common.attend`'s."""
    B, T, _D = x.shape
    hd, H, Hkv = cfg.head_dim, cfg.num_attention_heads, \
        cfg.num_key_value_heads
    q = rms_norm((x @ layer["q_proj"]).reshape(B, T, H, hd),
                 layer["q_norm"], cfg.rms_norm_eps)
    k = rms_norm((x @ layer["k_proj"]).reshape(B, T, Hkv, hd),
                 layer["k_norm"], cfg.rms_norm_eps)
    v = (x @ layer["v_proj"]).reshape(B, T, Hkv, hd)
    if window:
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
    out = attend(q, k, v, positions, window=window, past=past)
    return out @ layer["o_proj"], (k, v)


# ---------------------------------------------------------- expert layer


def _moe(layer, x, live, cfg: ExaoneMoeConfig, mesh: Mesh | None):
    """``x`` [N, D] → ``(mlp(x) [N, D], tokens per held expert [E])``:
    this family's scoring (sigmoid, the selection bias, the scale) and its
    shared expert around :func:`experts.routed`."""
    K = cfg.num_experts_per_tok
    with jax.named_scope("moe.route"):
        s = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), layer["router"].astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        _best, chosen = lax.top_k(s + layer["router_bias"], K)
        weights = jnp.take_along_axis(s, chosen, axis=1)
        if cfg.norm_topk_prob:
            weights = weights / weights.sum(axis=1, keepdims=True)
        weights = weights * cfg.routed_scaling_factor
        y, tokens = experts.routed(
            x, live, chosen, weights, layer["experts_gate_up"],
            layer["experts_down"], cfg.ep_rank * cfg.num_experts, mesh)
    shared = experts.swiglu(
        x, layer["shared_gate_proj"], layer["shared_up_proj"],
        layer["shared_down_proj"]) if cfg.num_shared_experts else 0.0
    return y.astype(x.dtype) + shared, tokens


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
    """Every layer over ``tokens`` [B, T] → ``(x, new kv, expert tokens
    [sparse layers, E])``; ``pasts`` is a layer's ``past`` or None."""
    x = params["embed"][tokens]
    new_kv, counts = [], []
    for layer, window, past in zip(params["layers"], cfg.sliding_windows,
                                   pasts):
        with jax.named_scope("attn.window" if window else "attn.full"):
            a, kv = _attn(layer, x, cfg, positions, window=window, past=past)
        new_kv.append(kv)
        x = x + rms_norm(a, layer["attn_norm"], cfg.rms_norm_eps)
        m, n = _mlp(layer, x, live, cfg, mesh)
        if n is not None:
            counts.append(n)
        x = x + rms_norm(m, layer["mlp_norm"], cfg.rms_norm_eps)
    return x, new_kv, jnp.stack(counts) if counts else jnp.zeros(
        (0, cfg.num_experts), jnp.int32)


def _head(params, x, cfg):
    return rms_norm(x, params["final_norm"], cfg.rms_norm_eps) \
        @ params["lm_head"]


# ------------------------------------------------------ the engine's steps


def cache_spec(cfg: ExaoneMoeConfig):
    """What the serving engine keeps for a sequence: every layer pages K
    and V, window and full alike, nothing of fixed size."""
    from demodel_tpu.serve.kvcache import CacheSpec

    return CacheSpec(cfg.num_hidden_layers, cfg.num_key_value_heads,
                     cfg.head_dim)


def step_prefill(params, tokens, cfg: ExaoneMoeConfig,
                 mesh: Mesh | None = None):
    """``tokens`` [B, T] (equal lengths) → ``(last_logits [B, V], kv,
    expert_tokens)``: ``kv`` the per-layer ``(k, v)``, each [B, T, Hkv,
    hd], for the caller to page into the pool; ``expert_tokens`` [sparse
    layers, held experts] int32, the assignments each held expert got."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    x, kv, counts = _forward(params, tokens, cfg, positions,
                             jnp.ones((B, T), bool),
                             [None] * cfg.num_hidden_layers, mesh)
    return _head(params, x[:, -1], cfg), kv, counts


def window_slots(window: int, block_tokens: int) -> int:
    """Table slots that cover the ``window - 1`` cached positions a window
    layer's new token sees, wherever they start in a block."""
    return -(-(window - 1) // block_tokens) + 1


def step_decode(params, tokens, cfg: ExaoneMoeConfig, cache, lengths,
                mesh: Mesh | None = None):
    """One decode step over a ragged batch: ``tokens`` [B], ``lengths``
    [B] the filled prefix of each row (0 for a pad row of the bucket,
    which then chooses no expert), ``cache`` the engine's pool with the
    batch's block table (``kvcache.Paged``: ``table`` [B, n],
    ``block_tokens``, ``read(layer, ids)``). A full layer reads all ``n``
    slots of a row (of a wide table the tiles its rows have filled); a
    window layer the :func:`window_slots` that cover its last ``window -
    1`` positions. Returns ``(logits [B, V], new_kv,
    expert_tokens)`` like :func:`step_prefill`, ``new_kv`` each [B, 1, Hkv,
    hd] for the caller to write at ``lengths``."""
    B, n = cache.table.shape
    bs = cache.block_tokens

    def slots(window: int):
        """``(block ids [B, m], positions [B, m * bs])`` a layer reads."""
        m = window_slots(window, bs) if window else n
        if m >= n:
            return cache.table, jnp.broadcast_to(jnp.arange(n * bs),
                                                 (B, n * bs))
        first = jnp.clip((lengths - (window - 1)) // bs, 0, n - m)
        at = first[:, None] + jnp.arange(m)[None, :]
        return (jnp.take_along_axis(cache.table, at, axis=1),
                first[:, None] * bs + jnp.arange(m * bs)[None, :])

    views = {w: slots(w) for w in set(cfg.sliding_windows)}
    # a full layer over a wide table follows the tiles its rows have filled
    tiles = cache.filled(lengths) if cache.wide else None
    pasts = [cache.past(li, tiles) if tiles is not None and not w
             else (*cache.read(li, views[w][0]), views[w][1],
                   views[w][1] < lengths[:, None])
             for li, w in enumerate(cfg.sliding_windows)]
    x, new_kv, counts = _forward(params, tokens[:, None], cfg,
                                 lengths[:, None], (lengths > 0)[:, None],
                                 pasts, mesh)
    return _head(params, x[:, 0], cfg), new_kv, counts


def observe(expert_tokens, tokens: int, cfg: ExaoneMoeConfig,
            platform: str = "cpu", rows: int = 0) -> dict:
    """A step's ``expert_tokens`` (on the host) and the tokens it ran (of
    the program's ``rows``) → the span's attributes; the counters are
    counted here."""
    return experts.observe(
        expert_tokens,
        tokens * cfg.num_experts_per_tok * cfg.sparse_layers,
        platform=platform, call_rows=rows * cfg.num_experts_per_tok)
