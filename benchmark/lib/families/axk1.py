"""The A.X-K1 family (``model_type`` ``axk1``), as one chip's share of an
expert-parallel replica holds it.

For a row ``x`` of the residual, every norm an RMSNorm with a learned
weight (``rms_norm_eps``): ``h = x + Attn(RMSNorm_in(x))``, ``y = h +
FFN(RMSNorm_post(h))``; a final RMSNorm and the untied head.

- **Latent attention**, written here in its *expanded* form at every
  position (the program decodes in the absorbed form, other algebra over
  the same weights). ``c_q = RMSNorm(W_qa x)`` (``q_lora_rank``); head ``i``
  of ``H``: ``[q_nope_i | q_rope_i] = W_qb c_q`` (``qk_nope_head_dim`` |
  ``qk_rope_head_dim``). ``[c_kv | k_r] = W_kva x`` (``kv_lora_rank`` |
  ``qk_rope_head_dim``), ``c_kv <- RMSNorm(c_kv)``; ``[k_nope_i | v_i] =
  W_kvb c_kv`` (``qk_nope_head_dim`` | ``v_head_dim``, a head's key rows
  before its value rows). ``q_rope_i`` and ``k_r`` (one for all heads) are
  rotated. ``s_i(t, u) = scale (q_nope_i(t) k_nope_i(u) + q_rope_i(t)
  k_r(u))``, causal softmax in float32, ``o_i = sum p_i v_i``, ``Attn = W_o
  [o_1 .. o_H]``.
- **Rotary**: YaRN over the rotary columns, adjacent columns ``(2j, 2j +
  1)`` a pair. Pair ``j`` turns ``theta ** (-2j / r)`` a position unscaled,
  ``factor`` times slower interpolated; the two are blended by the linear
  ramp between the correction dimensions of ``beta_fast`` and
  ``beta_slow`` turns over ``original_max_position_embeddings``. cos and
  sin carry ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``
  and ``scale = (nope + rope) ** -0.5 * mscale(factor, mscale_all_dim) **
  2``, ``mscale(f, m) = 0.1 m ln f + 1``.
- **FFN.** The first ``first_k_dense_replace`` layers: a SwiGLU of
  ``intermediate_size``. The others: ``s = sigmoid(W_r x)`` in float32 in
  every mode over the whole router; the router's outputs lie in
  ``n_group`` groups of consecutive experts, a group scores ``max s`` of
  its experts, the ``topk_group`` best groups are kept, the
  ``num_experts_per_tok`` largest ``s`` inside them chosen; ``w = s[chosen]
  / sum s[chosen] * routed_scaling_factor``; ``FFN(x) = sum(chosen, held)
  w_e E_e(x) + S(x)``, ``E_e`` and the shared ``S`` SwiGLUs of
  ``moe_intermediate_size``. ``topk_method`` is ``none``: there is no
  selection bias.

**The share.** ``n_routed_experts`` is how many experts are held; the
router is ``n_routed_experts * ep_size`` wide, the held experts are
``[ep_rank * n_routed_experts, (ep_rank + 1) * n_routed_experts)``. What
the absent experts would add is left out here as in the program.

The costs count what the mathematics needs. A prefill expands (scores of
192 and values of 128 a head over the causal half); a decode step reads
the weights once, the held experts its ``experts_hit`` attribute says
were hit, and one vector of ``kv_lora_rank + qk_rope_head_dim`` a layer a
cached position of each row.

Imports nothing of the program under test.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import reference
from . import Filled

#: queries a block of the reference's attention: 64 heads of float32
#: scores over 3 072 keys are then 0.2 GB
BLOCK = 256
#: every sequence of a call is padded to the longest of them, rounded up to
#: a multiple of this many positions: one compiled length a kind of layer
#: (a run that compiles them spends ~12 s on each) where a cell's sequences
#: of 1 025 to 3 072 positions would compile nine, or two
LENGTH = 1024


def _dims(cfg: dict) -> dict:
    held, ep = cfg["n_routed_experts"], cfg.get("ep_size", 1)
    L, dense = cfg["num_hidden_layers"], cfg.get("first_k_dense_replace", 0)
    return dict(
        D=cfg["hidden_size"], H=cfg["num_attention_heads"], L=L,
        V=cfg["vocab_size"], I=cfg["intermediate_size"],
        F=cfg["moe_intermediate_size"], Q=cfg["q_lora_rank"],
        C=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], vd=cfg["v_head_dim"],
        held=held, router=held * ep, ep=ep,
        first=cfg.get("ep_rank", 0) * held, K=cfg["num_experts_per_tok"],
        groups=cfg.get("n_group", 1), kept=cfg.get("topk_group", 1),
        sparse=[i >= dense for i in range(L)])


def rehearsal(config: dict) -> dict:
    """The toy the rehearsal swaps in: a dense layer and three sparse
    ones, 4 heads over a latent of 32 | 8, a quarter of 16 experts held in
    4 groups of which 2 are kept. ``num_experts`` is the alias the
    configuration's file keeps for the EXAONE family's reader."""
    return {"hidden_size": 128, "intermediate_size": 256,
            "moe_intermediate_size": 64, "num_hidden_layers": 4,
            "num_attention_heads": 4, "num_key_value_heads": 4,
            "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16, "vocab_size": 512,
            "n_routed_experts": 4, "num_experts": 4,
            "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2,
            "ep_size": 4, "ep_rank": 1}


# ------------------------------------------------------------- the weights


def tensors(config: dict) -> dict[str, Filled]:
    """The DeepSeek-V3 style of names in the checkpoint's order. A matrix
    is N(0, 1/fan_in) over its ``[out, in]`` layout's inputs, a norm ones;
    the embedding's fan-in is 1 (a row is selected, nothing is summed).
    The experts are one tensor a projection an expert, under their index
    in the whole layer."""
    d = _dims(config)
    D, H = d["D"], d["H"]

    def matrix(out: int, fan_in: int) -> Filled:
        return Filled((out, fan_in), "normal", fan_in)

    def swiglu(prefix: str, width: int) -> dict:
        return {prefix + "gate_proj.weight": matrix(width, D),
                prefix + "up_proj.weight": matrix(width, D),
                prefix + "down_proj.weight": matrix(D, width)}

    table = {"model.embed_tokens.weight": Filled((d["V"], D), "normal", 1)}
    for i in range(d["L"]):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        table.update({
            p + "input_layernorm.weight": Filled((D,), "ones"),
            a + "q_a_proj.weight": matrix(d["Q"], D),
            a + "q_a_layernorm.weight": Filled((d["Q"],), "ones"),
            a + "q_b_proj.weight": matrix(H * (d["nope"] + d["rope"]),
                                          d["Q"]),
            a + "kv_a_proj_with_mqa.weight": matrix(d["C"] + d["rope"], D),
            a + "kv_a_layernorm.weight": Filled((d["C"],), "ones"),
            a + "kv_b_proj.weight": matrix(H * (d["nope"] + d["vd"]),
                                           d["C"]),
            a + "o_proj.weight": matrix(D, H * d["vd"]),
            p + "post_attention_layernorm.weight": Filled((D,), "ones"),
        })
        if not d["sparse"][i]:
            table.update(swiglu(p + "mlp.", d["I"]))
            continue
        table[p + "mlp.gate.weight"] = matrix(d["router"], D)
        for e in range(d["first"], d["first"] + d["held"]):
            table.update(swiglu(f"{p}mlp.experts.{e}.", d["F"]))
        table.update(swiglu(p + "mlp.shared_experts.", d["F"]))
    table.update({"model.norm.weight": Filled((D,), "ones"),
                  "lm_head.weight": matrix(d["V"], D)})
    return table


# ----------------------------------------------------------- the reference


def _mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn(cfg: dict) -> tuple[np.ndarray, float, float]:
    """``(inverse frequency of each column pair, the factor on cos and sin,
    the softmax scale)`` from the configuration's ``rope_scaling``."""
    r, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    s = cfg["rope_scaling"]
    factor, trained = float(s["factor"]), s["original_max_position_embeddings"]

    def dimension(turns: float) -> float:
        # the pair that makes ``turns`` turns over the trained context
        return r * math.log(trained / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dimension(s.get("beta_fast", 32))), 0)
    high = min(math.ceil(dimension(s.get("beta_slow", 1))), r - 1)
    if low == high:
        high += 0.001
    j = np.arange(r // 2, dtype=np.float64)
    plain = theta ** (-2.0 * j / r)
    ramp = np.clip((j - low) / (high - low), 0.0, 1.0)   # 1: interpolated
    inv = plain * (1.0 - ramp) + plain / factor * ramp
    all_dim = _mscale(factor, s.get("mscale_all_dim", 0))
    scale = (cfg["qk_nope_head_dim"] + r) ** -0.5 * all_dim * all_dim
    return inv.astype(np.float32), _mscale(factor, s.get("mscale", 1)) \
        / all_dim, scale


def _rotate(x, inv, factor: float):
    """``x`` [T, h, r] at positions 0..T-1, column pairs ``(2j, 2j + 1)``
    turned where they lie."""
    T = x.shape[0]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _swiglu(x, gate, up, down, mode: str):
    linear = reference.linear
    return linear(jax.nn.silu(linear(x, gate, mode)) * linear(x, up, mode),
                  down, mode)


def choose(s, groups: int, kept: int, K: int):
    """Scores ``s`` [T, router] → the chosen experts [T, K]: the K largest
    scores inside the ``kept`` groups whose best expert scores highest."""
    T, R = s.shape
    best = s.reshape(T, groups, R // groups).max(axis=-1)
    order = jnp.argsort(-best, axis=1)      # stable: a tie to the lower
    inside = jnp.zeros((T, groups), bool).at[
        jnp.arange(T)[:, None], order[:, :kept]].set(True)
    return jax.lax.top_k(jnp.where(jnp.repeat(inside, R // groups, axis=1),
                                   s, -jnp.inf), K)[1]


def _attention(x, w, d: dict, eps: float, inv, factor: float, scale: float,
               mode: str):
    """Latent attention over ``x`` [T, D] (T a multiple of ``BLOCK``),
    expanded: every head's keys and values from ``c_kv``; the queries a
    block at a time."""
    linear, rms_norm = reference.linear, reference.rms_norm
    T = x.shape[0]
    H, nope, C = d["H"], d["nope"], d["C"]
    c_q = rms_norm(linear(x, w["q_a"], mode), w["q_a_norm"], eps)
    q = linear(c_q, w["q_b"], mode).reshape(T, H, -1)
    kv = linear(x, w["kv_a"], mode)
    c_kv = rms_norm(kv[:, :C], w["kv_a_norm"], eps)
    k_r = _rotate(kv[:, None, C:], inv, factor)              # [T, 1, r]
    heads = linear(c_kv, w["kv_b"], mode).reshape(T, H, -1)
    k = jnp.concatenate([heads[..., :nope],
                         jnp.broadcast_to(k_r, (T, H, k_r.shape[-1]))],
                        axis=-1)
    v = heads[..., nope:]
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], inv, factor)],
                        axis=-1)

    def block(args):
        qb, start = args
        s = jnp.einsum("qhd,khd->hqk", qb, k,
                       precision=reference.HIGHEST) * scale
        seen = (start + jnp.arange(BLOCK))[:, None] >= jnp.arange(T)[None, :]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=reference.HIGHEST)

    a = jax.lax.map(block, (q.reshape(T // BLOCK, BLOCK, H, -1),
                            jnp.arange(T // BLOCK) * BLOCK))
    return linear(a.reshape(T, -1), w["o"], mode)


@partial(jax.jit, static_argnames=("dims", "eps", "rot", "first",
                                   "weight_scale", "norm_topk", "mode"))
def _layer(x, w, inv, *, dims: tuple, eps: float, rot: tuple, first: int,
           weight_scale: float, norm_topk: bool, mode: str):
    """One layer over ``x`` [T, D] → ``x``."""
    d = dict(dims)
    rms_norm = reference.rms_norm
    x = x + _attention(rms_norm(x, w["in_norm"], eps), w, d, eps, inv,
                       *rot, mode)
    h = rms_norm(x, w["post_norm"], eps)
    if "router" not in w:
        return x + _swiglu(h, w["gate"], w["up"], w["down"], mode)
    score = jax.nn.sigmoid(reference.linear(h, w["router"], "float32"))
    chosen = choose(score, d["groups"], d["kept"], d["K"])
    weight = jnp.take_along_axis(score, chosen, axis=1)
    if norm_topk:
        weight = weight / weight.sum(axis=1, keepdims=True)
    weight = weight * weight_scale
    m = _swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"], mode)

    def one_more(m, e_and_weights):     # the held experts, in turn
        e, gate, up, down = e_and_weights
        mine = jnp.where(chosen == first + e, weight, 0.0).sum(axis=1)
        return m + mine[:, None] * _swiglu(h, gate, up, down, mode), None

    m, _ = jax.lax.scan(one_more, m, (
        jnp.arange(w["experts_gate"].shape[0]), w["experts_gate"],
        w["experts_up"], w["experts_down"]))
    return x + m


def _load(ckpt, d: dict, i: int) -> dict:
    p = f"model.layers.{i}."
    names = {"in_norm": "input_layernorm",
             "post_norm": "post_attention_layernorm",
             "q_a": "self_attn.q_a_proj",
             "q_a_norm": "self_attn.q_a_layernorm",
             "q_b": "self_attn.q_b_proj",
             "kv_a": "self_attn.kv_a_proj_with_mqa",
             "kv_a_norm": "self_attn.kv_a_layernorm",
             "kv_b": "self_attn.kv_b_proj", "o": "self_attn.o_proj"}
    if d["sparse"][i]:
        names["router"] = "mlp.gate"
        names.update({f"shared_{x}": f"mlp.shared_experts.{x}_proj"
                      for x in ("gate", "up", "down")})
    else:
        names.update({x: f"mlp.{x}_proj" for x in ("gate", "up", "down")})
    w = {key: ckpt.tensor(f"{p}{name}.weight") for key, name in names.items()}
    if d["sparse"][i]:
        for x in ("gate", "up", "down"):
            w[f"experts_{x}"] = np.stack([
                ckpt.tensor(f"{p}mlp.experts.{e}.{x}_proj.weight")
                for e in range(d["first"], d["first"] + d["held"])])
    return w


def logits(ckpt, sequences: list[list[int]], wanted: list[range],
           mode: str = "float32") -> list[jax.Array]:
    """:func:`reference.logits` for this family: every position in the
    expanded form, no cache, one layer's weights on the device at a
    time."""
    cfg = ckpt.config
    d = _dims(cfg)
    eps = float(cfg["rms_norm_eps"])
    inv, factor, scale = yarn(cfg)
    kw = dict(dims=tuple((k, v) for k, v in d.items()
                         if not isinstance(v, list)),
              eps=eps, rot=(float(factor), float(scale)), first=d["first"],
              weight_scale=float(cfg.get("routed_scaling_factor", 1.0)),
              norm_topk=bool(cfg.get("norm_topk_prob", True)), mode=mode)
    inv = jnp.asarray(inv)
    xs = reference.embed(ckpt, "model.embed_tokens.weight", sequences)
    T = -(-max(x.shape[0] for x in xs) // LENGTH) * LENGTH
    xs = [jnp.pad(x, ((0, T - x.shape[0]), (0, 0))) for x in xs]
    for w in reference.layers_ahead(partial(_load, ckpt, d), d["L"]):
        xs = jax.block_until_ready([_layer(x, w, inv, **kw) for x in xs])
        del w
    norm = jax.device_put(ckpt.tensor("model.norm.weight"))
    head = jax.device_put(ckpt.tensor("lm_head.weight"))
    return reference.head_rows(xs, wanted, norm, head, eps=eps, mode=mode)


# --------------------------------------------------------------- the costs


def attention_weights(cfg: dict) -> int:
    """Matmul weights of one layer's attention: the two query projections,
    the latent's, the heads' keys and values from it, the output's."""
    d = _dims(cfg)
    H = d["H"]
    return d["D"] * d["Q"] + d["Q"] * H * (d["nope"] + d["rope"]) \
        + d["D"] * (d["C"] + d["rope"]) + d["C"] * H * (d["nope"] + d["vd"]) \
        + H * d["vd"] * d["D"]


def expert_weights(cfg: dict) -> int:
    """Matmul weights of one routed expert."""
    d = _dims(cfg)
    return 3 * d["D"] * d["F"]


def unrouted_weights(cfg: dict) -> int:
    """Matmul weights every token passes through, whatever it chose: the
    attention of every layer, the dense MLPs, the shared experts and the
    routers (not the head, not the embedding)."""
    d = _dims(cfg)
    n_sparse = sum(d["sparse"])
    return d["L"] * attention_weights(cfg) \
        + (d["L"] - n_sparse) * 3 * d["D"] * d["I"] \
        + n_sparse * (3 * d["D"] * d["F"] + d["D"] * d["router"])


def parameters(cfg: dict) -> int:
    """Everything held on this chip, the norms' weights too."""
    d = _dims(cfg)
    norms = d["L"] * (2 * d["D"] + d["Q"] + d["C"]) + d["D"]
    return unrouted_weights(cfg) \
        + sum(d["sparse"]) * d["held"] * expert_weights(cfg) \
        + 2 * d["V"] * d["D"] + norms


def position_bytes(cfg: dict, itemsize: int = 2) -> int:
    """What one cached position holds over all layers: ``[c_kv | k_r]``."""
    d = _dims(cfg)
    return d["L"] * (d["C"] + d["rope"]) * itemsize


def prefill_flops(cfg: dict, tokens: int) -> float:
    """Operations one prefill of ``tokens`` positions needs: 2 a weight a
    token through everything unrouted (the expanded form: every position's
    keys and values of every head from its latent) and through the
    expected ``K / ep_size`` held experts a token a sparse layer (a token's
    assignments land on a held expert with probability ``1 / ep_size``);
    attention over the causal half, scores of ``nope + rope`` and values of
    ``v_head_dim`` a head a pair; the head for one position."""
    d = _dims(cfg)
    T = tokens
    matmul = 2.0 * T * (unrouted_weights(cfg) + sum(d["sparse"])
                        * d["K"] / d["ep"] * expert_weights(cfg)) \
        + 2.0 * d["V"] * d["D"]
    pairs = d["L"] * T * (T + 1) / 2
    return matmul + 2.0 * (d["nope"] + d["rope"] + d["vd"]) * d["H"] * pairs


def decode_bytes(cfg: dict, steps: list[dict], lengths: list[int],
                 itemsize: int = 2) -> float:
    """Bytes the decode steps must read: a step, everything unrouted and
    the head once and each held expert that was hit once (``experts_hit``,
    summed over the sparse layers, on the step's span); a decoded token,
    the one cached vector a layer of each position behind it."""
    d = _dims(cfg)
    fixed = (unrouted_weights(cfg) + d["V"] * d["D"]) * itemsize
    hit = sum(int(s.get("experts_hit", 0)) for s in steps)
    return float(len(steps)) * fixed \
        + float(hit) * expert_weights(cfg) * itemsize \
        + float(sum(lengths)) * position_bytes(cfg, itemsize)


# ------------------------------------------------------- the family's reader


def latent_share(obs, span: str, attr: str):
    """The latent page's bytes the window's decode steps read (``attr`` of
    every ``span``, as the program names it: filled positions times what
    every layer keeps of one) over the bytes those steps must move in all
    (:func:`decode_bytes`), in percent. None where the program names no
    such bytes."""
    steps = [s.get("attrs", {}) for s in obs.window_spans(span)]
    moved = sum(a[attr] for a in steps if attr in a)
    if not moved:
        return None
    # as readers.decode_bytes_roofline: token k of a request (k >= 2) came
    # from a step that read its prompt and the k - 2 tokens fed before it
    lengths = [len(r.prompt) + k - 1
               for r in obs.records
               for k, t in enumerate(r.times) if k >= 1
               and obs.t0 <= t <= obs.t1]
    return 100.0 * moved / decode_bytes(obs.model, steps, lengths)
