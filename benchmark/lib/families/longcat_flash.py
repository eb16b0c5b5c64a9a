"""The LongCat-Flash family (``model_type`` ``longcat_flash``: the language
model of LongCat-Flash-Omni), as one chip's share of an expert-parallel
replica holds it.

For a row ``x`` of the residual, every norm an RMSNorm with a learned
weight (``rms_norm_eps``); ``N_i``, ``P_i`` the input and post-attention
norms of sublayer ``i``::

    a = x + Attn_0(N_0(x))          m = MoE(P_0(a))
    b = a + FFN_0(P_0(a))
    c = b + Attn_1(N_1(b))
    y = c + FFN_1(P_1(c)) + m

a final RMSNorm and the untied head. ``FFN_i(u) = W_down,i (silu(W_gate,i
u) * W_up,i u)`` of ``ffn_hidden_size``. The expert layer reads what the
first dense block reads, and its result enters an attention and a dense
block later (the shortcut).

- **Latent attention**, each of the two a layer with its own weights,
  written here in its *expanded* form at every position (the program
  decodes in the absorbed form, other algebra over the same weights).
  ``c_q = RMSNorm(W_qa u)`` (``q_lora_rank``); head ``h`` of ``H``:
  ``[q_nope_h | q_rope_h] = s_q W_qb c_q`` (``qk_nope_head_dim`` |
  ``qk_rope_head_dim``), ``s_q = (hidden / q_lora_rank) ** 0.5`` under
  ``mla_scale_q_lora``. ``[c_kv | k_r] = W_kva u`` (``kv_lora_rank`` |
  ``qk_rope_head_dim``), ``c_kv <- s_kv RMSNorm(c_kv)``, ``s_kv = (hidden /
  kv_lora_rank) ** 0.5`` under ``mla_scale_kv_lora`` (``k_r`` is not
  scaled); ``[k_nope_h | v_h] = W_kvb c_kv`` (``qk_nope_head_dim`` |
  ``v_head_dim``, a head's key rows before its value rows). The scales are
  multiplied here, where the equations put them (the program folds them
  into the two norms' weights). ``q_rope_h`` and ``k_r`` (one for all
  heads) are rotated: adjacent columns ``(2j, 2j + 1)`` a pair, pair ``j``
  turning ``rope_theta ** (-2j / r)`` a position, no scaling of positions.
  ``s_h(t, u) = (nope + rope) ** -0.5 (q_nope_h(t) k_nope_h(u) + q_rope_h(t)
  k_r(u))``, causal softmax in float32, ``o_h = sum p_h v_h``, ``Attn = W_o
  [o_1 .. o_H]``.
- **Expert layer**, ``u = P_0(a)``: ``p = softmax(W_r u)`` in float32 in
  every mode over the whole router, ``n_routed_experts * ep_size`` routed
  experts and then ``zero_expert_num`` identity experts; the ``moe_topk``
  largest of ``p + bias`` are chosen (the bias enters the choice only; a
  tie to the lower index); ``w_e = routed_scaling_factor p_e`` for the
  chosen, not renormalised; ``MoE(u) = sum(chosen, routed, held) w_e E_e(u)
  + u sum(chosen, identity) w_e``, ``E_e`` a SwiGLU of
  ``expert_ffn_hidden_size``: a plain loop over the chosen, with the
  identity branch.

**The share.** ``n_routed_experts`` is how many routed experts are held;
the held ones are ``[ep_rank * n_routed_experts, (ep_rank + 1) *
n_routed_experts)``. What the absent experts would add is left out here as
in the program; the identity part is whole (every chip computes it for its
own tokens, once).

The costs count what the mathematics needs. A prefill expands (scores of
192 and values of 128 a head over the causal half, two sublayers a
layer); a decode step reads the weights once, the held experts its
``experts_hit`` attribute says were hit, and one vector of ``kv_lora_rank +
qk_rope_head_dim`` a sublayer a cached position of each row. An identity
assignment costs nothing.

Imports nothing of the program under test.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import reference
from . import Filled

#: queries a block of the reference's attention: 64 heads of float32
#: scores over 2 048 keys are then 0.13 GB
BLOCK = 256
#: every sequence of a call is padded to the longest of them, rounded up to
#: a multiple of this many positions: one compiled length (the cell's
#: sequences of 1 025 to 2 048 positions would otherwise compile five)
LENGTH = 1024


def _dims(cfg: dict) -> dict:
    held, ep = cfg["n_routed_experts"], cfg.get("ep_size", 1)
    D = cfg["hidden_size"]
    return dict(
        D=D, H=cfg["num_attention_heads"], L=cfg["num_layers"],
        V=cfg["vocab_size"], I=cfg["ffn_hidden_size"],
        F=cfg["expert_ffn_hidden_size"], Q=cfg["q_lora_rank"],
        C=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], vd=cfg["v_head_dim"],
        held=held, ep=ep, routed=held * ep,
        zero=cfg.get("zero_expert_num", 0),
        router=held * ep + cfg.get("zero_expert_num", 0),
        first=cfg.get("ep_rank", 0) * held, K=cfg["moe_topk"],
        s_q=(D / cfg["q_lora_rank"]) ** 0.5
        if cfg.get("mla_scale_q_lora") else 1.0,
        s_kv=(D / cfg["kv_lora_rank"]) ** 0.5
        if cfg.get("mla_scale_kv_lora") else 1.0)


def rehearsal(config: dict) -> dict:
    """The toy the rehearsal swaps in: four double layers, 4 heads over a
    latent of 32 | 8, a quarter of 16 routed experts held beside 8 identity
    ones, 6 a token. The second group are the aliases the configuration's
    file keeps for the EXAONE family's reader."""
    return {"hidden_size": 128, "ffn_hidden_size": 256,
            "expert_ffn_hidden_size": 64, "num_layers": 4,
            "num_attention_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
            "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
            "vocab_size": 512, "n_routed_experts": 4, "zero_expert_num": 8,
            "moe_topk": 6, "ep_size": 4, "ep_rank": 1,
            "num_hidden_layers": 4, "num_key_value_heads": 4,
            "intermediate_size": 256, "moe_intermediate_size": 64,
            "num_experts": 4, "num_experts_per_tok": 6}


# ------------------------------------------------------------- the weights


def tensors(config: dict) -> dict[str, Filled]:
    """The LongCat-Flash style of names in the checkpoint's order: a
    layer's two sublayers under ``self_attn.<i>``, ``input_layernorm.<i>``,
    ``post_attention_layernorm.<i>`` and ``mlps.<i>``, its expert layer
    under ``mlp``. A matrix is N(0, 1/fan_in) over its ``[out, in]``
    layout's inputs, a norm ones, the selection bias zeros; the embedding's
    fan-in is 1 (a row is selected, nothing is summed). **``q_b_proj`` and
    ``kv_b_proj`` are filled at the fan-in their input's scale gives them**,
    ``rank * s ** 2`` (the hidden size under ``mla_scale_q_lora`` /
    ``mla_scale_kv_lora``, the rank without): the scale makes the
    normalised latent as large as a vector of the hidden size spread over
    ``rank`` columns, so queries, keys and values come out at unit
    variance and the scores at about 1, as in every other family. Filled
    at the rank instead, the scores' spread is 5.8, every attention an
    argmax, and at the published widths on the chip the reference's own
    bfloat16 mode lies 1.5 logits from its float32 and chooses another
    token 60 % of the time: nothing could then tell a sound program from a
    broken one (PERF.md, Findings, PR 44). The held experts are one tensor
    a projection an expert, under their index in the whole layer; an
    identity expert has no tensor."""
    d = _dims(config)
    D, H = d["D"], d["H"]

    def matrix(out: int, fan_in: int) -> Filled:
        return Filled((out, fan_in), "normal", fan_in)

    def swiglu(prefix: str, width: int) -> dict:
        return {prefix + "gate_proj.weight": matrix(width, D),
                prefix + "up_proj.weight": matrix(width, D),
                prefix + "down_proj.weight": matrix(D, width)}

    table = {"model.embed_tokens.weight": Filled((d["V"], D), "normal", 1)}
    for li in range(d["L"]):
        p = f"model.layers.{li}."
        for i in range(2):
            a = f"{p}self_attn.{i}."
            table.update({
                f"{p}input_layernorm.{i}.weight": Filled((D,), "ones"),
                a + "q_a_proj.weight": matrix(d["Q"], D),
                a + "q_a_layernorm.weight": Filled((d["Q"],), "ones"),
                a + "q_b_proj.weight": Filled(
                    (H * (d["nope"] + d["rope"]), d["Q"]), "normal",
                    round(d["Q"] * d["s_q"] ** 2)),
                a + "kv_a_proj_with_mqa.weight": matrix(d["C"] + d["rope"],
                                                        D),
                a + "kv_a_layernorm.weight": Filled((d["C"],), "ones"),
                a + "kv_b_proj.weight": Filled(
                    (H * (d["nope"] + d["vd"]), d["C"]), "normal",
                    round(d["C"] * d["s_kv"] ** 2)),
                a + "o_proj.weight": matrix(D, H * d["vd"]),
                f"{p}post_attention_layernorm.{i}.weight":
                    Filled((D,), "ones"),
            })
            table.update(swiglu(f"{p}mlps.{i}.", d["I"]))
        table[p + "mlp.router.classifier.weight"] = matrix(d["router"], D)
        table[p + "mlp.router.e_score_correction_bias"] = Filled(
            (d["router"],), "zeros")
        for e in range(d["first"], d["first"] + d["held"]):
            table.update(swiglu(f"{p}mlp.experts.{e}.", d["F"]))
    table.update({"model.norm.weight": Filled((D,), "ones"),
                  "lm_head.weight": matrix(d["V"], D)})
    return table


# ----------------------------------------------------------- the reference


def frequencies(cfg: dict) -> np.ndarray:
    """The inverse frequency of each rotary column pair: plain rotary."""
    r = cfg["qk_rope_head_dim"]
    return (float(cfg["rope_theta"])
            ** (-2.0 * np.arange(r // 2, dtype=np.float64) / r)
            ).astype(np.float32)


def _rotate(x, inv):
    """``x`` [T, h, r] at positions 0..T-1, column pairs ``(2j, 2j + 1)``
    turned where they lie."""
    T = x.shape[0]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _swiglu(x, gate, up, down, mode: str):
    linear = reference.linear
    return linear(jax.nn.silu(linear(x, gate, mode)) * linear(x, up, mode),
                  down, mode)


def route(logits, bias, K: int, scaling: float):
    """The router's float32 ``logits`` [T, router] → ``(chosen [T, K],
    weights [T, K])``: softmax over routed and identity experts alike, the
    K largest of ``p + bias`` by a stable sort (a tie to the lower index),
    ``scaling * p`` of the chosen, not renormalised."""
    p = jax.nn.softmax(logits, axis=-1)
    chosen = jnp.argsort(-(p + bias), axis=1, stable=True)[:, :K]
    return chosen, jnp.take_along_axis(p, chosen, axis=1) * scaling


def moe(u, w, d: dict, scaling: float, mode: str):
    """The expert layer over ``u`` [T, D]: every chosen expert in turn, a
    held routed one through its SwiGLU, an identity one as ``u`` itself,
    an absent one left out."""
    chosen, weight = route(reference.linear(u, w["router"], "float32"),
                           w["router_bias"].astype(jnp.float32), d["K"],
                           scaling)
    m = jnp.zeros_like(u)
    for k in range(d["K"]):
        e, mine = chosen[:, k], weight[:, k]
        m = m + jnp.where(e >= d["routed"], mine, 0.0)[:, None] * u

    def one_more(m, e_and_weights):     # the held experts, in turn
        e, gate, up, down = e_and_weights
        mine = jnp.where(chosen == d["first"] + e, weight, 0.0).sum(axis=1)
        return m + mine[:, None] * _swiglu(u, gate, up, down, mode), None

    m, _ = jax.lax.scan(one_more, m, (
        jnp.arange(w["experts_gate"].shape[0]), w["experts_gate"],
        w["experts_up"], w["experts_down"]))
    return m


def _attention(x, w, d: dict, eps: float, inv, mode: str):
    """Latent attention over ``x`` [T, D] (T a multiple of ``BLOCK``),
    expanded: every head's keys and values from ``c_kv``; the queries a
    block at a time."""
    linear, rms_norm = reference.linear, reference.rms_norm
    T = x.shape[0]
    H, nope, C = d["H"], d["nope"], d["C"]
    c_q = rms_norm(linear(x, w["q_a"], mode), w["q_a_norm"], eps)
    q = (d["s_q"] * linear(c_q, w["q_b"], mode)).reshape(T, H, -1)
    kv = linear(x, w["kv_a"], mode)
    c_kv = d["s_kv"] * rms_norm(kv[:, :C], w["kv_a_norm"], eps)
    k_r = _rotate(kv[:, None, C:], inv)                      # [T, 1, r]
    heads = linear(c_kv, w["kv_b"], mode).reshape(T, H, -1)
    k = jnp.concatenate([heads[..., :nope],
                         jnp.broadcast_to(k_r, (T, H, k_r.shape[-1]))],
                        axis=-1)
    v = heads[..., nope:]
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], inv)],
                        axis=-1)
    scale = (nope + d["rope"]) ** -0.5

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


def layer_parts(x, w, inv, *, dims: tuple, eps: float, scaling: float,
                mode: str):
    """One layer over ``x`` [T, D] → ``(a, m, b, c, y)`` of the equations
    at the top of this file."""
    d = dict(dims)
    rms_norm = reference.rms_norm
    s0, s1 = w["sub"]
    a = x + _attention(rms_norm(x, s0["in_norm"], eps), s0, d, eps, inv,
                       mode)
    u = rms_norm(a, s0["post_norm"], eps)
    m = moe(u, w, d, scaling, mode)
    b = a + _swiglu(u, s0["gate"], s0["up"], s0["down"], mode)
    c = b + _attention(rms_norm(b, s1["in_norm"], eps), s1, d, eps, inv,
                       mode)
    y = c + _swiglu(rms_norm(c, s1["post_norm"], eps), s1["gate"], s1["up"],
                    s1["down"], mode) + m
    return a, m, b, c, y


@partial(jax.jit, static_argnames=("dims", "eps", "scaling", "mode"))
def _layer(x, w, inv, **kw):
    return layer_parts(x, w, inv, **kw)[-1]


def _load(ckpt, d: dict, li: int) -> dict:
    p = f"model.layers.{li}."

    def sub(i: int) -> dict:
        names = {"in_norm": f"input_layernorm.{i}",
                 "post_norm": f"post_attention_layernorm.{i}",
                 "q_a": f"self_attn.{i}.q_a_proj",
                 "q_a_norm": f"self_attn.{i}.q_a_layernorm",
                 "q_b": f"self_attn.{i}.q_b_proj",
                 "kv_a": f"self_attn.{i}.kv_a_proj_with_mqa",
                 "kv_a_norm": f"self_attn.{i}.kv_a_layernorm",
                 "kv_b": f"self_attn.{i}.kv_b_proj",
                 "o": f"self_attn.{i}.o_proj",
                 **{x: f"mlps.{i}.{x}_proj" for x in ("gate", "up", "down")}}
        return {key: ckpt.tensor(f"{p}{name}.weight")
                for key, name in names.items()}

    w = {"sub": [sub(0), sub(1)],
         "router": ckpt.tensor(p + "mlp.router.classifier.weight"),
         "router_bias": ckpt.tensor(
             p + "mlp.router.e_score_correction_bias")}
    for x in ("gate", "up", "down"):
        w[f"experts_{x}"] = np.stack([
            ckpt.tensor(f"{p}mlp.experts.{e}.{x}_proj.weight")
            for e in range(d["first"], d["first"] + d["held"])])
    return w


def _static(cfg: dict, d: dict, mode: str) -> dict:
    return dict(dims=tuple(d.items()), eps=float(cfg["rms_norm_eps"]),
                scaling=float(cfg.get("routed_scaling_factor", 1.0)),
                mode=mode)


def logits(ckpt, sequences: list[list[int]], wanted: list[range],
           mode: str = "float32") -> list[jax.Array]:
    """:func:`reference.logits` for this family: every position in the
    expanded form, no cache, one layer's weights on the device at a
    time."""
    cfg = ckpt.config
    d = _dims(cfg)
    kw = _static(cfg, d, mode)
    inv = jnp.asarray(frequencies(cfg))
    xs = reference.embed(ckpt, "model.embed_tokens.weight", sequences)
    T = -(-max(x.shape[0] for x in xs) // LENGTH) * LENGTH
    xs = [jnp.pad(x, ((0, T - x.shape[0]), (0, 0))) for x in xs]
    for w in reference.layers_ahead(partial(_load, ckpt, d), d["L"]):
        xs = jax.block_until_ready([_layer(x, w, inv, **kw) for x in xs])
        del w
    norm = jax.device_put(ckpt.tensor("model.norm.weight"))
    head = jax.device_put(ckpt.tensor("lm_head.weight"))
    return reference.head_rows(xs, wanted, norm, head, eps=kw["eps"],
                               mode=mode)


# --------------------------------------------------------------- the costs


def attention_weights(cfg: dict) -> int:
    """Matmul weights of one attention sublayer: the two query
    projections, the latent's, the heads' keys and values from it, the
    output's."""
    d = _dims(cfg)
    H = d["H"]
    return d["D"] * d["Q"] + d["Q"] * H * (d["nope"] + d["rope"]) \
        + d["D"] * (d["C"] + d["rope"]) + d["C"] * H * (d["nope"] + d["vd"]) \
        + H * d["vd"] * d["D"]


def expert_weights(cfg: dict) -> int:
    """Matmul weights of one routed expert (an identity expert has none)."""
    d = _dims(cfg)
    return 3 * d["D"] * d["F"]


def unrouted_weights(cfg: dict) -> int:
    """Matmul weights every token passes through, whatever it chose: the
    two attentions and the two dense blocks of every layer and its router
    (not the head, not the embedding)."""
    d = _dims(cfg)
    return d["L"] * (2 * attention_weights(cfg) + 2 * 3 * d["D"] * d["I"]
                     + d["D"] * d["router"])


def parameters(cfg: dict) -> int:
    """Everything held on this chip, the norms' weights and the selection
    bias too."""
    d = _dims(cfg)
    small = d["L"] * (2 * (2 * d["D"] + d["Q"] + d["C"]) + d["router"]) \
        + d["D"]
    return unrouted_weights(cfg) + d["L"] * d["held"] * expert_weights(cfg) \
        + 2 * d["V"] * d["D"] + small


def position_bytes(cfg: dict, itemsize: int = 2) -> int:
    """What one cached position holds over all sublayers: ``[c_kv | k_r]``
    of each of the two a layer."""
    d = _dims(cfg)
    return 2 * d["L"] * (d["C"] + d["rope"]) * itemsize


def prefill_flops(cfg: dict, tokens: int) -> float:
    """Operations one prefill of ``tokens`` positions needs: 2 a weight a
    token through everything unrouted (the expanded form) and through the
    expected ``K * held / router`` held experts a token a layer (an
    assignment lands on each of the router's outputs alike; one that lands
    on an identity expert costs nothing); attention over the causal half of
    two sublayers a layer, scores of ``nope + rope`` and values of
    ``v_head_dim`` a head a pair; the head for one position."""
    d = _dims(cfg)
    T = tokens
    matmul = 2.0 * T * (unrouted_weights(cfg) + d["L"] * d["K"] * d["held"]
                        / d["router"] * expert_weights(cfg)) \
        + 2.0 * d["V"] * d["D"]
    pairs = 2 * d["L"] * T * (T + 1) / 2
    return matmul + 2.0 * (d["nope"] + d["rope"] + d["vd"]) * d["H"] * pairs


def decode_bytes(cfg: dict, steps: list[dict], lengths: list[int],
                 itemsize: int = 2) -> float:
    """Bytes the decode steps must read: a step, everything unrouted and
    the head once and each held expert that was hit once (``experts_hit``,
    summed over the layers, on the step's span); a decoded token, the one
    cached vector a sublayer of each position behind it."""
    d = _dims(cfg)
    fixed = (unrouted_weights(cfg) + d["V"] * d["D"]) * itemsize
    hit = sum(int(s.get("experts_hit", 0)) for s in steps)
    return float(len(steps)) * fixed \
        + float(hit) * expert_weights(cfg) * itemsize \
        + float(sum(lengths)) * position_bytes(cfg, itemsize)


# ------------------------------------------------------- the family's reader


def zero_share(obs, span: str):
    """Of the assignments the window's ``span``s made (``assignments``:
    live rows times ``moe_topk`` times the layers), the share that fell on
    an identity expert (``zero_tokens``), in percent. None where the
    program names no such counts."""
    steps = [a for a in (s.get("attrs", {}) for s in obs.window_spans(span))
             if "assignments" in a and "zero_tokens" in a]
    made = sum(a["assignments"] for a in steps)
    if not made:
        return None
    return 100.0 * sum(a["zero_tokens"] for a in steps) / made
