"""The Qwen3-Next family (``model_type`` ``qwen3_next``), as one chip's share
of an expert-parallel replica holds it.

For layer input ``x`` [T, D]. Every norm but one is *zero-centred*: ``x̂ · (1
+ w)`` with ``x̂ = x / sqrt(mean(x²) + eps)`` (:func:`zc_norm`).

- ``x += mixer(norm(x))``; ``x += moe(norm(x))``; a final norm and the untied
  head. Layer ``i`` is a ``full_attention`` layer when ``(i + 1) %
  full_attention_interval == 0``, else a ``linear_attention`` (Gated DeltaNet,
  GDN) layer.
- **GDN mixer**, ``Hk`` key heads of ``dk`` and ``Hv`` value heads of ``dv``,
  ``r = Hv / Hk`` value heads a key head. ``in_proj_qkvz`` (``2 Hk dk + 2 Hv
  dv`` outputs) lies a key head at a time: ``q`` [dk], ``k`` [dk], ``v`` [r
  dv], ``z`` [r dv] of head 0, then head 1; ``in_proj_ba`` likewise ``b`` [r],
  ``a`` [r]. ``[q | k | v]`` of all heads (``2 Hk dk + Hv dv`` channels) go
  through a causal depthwise convolution (``conv1d.weight`` [channels, 1,
  kernel], no bias: ``y_t = Σ_i w[:, 0, i] x_{t − kernel + 1 + i}``) and a
  SiLU. ``β = sigmoid(b)``, ``g = −exp(A_log) · softplus(a + dt_bias)``, one a
  value head. ``q``, ``k`` are L2-normalised over the head (``x / sqrt(Σx² +
  1e-6)``), ``q`` scaled by ``dk^-0.5``; value head ``h`` uses key head ``h //
  r``. A value head's state ``S`` [dk, dv] starts at zero and, a token: ``S ←
  e^{g_t} S``; ``S ← S + k_t ⊗ β_t (v_t − Sᵀ k_t)``; ``o_t = Sᵀ q_t``. Output:
  ``out_proj((o_t / sqrt(mean(o_t²) + eps) · w_norm) ⊙ silu(z_t))``, that norm
  over the ``dv`` of a head with a plain weight.
- **Gated attention mixer**: ``q_proj`` (``2 H hd`` outputs) lies a head at a
  time, the head's query [hd] then its gate [hd]; q and k get a zero-centred
  RMSNorm over the head; the first ``partial_rotary_factor · hd`` of a head is
  rotated (RoPE, rotate-half inside that part, ``rope_theta``); causal
  softmax over scores ``q k / sqrt(hd)``; ``o_proj(attn ⊙ sigmoid(gate))``.
- **Expert layer**, every layer: ``p = softmax(x W_r)`` over the whole router,
  in float32 in every mode; the ``num_experts_per_tok`` largest are chosen,
  their ``p`` renormalised to sum 1 (``norm_topk_prob``); ``moe(x) =
  sum(chosen, held) w_e E_e(x) + sigmoid(x · w_sg) S(x)``, ``E_e`` and ``S``
  SwiGLUs of ``moe_intermediate_size`` / ``shared_expert_intermediate_size``.

**The share.** ``num_experts`` is how many experts are held; the router is
``num_experts * ep_size`` wide, and the held experts are ``[ep_rank *
num_experts, (ep_rank + 1) * num_experts)`` (top-level ``ep_size``,
``ep_rank`` of the configuration, which the program reads from the same
``config.json``). What the absent experts would add is left out here as in
the program, and the partial result goes on to the next layer. The
multi-token-prediction layer is left out (it changes no next-token logit).

The reference runs the recurrence one token at a time and attention as one
masked softmax, the queries taken in blocks so that the scores fit.

The costs count what the mathematics needs. A prefill's recurrence is
counted chunk-wise, ``CHUNK`` positions a chunk, as every fast form of it
runs (the products inside a chunk, the triangular solve by substitution,
three products with the state a chunk); its routed operations are the
expected ones (a token's assignments fall on held experts with probability
``1 / ep_size``). A decode step reads the weights once (the held experts its
``experts_hit`` attribute says were hit), reads and writes each row's slot
(every GDN layer's states in ``STATE_BYTES`` a number and the convolution's
``kernel − 1`` last inputs), and reads the filled pages of the full layers.

Imports nothing of the program under test.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import reference
from . import Filled

#: sequences whose bfloat16 forward is run beside the float32 one, to count
#: the top-k choices the two precisions make differently
SHADOWED = 2
#: positions a chunk of the prefill's recurrence is counted with
CHUNK = 64
#: the recurrent state is kept in float32
STATE_BYTES = 4
#: queries a block of the reference's attention
BLOCK = 256


def _dims(cfg: dict) -> dict:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    L = cfg["num_hidden_layers"]
    every = cfg.get("full_attention_interval", 4)
    kinds = list(cfg.get("layer_types") or [
        "full_attention" if (i + 1) % every == 0 else "linear_attention"
        for i in range(L)])[:L]
    held, ep = cfg["num_experts"], cfg.get("ep_size", 1)
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    hd = cfg.get("head_dim") or D // H
    return dict(
        D=D, H=H, Hkv=cfg["num_key_value_heads"], hd=hd, L=L,
        V=cfg["vocab_size"], F=cfg["moe_intermediate_size"],
        Fs=cfg["shared_expert_intermediate_size"], held=held,
        router=held * ep, ep=ep, first=cfg.get("ep_rank", 0) * held,
        K=cfg["num_experts_per_tok"], Hk=Hk, Hv=Hv, dk=dk, dv=dv,
        r=Hv // Hk, kernel=cfg["linear_conv_kernel_dim"],
        C=2 * Hk * dk + Hv * dv, Z=Hv * dv,
        rot=int(hd * cfg.get("partial_rotary_factor", 1.0)),
        full=[kind == "full_attention" for kind in kinds])


def rehearsal(config: dict) -> dict:
    """The toy the rehearsal swaps in: two periods, a quarter of 16 experts
    held."""
    return {"hidden_size": 128, "num_hidden_layers": 8,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 32, "linear_num_key_heads": 2,
            "linear_num_value_heads": 4, "linear_key_head_dim": 32,
            "linear_value_head_dim": 32, "moe_intermediate_size": 64,
            "shared_expert_intermediate_size": 64, "vocab_size": 512,
            "num_experts": 4, "num_experts_per_tok": 4, "ep_size": 4,
            "ep_rank": 1}


# ------------------------------------------------------------- the weights


def tensors(config: dict) -> dict[str, Filled]:
    """Hugging Face's names in the checkpoint's order. A matrix is N(0,
    1/fan_in) over its ``[out, in]`` layout's inputs (the convolution's
    fan-in is its kernel, the embedding's 1: a row is selected, nothing is
    summed, so the first layer's input is of unit scale); the weights of
    the zero-centred norms and ``A_log`` zeros; ``dt_bias`` and the gated
    norm's plain weight ones. The experts are one tensor a projection an
    expert, under their index in the whole layer."""
    d = _dims(config)
    D, hd = d["D"], d["hd"]

    def matrix(out: int, fan_in: int) -> Filled:
        return Filled((out, fan_in), "normal", fan_in)

    def swiglu(prefix: str, width: int) -> dict:
        return {prefix + "gate_proj.weight": matrix(width, D),
                prefix + "up_proj.weight": matrix(width, D),
                prefix + "down_proj.weight": matrix(D, width)}

    table = {"model.embed_tokens.weight": Filled((d["V"], D), "normal", 1)}
    for i, full in enumerate(d["full"]):
        p = f"model.layers.{i}."
        table[p + "input_layernorm.weight"] = Filled((D,), "zeros")
        if full:
            table.update({
                p + "self_attn.q_proj.weight": matrix(2 * d["H"] * hd, D),
                p + "self_attn.k_proj.weight": matrix(d["Hkv"] * hd, D),
                p + "self_attn.v_proj.weight": matrix(d["Hkv"] * hd, D),
                p + "self_attn.o_proj.weight": matrix(D, d["H"] * hd),
                p + "self_attn.q_norm.weight": Filled((hd,), "zeros"),
                p + "self_attn.k_norm.weight": Filled((hd,), "zeros"),
            })
        else:
            table.update({
                p + "linear_attn.in_proj_qkvz.weight":
                    matrix(d["C"] + d["Z"], D),
                p + "linear_attn.in_proj_ba.weight": matrix(2 * d["Hv"], D),
                p + "linear_attn.conv1d.weight": Filled(
                    (d["C"], 1, d["kernel"]), "normal", d["kernel"]),
                p + "linear_attn.dt_bias": Filled((d["Hv"],), "ones"),
                p + "linear_attn.A_log": Filled((d["Hv"],), "zeros"),
                p + "linear_attn.norm.weight": Filled((d["dv"],), "ones"),
                p + "linear_attn.out_proj.weight": matrix(D, d["Z"]),
            })
        table[p + "post_attention_layernorm.weight"] = Filled((D,), "zeros")
        table[p + "mlp.gate.weight"] = matrix(d["router"], D)
        for e in range(d["first"], d["first"] + d["held"]):
            table.update(swiglu(f"{p}mlp.experts.{e}.", d["F"]))
        table.update(swiglu(p + "mlp.shared_expert.", d["Fs"]))
        table[p + "mlp.shared_expert_gate.weight"] = matrix(1, D)
    table.update({"model.norm.weight": Filled((D,), "zeros"),
                  "lm_head.weight": matrix(d["V"], D)})
    return table


# ----------------------------------------------------------- the reference


def zc_norm(x, w, eps: float):
    """The zero-centred RMSNorm: ``x̂ · (1 + w)``."""
    return reference.rms_norm(x, 1.0 + w.astype(jnp.float32), eps)


def _swiglu(x, gate, up, down, mode: str):
    linear = reference.linear
    return linear(jax.nn.silu(linear(x, gate, mode)) * linear(x, up, mode),
                  down, mode)


def _gdn(x, w, d: dict, eps: float, mode: str):
    """The Gated DeltaNet mixer over ``x`` [T, D], a token at a time."""
    T = x.shape[0]
    Hk, Hv, dk, dv, r = d["Hk"], d["Hv"], d["dk"], d["dv"], d["r"]
    qkvz = reference.linear(x, w["qkvz"], mode).reshape(
        T, Hk, 2 * dk + 2 * r * dv)
    ba = reference.linear(x, w["ba"], mode).reshape(T, Hk, 2 * r)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv]
    z = qkvz[..., 2 * dk + r * dv:].reshape(T, Hv, dv)
    b, a = ba[..., :r].reshape(T, Hv), ba[..., r:].reshape(T, Hv)
    mixed = jnp.concatenate([q.reshape(T, -1), k.reshape(T, -1),
                             v.reshape(T, -1)], axis=1)         # [T, C]
    taps = w["conv"].astype(jnp.float32)[:, 0, :]               # [C, kernel]
    kernel = taps.shape[1]
    padded = jnp.pad(mixed, ((kernel - 1, 0), (0, 0)))
    act = jax.nn.silu(sum(padded[i:i + T] * taps[:, i]
                          for i in range(kernel)))

    def l2(y):
        return y * jax.lax.rsqrt((y * y).sum(axis=-1, keepdims=True) + 1e-6)

    q = jnp.repeat(l2(act[:, :Hk * dk].reshape(T, Hk, dk)) * dk ** -0.5, r,
                   axis=1)
    k = jnp.repeat(l2(act[:, Hk * dk:2 * Hk * dk].reshape(T, Hk, dk)), r,
                   axis=1)
    v = act[:, 2 * Hk * dk:].reshape(T, Hv, dv)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(w["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        a + w["dt_bias"].astype(jnp.float32))

    def token(S, t):    # S [Hv, dk, dv]
        q_t, k_t, v_t, g_t, beta_t = t
        S = S * jnp.exp(g_t)[:, None, None]
        delta = (v_t - (S * k_t[:, :, None]).sum(axis=1)) * beta_t[:, None]
        S = S + k_t[:, :, None] * delta[:, None, :]
        return S, (S * q_t[:, :, None]).sum(axis=1)

    _S, o = jax.lax.scan(token, jnp.zeros((Hv, dk, dv), jnp.float32),
                         (q, k, v, g, beta))
    o = reference.rms_norm(o, w["gdn_norm"], eps) * jax.nn.silu(z)
    return reference.linear(o.reshape(T, Hv * dv), w["out"], mode)


def _attention(x, w, d: dict, eps: float, theta: float, mode: str):
    """The gated attention mixer over ``x`` [T, D] (T a multiple of
    ``BLOCK``), the queries a block at a time."""
    T = x.shape[0]
    H, Hkv, hd, rot = d["H"], d["Hkv"], d["hd"], d["rot"]
    qg = reference.linear(x, w["q"], mode).reshape(T, H, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:].reshape(T, H * hd)
    k = reference.linear(x, w["k"], mode).reshape(T, Hkv, hd)
    v = reference.linear(x, w["v"], mode).reshape(T, Hkv, hd)
    q, k = zc_norm(q, w["q_norm"], eps), zc_norm(k, w["k_norm"], eps)

    def rotated(y):
        return jnp.concatenate([reference.rope(y[..., :rot], theta),
                                y[..., rot:]], axis=-1)

    q = rotated(q)
    k = jnp.repeat(rotated(k), H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)

    def block(args):
        qb, start = args
        s = jnp.einsum("qhd,khd->hqk", qb, k,
                       precision=reference.HIGHEST) / np.sqrt(hd)
        seen = (start + jnp.arange(BLOCK))[:, None] >= jnp.arange(T)[None, :]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=reference.HIGHEST)

    a = jax.lax.map(block, (q.reshape(T // BLOCK, BLOCK, H, hd),
                            jnp.arange(T // BLOCK) * BLOCK))
    return reference.linear(a.reshape(T, H * hd) * jax.nn.sigmoid(gate),
                            w["o"], mode)


@partial(jax.jit, static_argnames=("dims", "eps", "theta", "first",
                                   "norm_topk", "mode"))
def _layer(x, w, *, dims: tuple, eps: float, theta: float, first: int,
           norm_topk: bool, mode: str):
    """One layer over ``x`` [T, D] → ``(x, chosen [T, K])``."""
    d = dict(dims)
    h = zc_norm(x, w["in_norm"], eps)
    x = x + (_attention(h, w, d, eps, theta, mode) if "q" in w
             else _gdn(h, w, d, eps, mode))
    h = zc_norm(x, w["post_norm"], eps)
    p = jax.nn.softmax(reference.linear(h, w["router"], "float32"), axis=-1)
    weight, chosen = jax.lax.top_k(p, d["K"])
    if norm_topk:
        weight = weight / weight.sum(axis=1, keepdims=True)
    m = _swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"], mode) \
        * jax.nn.sigmoid(reference.linear(h, w["shared_expert_gate"], mode))

    def one_more(m, e_and_weights):     # the held experts, in turn
        e, gate, up, down = e_and_weights
        mine = jnp.where(chosen == first + e, weight, 0.0).sum(axis=1)
        return m + mine[:, None] * _swiglu(h, gate, up, down, mode), None

    held = w["experts_gate"].shape[0]
    m, _ = jax.lax.scan(one_more, m, (
        jnp.arange(held), w["experts_gate"], w["experts_up"],
        w["experts_down"]))
    return x + m, chosen


def _load(ckpt, d: dict, i: int) -> dict:
    p = f"model.layers.{i}."
    names = {"in_norm": "input_layernorm.weight",
             "post_norm": "post_attention_layernorm.weight",
             "router": "mlp.gate.weight",
             "shared_expert_gate": "mlp.shared_expert_gate.weight"}
    names.update({f"shared_{x}": f"mlp.shared_expert.{x}_proj.weight"
                  for x in ("gate", "up", "down")})
    if d["full"][i]:
        names.update({x: f"self_attn.{x}_proj.weight" for x in "qkvo"})
        names.update({f"{x}_norm": f"self_attn.{x}_norm.weight"
                      for x in "qk"})
    else:
        names.update({"qkvz": "linear_attn.in_proj_qkvz.weight",
                      "ba": "linear_attn.in_proj_ba.weight",
                      "conv": "linear_attn.conv1d.weight",
                      "dt_bias": "linear_attn.dt_bias",
                      "A_log": "linear_attn.A_log",
                      "gdn_norm": "linear_attn.norm.weight",
                      "out": "linear_attn.out_proj.weight"})
    w = {key: ckpt.tensor(p + name) for key, name in names.items()}
    for x in ("gate", "up", "down"):
        w[f"experts_{x}"] = np.stack([
            ckpt.tensor(f"{p}mlp.experts.{e}.{x}_proj.weight")
            for e in range(d["first"], d["first"] + d["held"])])
    return w


def logits(ckpt, sequences: list[list[int]], wanted: list[range],
           mode: str = "float32") -> list[jax.Array]:
    """:func:`reference.logits` for this family. In ``float32`` it also
    runs the first ``SHADOWED`` sequences in ``bfloat16`` and prints how
    many (token, layer) top-k choices the two precisions made differently,
    and in how many of those a held expert is among the ones exchanged:
    there the partial result moves by a whole expert."""
    cfg = ckpt.config
    d = _dims(cfg)
    eps = float(cfg["rms_norm_eps"])
    kw = dict(dims=tuple((k, tuple(v) if isinstance(v, list) else v)
                         for k, v in d.items()),
              eps=eps, theta=float(cfg["rope_theta"]), first=d["first"],
              norm_topk=bool(cfg.get("norm_topk_prob", True)))
    xs = reference.embed(ckpt, "model.embed_tokens.weight", sequences)
    shadow = list(xs[:SHADOWED]) if mode == "float32" else []
    differed = on_held = pairs = 0
    for w in reference.layers_ahead(partial(_load, ckpt, d), d["L"]):
        out = [_layer(x, w, mode=mode, **kw) for x in xs]
        low = [_layer(x, w, mode="bfloat16", **kw) for x in shadow]
        xs = jax.block_until_ready([x for x, _c in out])
        shadow = [x for x, _c in low]
        for (_x, ours), (_y, theirs), seq in zip(out, low, sequences):
            a = np.sort(np.asarray(ours)[:len(seq)], axis=1)
            b = np.sort(np.asarray(theirs)[:len(seq)], axis=1)
            rows = (a != b).any(axis=1)
            pairs += len(seq)
            differed += int(rows.sum())
            for x, y in zip(a[rows], b[rows]):
                moved = set(x.tolist()) ^ set(y.tolist())
                on_held += any(d["first"] <= e < d["first"] + d["held"]
                               for e in moved)
        del w, out, low
    if pairs:
        print(f"[bench] qwen3_next reference: {differed} of {pairs} (token, "
              f"layer) top-{d['K']} choices differ between bfloat16 and "
              f"float32 over {len(shadow)} sequences, {on_held} of them in "
              "a held expert", flush=True)
    norm = jax.device_put(
        1.0 + ckpt.tensor("model.norm.weight").astype(np.float32))
    head = jax.device_put(ckpt.tensor("lm_head.weight"))
    return reference.head_rows(xs, wanted, norm, head, eps=eps, mode=mode)


# --------------------------------------------------------------- the costs


def gdn_weights(cfg: dict) -> int:
    """Matmul weights of one GDN mixer, the convolution's taps among them."""
    d = _dims(cfg)
    return d["D"] * (d["C"] + d["Z"] + 2 * d["Hv"]) + d["kernel"] * d["C"] \
        + d["Z"] * d["D"]


def attention_weights(cfg: dict) -> int:
    d = _dims(cfg)
    return 3 * d["D"] * d["H"] * d["hd"] + 2 * d["D"] * d["Hkv"] * d["hd"]


def expert_weights(cfg: dict) -> int:
    """Matmul weights of one routed expert."""
    d = _dims(cfg)
    return 3 * d["D"] * d["F"]


def unrouted_weights(cfg: dict) -> int:
    """Matmul weights every token passes through, whatever it chose: the
    mixers, the routers, the shared experts and their gates (not the head,
    not the embedding)."""
    d = _dims(cfg)
    n_full = sum(d["full"])
    return n_full * attention_weights(cfg) \
        + (d["L"] - n_full) * gdn_weights(cfg) \
        + d["L"] * (d["D"] * d["router"] + 3 * d["D"] * d["Fs"] + d["D"])


def parameters(cfg: dict) -> int:
    """Matrices held on this chip (norms, ``A_log`` and ``dt_bias`` apart)."""
    d = _dims(cfg)
    return unrouted_weights(cfg) + d["L"] * d["held"] * expert_weights(cfg) \
        + 2 * d["V"] * d["D"]


def slot_bytes(cfg: dict, itemsize: int = 2) -> int:
    """What a sequence keeps of fixed size: a GDN layer's states and the
    ``kernel − 1`` last inputs of its convolution."""
    d = _dims(cfg)
    return (d["L"] - sum(d["full"])) * (
        d["Hv"] * d["dk"] * d["dv"] * STATE_BYTES
        + (d["kernel"] - 1) * d["C"] * itemsize)


def scan_flops(cfg: dict) -> float:
    """Operations a token of one GDN layer's chunk-wise recurrence needs,
    ``CHUNK`` positions a chunk: ``k kᵀ`` and ``q kᵀ`` a key head; a value
    head, the triangular solve by substitution (``CHUNK³ / 3``
    multiply-adds), its two products (with ``β v`` and ``β k e^G``), ``(q
    kᵀ) v`` and three products with the state."""
    d = _dims(cfg)
    c, dk, dv = CHUNK, d["dk"], d["dv"]
    key_head = 2 * 2.0 * c * dk
    value_head = 2.0 * c * c / 3 + 2.0 * c * (dk + dv) + 2.0 * c * dv \
        + 3 * 2.0 * dk * dv
    return d["Hk"] * key_head + d["Hv"] * value_head


def prefill_flops(cfg: dict, tokens: int) -> float:
    """Operations one prefill of ``tokens`` positions needs: 2 a weight a
    token through everything unrouted and through the expected ``K /
    ep_size`` held experts a token a layer; the recurrence of the GDN
    layers (:func:`scan_flops`); attention over the pairs a query sees (the
    causal half), ``4 * hd`` a head a pair, in the full layers; the head
    for one position."""
    d = _dims(cfg)
    T = tokens
    n_full = sum(d["full"])
    matmul = 2.0 * T * (unrouted_weights(cfg) + d["L"] * d["K"] / d["ep"]
                        * expert_weights(cfg)) + 2.0 * d["V"] * d["D"]
    pairs = n_full * T * (T + 1) / 2
    return matmul + T * (d["L"] - n_full) * scan_flops(cfg) \
        + 4.0 * d["hd"] * d["H"] * pairs


def decode_bytes(cfg: dict, steps: list[dict], lengths: list[int],
                 itemsize: int = 2) -> float:
    """Bytes the decode steps must move: a step, everything unrouted and
    the head once and each held expert that was hit once (``experts_hit``,
    summed over the layers, on the step's span); a decoded token, its slot
    read and written, and the cached positions its full layers see at the
    KV heads."""
    d = _dims(cfg)
    fixed = (unrouted_weights(cfg) + d["V"] * d["D"]) * itemsize
    hit = sum(int(s.get("experts_hit", 0)) for s in steps)
    position = 2 * d["Hkv"] * d["hd"] * itemsize * sum(d["full"])
    return float(len(steps)) * fixed \
        + float(hit) * expert_weights(cfg) * itemsize \
        + float(len(lengths)) * 2 * slot_bytes(cfg, itemsize) \
        + float(sum(lengths)) * position


# ------------------------------------------------------- the family's readers


def state_share(obs, span: str, attr: str):
    """The slots' bytes the window's decode steps read and wrote (``attr``
    of every ``span``, as the program names it) over the bytes those steps
    must move in all (:func:`decode_bytes`), in percent: how much of a
    step's least traffic is the fixed state."""
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
