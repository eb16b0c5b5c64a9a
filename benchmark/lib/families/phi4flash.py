"""The Phi-4-mini-flash family (``model_type`` ``phi4flash``): the SambaY
decoder, whole on one chip.

For layer input ``x`` [T, D]. LayerNorm has a weight and a bias (``(x − μ) /
sqrt(σ² + eps) · w + b``); there is no positional encoding anywhere.

- ``x += mixer(LN1(x))``; ``x += mlp(LN2(x))``; a final LayerNorm; the head
  is the embedding (``tie_word_embeddings``), no bias. With ``half =
  num_hidden_layers / 2``, layer ``i``'s mixer is: ``i <= half`` even,
  **Mamba**; ``i < half`` odd, **window attention** (a key ``sliding_window``
  or more behind is not seen); ``i == half + 1``, **full attention**; ``i >
  half + 1`` even, a **gated memory unit**; ``i > half + 1`` odd,
  **cross-attention** over layer ``half + 1``'s keys and values.
- **MLP**: ``fc1`` (``2 · intermediate_size`` outputs, no bias) gives ``[gate
  | up]``; ``fc2(up ⊙ silu(gate))``.
- **Mamba** (``d_inner = expand · D``, ``N = d_state``, ``R = dt_rank``):
  ``[x | z] = in_proj(u)``; ``x ← silu(conv(x))``, a causal depthwise
  convolution with bias (``conv1d.weight`` [d_inner, 1, kernel]: ``y_t = Σ_i
  w[:, 0, i] x_{t − kernel + 1 + i} + b``); ``[δ | B | C] = x_proj(x)`` (``R +
  2N``); ``Δ = softplus(dt_proj(δ) + dt_proj.bias)``; ``A = −exp(A_log)``
  [d_inner, N]; a token: ``h ← exp(Δ_t A) ⊙ h + (Δ_t x_t) ⊗ B_t``; ``y_t = h
  C_t + D ⊙ x_t``; output ``out_proj(y ⊙ silu(z))``. Layer ``half``'s ``y``
  (before the gate) is the **memory** ``M`` the gated memory units read.
- **Gated memory unit**: ``out_proj(silu(in_proj(u)) ⊙ M)``, ``M`` at the same
  position.
- **Differential attention** (window, full and cross): ``Wqkv`` gives ``H``
  query heads, then ``Hkv`` key heads, then ``Hkv`` value heads of ``hd``,
  with a bias (a cross layer's ``Wqkv`` gives the queries only; its keys and
  values are the full layer's). Adjacent heads pair: queries ``2j, 2j + 1``
  are ``q1, q2`` of pair ``j``, keys and values likewise; query pair ``j``
  reads KV pair ``j // (H / Hkv)``. ``a_s = softmax(q_s k_sᵀ / sqrt(hd)) [v1 |
  v2]``; ``λ = exp(λ_q1 · λ_k1) − exp(λ_q2 · λ_k2) + λ_init``, ``λ_init = 0.8 −
  0.6 exp(−0.3 i)``; ``o = RMSNorm_w(a_1 − λ a_2) · (1 − λ_init)`` over the
  ``2 hd`` of a pair (eps 1e-5); ``out_proj`` (with bias) over the pairs in
  order.

The reference runs the recurrence one token at a time and attention as four
masked softmax products a pair (``q1 k1ᵀ`` and ``q2 k2ᵀ``, each over ``v1``
and over ``v2``), the queries taken in blocks so that the scores fit, every
layer over every position.

The costs count what the mathematics of serving needs. Nothing past layer
``half + 1`` leaves anything a later token reads, and of that layer only its
keys and values are read again: a prefill needs layers ``0..half`` over the
prompt, that layer's key and value projection over the prompt, and from its
query on, everything at the last position only. A decode step reads the
weights once (the embedding as the head), the full layer's filled positions
once for each layer that attends over them, each window layer's last
``sliding_window`` positions (and writes one), and reads and writes each
row's Mamba states and convolution tails.

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

#: the Mamba state is kept in float32
STATE_BYTES = 4
#: queries a block of the reference's attention
BLOCK = 256
#: eps of the sub-norm over a pair
SUBLN_EPS = 1e-5


def _dims(cfg: dict) -> dict:
    D, H, L = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_hidden_layers"]
    rank = cfg.get("mamba_dt_rank", "auto")
    half = L // 2
    return dict(
        D=D, H=H, Hkv=cfg["num_key_value_heads"], hd=D // H, L=L, half=half,
        V=cfg["vocab_size"], I=cfg["intermediate_size"],
        W=cfg["sliding_window"], Dn=cfg.get("mamba_expand", 2) * D,
        N=cfg.get("mamba_d_state", 16), K=cfg.get("mamba_d_conv", 4),
        R=-(-D // 16) if rank == "auto" else rank,
        kinds=tuple(
            ("mamba" if i % 2 == 0 else "window") if i <= half
            else "full" if i == half + 1
            else "gmu" if i % 2 == 0 else "cross" for i in range(L)))


def rehearsal(config: dict) -> dict:
    """The toy the rehearsal swaps in: 8 layers (three Mamba, two window,
    the full one, a memory unit, a cross-attention), a window of 16."""
    return {"hidden_size": 128, "intermediate_size": 256,
            "num_hidden_layers": 8, "num_attention_heads": 4,
            "num_key_value_heads": 2, "sliding_window": 16,
            "vocab_size": 512, "mamba_d_state": 8}


# ------------------------------------------------------------- the weights


def tensors(config: dict) -> dict[str, Filled]:
    """The published names in the checkpoint's order (every layer's mixer
    is ``attn``). A matrix is N(0, 1/fan_in) over its ``[out, in]`` layout's
    inputs (the convolution's fan-in is its kernel; the embedding's is its
    width, as the head it is: logits are of unit scale, and the first
    LayerNorm brings a row of it to unit scale too); the
    ``λ`` vectors N(0, 1/100), the published std 0.1; norm weights, the
    sub-norm and ``D`` ones; every bias and ``A_log`` zeros (so ``A = −1``
    everywhere, which nothing may use)."""
    d = _dims(config)
    D, hd, Dn = d["D"], d["hd"], d["Dn"]

    def matrix(out: int, fan_in: int) -> Filled:
        return Filled((out, fan_in), "normal", fan_in)

    table = {"model.embed_tokens.weight": Filled((d["V"], D), "normal", D)}
    for i, kind in enumerate(d["kinds"]):
        p = f"model.layers.{i}."
        table[p + "input_layernorm.weight"] = Filled((D,), "ones")
        table[p + "input_layernorm.bias"] = Filled((D,), "zeros")
        if kind == "mamba":
            table.update({
                p + "attn.in_proj.weight": matrix(2 * Dn, D),
                p + "attn.conv1d.weight":
                    Filled((Dn, 1, d["K"]), "normal", d["K"]),
                p + "attn.conv1d.bias": Filled((Dn,), "zeros"),
                p + "attn.x_proj.weight": matrix(d["R"] + 2 * d["N"], Dn),
                p + "attn.dt_proj.weight": matrix(Dn, d["R"]),
                p + "attn.dt_proj.bias": Filled((Dn,), "zeros"),
                p + "attn.A_log": Filled((Dn, d["N"]), "zeros"),
                p + "attn.D": Filled((Dn,), "ones"),
                p + "attn.out_proj.weight": matrix(D, Dn),
            })
        elif kind == "gmu":
            table.update({p + "attn.in_proj.weight": matrix(Dn, D),
                          p + "attn.out_proj.weight": matrix(D, Dn)})
        else:
            out = d["H"] * hd + (0 if kind == "cross"
                                 else 2 * d["Hkv"] * hd)
            table.update({
                p + "attn.Wqkv.weight": matrix(out, D),
                p + "attn.Wqkv.bias": Filled((out,), "zeros"),
                p + "attn.out_proj.weight": matrix(D, d["H"] * hd),
                p + "attn.out_proj.bias": Filled((D,), "zeros"),
                **{p + f"attn.inner_cross_attn.lambda_{x}":
                   Filled((hd,), "normal", 100)
                   for x in ("q1", "k1", "q2", "k2")},
                p + "attn.inner_cross_attn.subln.weight":
                    Filled((2 * hd,), "ones"),
            })
        table[p + "post_attention_layernorm.weight"] = Filled((D,), "ones")
        table[p + "post_attention_layernorm.bias"] = Filled((D,), "zeros")
        table[p + "mlp.fc1.weight"] = matrix(2 * d["I"], D)
        table[p + "mlp.fc2.weight"] = matrix(D, d["I"])
    table["model.final_layernorm.weight"] = Filled((D,), "ones")
    table["model.final_layernorm.bias"] = Filled((D,), "zeros")
    return table


# ----------------------------------------------------------- the reference


def layer_norm(x, w, b, eps: float):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32) \
        + b.astype(jnp.float32)


def _mamba(x, w, d: dict, mode: str):
    """The Mamba mixer over ``x`` [T, D], a token at a time → ``(out, y)``,
    ``y`` [T, d_inner] the scan's output before the gate."""
    T = x.shape[0]
    Dn, N, R, K = d["Dn"], d["N"], d["R"], d["K"]
    xz = reference.linear(x, w["in_proj"], mode)
    u, z = xz[:, :Dn], xz[:, Dn:]
    taps = w["conv"].astype(jnp.float32)[:, 0, :]               # [Dn, K]
    padded = jnp.pad(u, ((K - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(padded[i:i + T] * taps[:, i] for i in range(K))
                    + w["conv_bias"].astype(jnp.float32))
    dbc = reference.linear(u, w["x_proj"], mode)
    delta = jax.nn.softplus(
        reference.linear(dbc[:, :R], w["dt_proj"], mode)
        + w["dt_bias"].astype(jnp.float32))
    Bm, Cm = dbc[:, R:R + N], dbc[:, R + N:]
    A = -jnp.exp(w["A_log"].astype(jnp.float32))               # [Dn, N]

    def token(h, t):        # h [Dn, N]
        u_t, delta_t, B_t, C_t = t
        h = jnp.exp(delta_t[:, None] * A) * h \
            + (delta_t * u_t)[:, None] * B_t[None, :]
        return h, (h * C_t[None, :]).sum(axis=1)

    _h, y = jax.lax.scan(token, jnp.zeros((Dn, N), jnp.float32),
                         (u, delta, Bm, Cm))
    y = y + w["D"].astype(jnp.float32) * u
    return reference.linear(y * jax.nn.silu(z), w["out_proj"], mode), y


def _attention(x, w, d: dict, init, kind: str, shared, mode: str):
    """A differential-attention mixer over ``x`` [T, D] (T a multiple of
    ``BLOCK``) → ``(out, (k, v))``, the keys and values [T, Hkv, hd] (a
    cross layer's are ``shared``); ``init`` the layer's ``λ_init``."""
    T = x.shape[0]
    H, Hkv, hd, W = d["H"], d["Hkv"], d["hd"], d["W"]
    qkv = reference.linear(x, w["wqkv"], mode) \
        + w["bqkv"].astype(jnp.float32)
    q = qkv[:, :H * hd].reshape(T, H // 2, 2, hd)
    if kind == "cross":
        k, v = shared
    else:
        k = qkv[:, H * hd:(H + Hkv) * hd].reshape(T, Hkv, hd)
        v = qkv[:, (H + Hkv) * hd:].reshape(T, Hkv, hd)
    g = H // Hkv        # query pairs a KV pair
    kp = jnp.repeat(k.reshape(T, Hkv // 2, 2, hd), g, axis=1)
    vp = jnp.repeat(v.reshape(T, Hkv // 2, 2, hd), g, axis=1)
    k1, k2, v1, v2 = kp[:, :, 0], kp[:, :, 1], vp[:, :, 0], vp[:, :, 1]

    def product(qb, kk, vv, seen):
        s = jnp.einsum("qjd,kjd->jqk", qb, kk,
                       precision=reference.HIGHEST) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("jqk,kjd->qjd", p, vv, precision=reference.HIGHEST)

    def block(args):
        qb, start = args
        behind = (start + jnp.arange(BLOCK))[:, None] - jnp.arange(T)[None, :]
        seen = behind >= 0
        if kind == "window":
            seen &= behind < W
        q1, q2 = qb[:, :, 0], qb[:, :, 1]
        a1 = jnp.concatenate([product(q1, k1, v1, seen),
                              product(q1, k1, v2, seen)], axis=-1)
        a2 = jnp.concatenate([product(q2, k2, v1, seen),
                              product(q2, k2, v2, seen)], axis=-1)
        return a1, a2

    a1, a2 = jax.lax.map(block, (q.reshape(T // BLOCK, BLOCK, H // 2, 2, hd),
                                 jnp.arange(T // BLOCK) * BLOCK))
    a1 = a1.reshape(T, H // 2, 2 * hd)
    a2 = a2.reshape(T, H // 2, 2 * hd)

    def dot(a, b):
        return jnp.exp(jnp.sum(w[f"lambda_{a}"].astype(jnp.float32)
                               * w[f"lambda_{b}"].astype(jnp.float32)))

    lam = dot("q1", "k1") - dot("q2", "k2") + init
    o = reference.rms_norm(a1 - lam * a2, w["subln"], SUBLN_EPS) \
        * (1.0 - init)
    out = reference.linear(o.reshape(T, H * hd), w["out_proj"], mode) \
        + w["out_bias"].astype(jnp.float32)
    return out, (k, v)


@partial(jax.jit, static_argnames=("dims", "kind", "eps", "mode"))
def _mixer(x, memory, shared, init, w, *, dims: tuple, kind: str, eps: float,
           mode: str):
    """A layer's first half over ``x`` [T, D] → ``(x, memory, shared)``: the
    last Mamba layer's ``y`` and the full layer's keys and values go on
    with it. ``init`` is the layer's ``λ_init`` (an argument, so that a kind
    of layer compiles once and not once a layer)."""
    d = dict(dims)
    h = layer_norm(x, w["ln1_w"], w["ln1_b"], eps)
    if kind == "mamba":
        a, memory = _mamba(h, w, d, mode)
    elif kind == "gmu":
        a = reference.linear(
            jax.nn.silu(reference.linear(h, w["in_proj"], mode)) * memory,
            w["out_proj"], mode)
    else:
        a, kv = _attention(h, w, d, init, kind, shared, mode)
        if kind == "full":
            shared = kv
    return x + a, memory, shared


@partial(jax.jit, static_argnames=("eps", "mode"))
def _mlp(x, w, *, eps: float, mode: str):
    """A layer's second half, the same in every layer: ``x + fc2(up ⊙
    silu(gate))`` of ``LN2(x)``."""
    h = reference.linear(layer_norm(x, w["ln2_w"], w["ln2_b"], eps),
                         w["fc1"], mode)
    gate, up = jnp.split(h, 2, axis=1)
    return x + reference.linear(up * jax.nn.silu(gate), w["fc2"], mode)


def _load(ckpt, d: dict, i: int) -> dict:
    p = f"model.layers.{i}."
    kind = d["kinds"][i]
    names = {"ln1_w": "input_layernorm.weight",
             "ln1_b": "input_layernorm.bias",
             "ln2_w": "post_attention_layernorm.weight",
             "ln2_b": "post_attention_layernorm.bias",
             "fc1": "mlp.fc1.weight", "fc2": "mlp.fc2.weight",
             "out_proj": "attn.out_proj.weight"}
    if kind == "mamba":
        names.update({"in_proj": "attn.in_proj.weight",
                      "conv": "attn.conv1d.weight",
                      "conv_bias": "attn.conv1d.bias",
                      "x_proj": "attn.x_proj.weight",
                      "dt_proj": "attn.dt_proj.weight",
                      "dt_bias": "attn.dt_proj.bias",
                      "A_log": "attn.A_log", "D": "attn.D"})
    elif kind == "gmu":
        names["in_proj"] = "attn.in_proj.weight"
    else:
        names.update({"wqkv": "attn.Wqkv.weight", "bqkv": "attn.Wqkv.bias",
                      "out_bias": "attn.out_proj.bias",
                      "subln": "attn.inner_cross_attn.subln.weight"})
        names.update({f"lambda_{x}": f"attn.inner_cross_attn.lambda_{x}"
                      for x in ("q1", "k1", "q2", "k2")})
    return {key: ckpt.tensor(p + name) for key, name in names.items()}


@partial(jax.jit, static_argnames=("eps", "mode"))
def _head(x, w, b, head, *, eps: float, mode: str):
    return reference.linear(layer_norm(x, w, b, eps), head, mode)


def logits(ckpt, sequences: list[list[int]], wanted: list[range],
           mode: str = "float32") -> list[jax.Array]:
    """:func:`reference.logits` for this family: every layer over every
    position, no cache; the final LayerNorm and the embedding as the head
    at the ``wanted`` positions, the rows padded to a multiple of
    ``reference.ROWS``."""
    cfg = ckpt.config
    d = _dims(cfg)
    eps = float(cfg.get("layer_norm_eps", 1e-5))
    dims = tuple(d.items())
    # every sequence of a call padded on to one length, a power of two: a
    # kind of layer compiles once a call
    xs = reference.embed(ckpt, "model.embed_tokens.weight", sequences)
    T = 1 << (max(x.shape[0] for x in xs) - 1).bit_length()
    xs = [jnp.pad(x, ((0, T - x.shape[0]), (0, 0))) for x in xs]
    carried = [(None, None)] * len(xs)
    mlp_keys = ("ln2_w", "ln2_b", "fc1", "fc2")
    for i, w in enumerate(reference.layers_ahead(partial(_load, ckpt, d),
                                                 d["L"])):
        init = np.float32(0.8 - 0.6 * math.exp(-0.3 * i))
        mlp = {key: w.pop(key) for key in mlp_keys}
        out = [_mixer(x, memory, shared, init, w, dims=dims,
                      kind=d["kinds"][i], eps=eps, mode=mode)
               for x, (memory, shared) in zip(xs, carried)]
        carried = [(m, s) for _x, m, s in out]
        xs = jax.block_until_ready(
            [_mlp(x, mlp, eps=eps, mode=mode) for x, _m, _s in out])
        del w, mlp, out
    del carried
    norm_w = jax.device_put(ckpt.tensor("model.final_layernorm.weight"))
    norm_b = jax.device_put(ckpt.tensor("model.final_layernorm.bias"))
    head = jax.device_put(ckpt.tensor("model.embed_tokens.weight"))
    rows_of = []
    for x, want in zip(xs, wanted):
        rows = np.asarray(want)
        padded = np.concatenate(
            [rows, np.full(-len(rows) % reference.ROWS, rows[-1],
                           rows.dtype)])
        rows_of.append(_head(x[padded], norm_w, norm_b, head, eps=eps,
                             mode=mode))
    return rows_of


# --------------------------------------------------------------- the costs


def parameters(cfg: dict) -> int:
    """Every number the checkpoint holds (the embedding once: it is the
    head)."""
    return sum(math.prod(t.shape) for t in tensors(cfg).values())


def mixer_weights(cfg: dict, kind: str) -> int:
    """Matmul weights of one mixer of ``kind`` (the convolution's taps
    among a Mamba layer's)."""
    d = _dims(cfg)
    D, Dn, hd = d["D"], d["Dn"], d["hd"]
    if kind == "mamba":
        return D * 2 * Dn + d["K"] * Dn + Dn * (d["R"] + 2 * d["N"]) \
            + d["R"] * Dn + Dn * D
    if kind == "gmu":
        return 2 * D * Dn
    keys = 0 if kind == "cross" else D * 2 * d["Hkv"] * hd
    return 2 * D * d["H"] * hd + keys


def mlp_weights(cfg: dict) -> int:
    d = _dims(cfg)
    return 3 * d["D"] * d["I"]


def page_bytes(cfg: dict, itemsize: int = 2) -> int:
    """One cached position of the full-attention layer: keys and values."""
    d = _dims(cfg)
    return 2 * d["Hkv"] * d["hd"] * itemsize


def readers(cfg: dict) -> int:
    """Layers that attend over the full-attention layer's positions."""
    kinds = _dims(cfg)["kinds"]
    return kinds.count("full") + kinds.count("cross")


def ring_bytes(cfg: dict, itemsize: int = 2) -> int:
    """One position of every window layer's ring."""
    return _dims(cfg)["kinds"].count("window") * page_bytes(cfg, itemsize)


def ssm_bytes(cfg: dict, itemsize: int = 2) -> int:
    """What a sequence keeps for its Mamba layers: the states and the
    ``kernel − 1`` last inputs of the convolutions."""
    d = _dims(cfg)
    return d["kinds"].count("mamba") * d["Dn"] * (
        d["N"] * STATE_BYTES + (d["K"] - 1) * itemsize)


def slot_bytes(cfg: dict, itemsize: int = 2) -> int:
    """A sequence's slot: the rings whole, and the Mamba layers' part."""
    return _dims(cfg)["W"] * ring_bytes(cfg, itemsize) \
        + ssm_bytes(cfg, itemsize)


def scan_flops(cfg: dict) -> float:
    """Operations a token of one Mamba layer's recurrence needs: a state
    entry, the decay's multiply-add, the input's product and the output's
    multiply-add (the exponentials not counted)."""
    d = _dims(cfg)
    return 6.0 * d["Dn"] * d["N"]


def prefill_flops(cfg: dict, tokens: int) -> float:
    """Operations one prefill of ``tokens`` positions needs: 2 a weight a
    token through layers ``0..half`` and through the full layer's key and
    value projection; the recurrence of the Mamba layers (:func:`scan_flops`);
    attention over the pairs a query of a window layer sees (the band: ``2
    hd`` for the score and ``4 hd`` for the pair's ``2 hd``-wide values, a
    query head a key); from the full layer's query on, 2 a weight for one
    position, that position's attention over the prompt in each layer that
    reads it, and the head for one position."""
    d = _dims(cfg)
    T, W, kinds = tokens, d["W"], d["kinds"]
    mlp = mlp_weights(cfg)
    over_prompt = sum(mixer_weights(cfg, k) + mlp
                      for k in kinds[:d["half"] + 1]) \
        + d["D"] * 2 * d["Hkv"] * d["hd"]
    once = mixer_weights(cfg, "full") - d["D"] * 2 * d["Hkv"] * d["hd"] \
        + mlp + sum(mixer_weights(cfg, k) + mlp
                    for k in kinds[d["half"] + 2:]) + d["V"] * d["D"]
    pair = 6.0 * d["hd"] * d["H"]
    band = sum(min(t + 1, W) for t in range(T)) if T < W \
        else W * (W + 1) / 2 + (T - W) * W
    return 2.0 * T * over_prompt + 2.0 * once \
        + T * kinds.count("mamba") * scan_flops(cfg) \
        + pair * (kinds.count("window") * band + readers(cfg) * T)


def decode_bytes(cfg: dict, steps: list[dict], lengths: list[int],
                 itemsize: int = 2) -> float:
    """Bytes the decode steps must move: a step, every weight once (the
    embedding as the head's rows); a decoded token, the cached positions of
    the full layer once for each layer that reads them, the window layers'
    last ``sliding_window`` positions read and one written, its Mamba
    states and tails read and written."""
    W = _dims(cfg)["W"]
    return float(len(steps)) * parameters(cfg) * itemsize \
        + float(sum(lengths)) * page_bytes(cfg, itemsize) * readers(cfg) \
        + float(sum(min(n, W) + 1 for n in lengths)) \
        * ring_bytes(cfg, itemsize) \
        + float(len(lengths)) * 2 * ssm_bytes(cfg, itemsize)


# ------------------------------------------------------- the family's readers


def span_share(obs, span: str, attr: str):
    """Bytes the program names on the window's decode steps (``attr`` of
    every ``span``: ``shared_kv_bytes``, the full layer's positions times
    their readers; ``state_bytes``, rings and Mamba slots read and written)
    over the bytes those steps must move in all (:func:`decode_bytes`), in
    percent. None where the program names no such bytes."""
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
