"""The ZAYA1 family (``model_type`` ``zaya``): compressed convolutional
attention, a router that is an MLP with a stream of its own through the
depth, one expert a token of a set that is held whole, and a skip.

Two streams enter layer ``l``: the residual ``x_t`` and the router's
``rho_t`` of the layer before (zero into layer 0). ``H`` query heads over
``Hkv`` cached heads of ``hd`` columns, ``g = H / Hkv``, ``k0 = cca_time0``,
``k1 = cca_time1``, every norm an RMSNorm with a learned weight
(``rms_norm_eps``); anything of the sequence before position 0 is zero.

Attention sublayer, position ``t``:

- ``h_t = RMSNorm(x_t)``; ``q~_t = W_q h_t`` (``H`` heads), ``k~_t = W_k
  h_t`` (``Hkv`` heads); ``v_t = [W_v1 h_t ; W_v2 h_{t-1}]``: the first half
  of the value columns (at the published widths KV head 0) comes from this
  token, the second half from the token before (``v_proj``'s first half of
  rows is ``W_v1``).
- Mixing, ``u_t = [q~_t ; k~_t]``: ``c0_t = b0 + sum_{j<k0} w0[:, j] *
  u_{t-(k0-1)+j}`` (depthwise, causal); ``c1_t = b1 + sum_{j<k1} W1[j]
  c0_{t-(k1-1)+j}``, each ``W1[j]`` block-diagonal with one ``hd x hd``
  block a head, query and key heads alike; no activation between the two;
  the sequence is padded once, on the left, by ``(k0-1) + (k1-1)`` zeros of
  ``u`` (so ``c0`` before position 0 is ``b0``).
- The mean: ``m_q^(i) = (q~^(i) + k~^(i // g)) / 2``; ``m_k^(j)`` the mean
  of ``m_q^(i)`` over the ``i`` with ``i // g = j``; ``q^(i) = c1[q]^(i) +
  m_q^(i)``, ``k^(j) = c1[k]^(j) + m_k^(j)``.
- ``q^ = q sqrt(hd) / |q|`` a head, ``k^ = tau_j k sqrt(hd) / |k|`` with one
  learned ``tau_j`` a KV head (a zero vector stays zero); rotary on the
  first ``partial_rotary_factor hd`` columns of a head, rotate-half within
  them, pair ``j`` turning ``rope_theta ** (-2j / rotary)`` a position.
- Causal softmax attention in float32 of the ``H`` heads over the ``Hkv``,
  scale ``hd ** -0.5``; ``a_t = W_o out_t``.
- Merge: ``x'_t = s_r * (x_t + b_r) + s_o * (a_t + b_o)``.

Expert sublayer:

- ``g_t = RMSNorm(x'_t)``; ``rho^l_t = W_down g_t + gamma_l * rho^{l-1}_t``
  (no ``gamma`` in layer 0), which goes on to layer ``l + 1``.
- ``s_t = W_3 gelu(W_2 gelu(W_1 RMSNorm(rho^l_t) + c_1) + c_2) + c_3`` (the
  exact gelu), ``E + 1`` outputs; ``p = softmax(s)``; ``e* = argmax(p +
  beta)``, the first maximum. The stream, the MLP and the choice are in
  float32 in every mode; ``W_down`` is a linear layer of the mode.
- ``y_t = p[e*] W_down^{e*} (silu(W_gate^{e*} g_t) * W_up^{e*} g_t)`` for
  ``e* < E``; ``y_t = 0`` for ``e* = E``, the skip: a plain loop over the
  experts.
- Merge as above with its own four vectors. After the last layer a norm;
  the logits are ``E_mb h`` with the embedding.

The costs count what the mathematics needs. A decode step reads the weights
outside the experts once, the table once (as the head), each expert that
was hit once (its ``experts_hit`` attribute), a position's ``[v | k^]`` a
layer of every cached position behind a decoded token, and each row's tails
in and out.

Imports nothing of the program under test.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import reference
from . import Filled

#: queries a block of the reference's attention
BLOCK = 256
#: every sequence of a call is padded to the longest of them, rounded up to
#: a multiple of this many positions: few compiled lengths
LENGTH = 1024
#: positions a block of the head: 262 272 float32 logits a position are
#: 1 MB, a block 0.27 GB on the device
HEAD_ROWS = 256
#: the fills that are not the plain ones (:func:`tensors`)
GAMMA_FAN_IN = 4
ROUTER_OUT_FAN_IN_CUT = 64
O_FAN_IN_FACTOR = 16
#: the four vectors of a merge, as the checkpoint names them
MERGE = ("residual_scale", "residual_bias", "output_scale", "output_bias")


def _dims(cfg: dict) -> dict:
    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    rope = (cfg.get("rope_parameters") or {}).get("hybrid") or {}
    return dict(
        D=cfg["hidden_size"], L=cfg["num_hidden_layers"],
        V=cfg["vocab_size"], H=H, Hkv=Hkv, hd=hd, g=H // Hkv,
        Cq=H * hd, Ck=Hkv * hd, C=(H + Hkv) * hd,
        k0=cfg.get("cca_time0", 2), k1=cfg.get("cca_time1", 2),
        rot=int(hd * rope.get("partial_rotary_factor",
                              cfg.get("partial_rotary_factor", 0.5))),
        theta=float(rope.get("rope_theta", cfg.get("rope_theta", 5e6))),
        E=cfg["num_experts"], F=cfg["moe_intermediate_size"],
        R=cfg["router_hidden_size"])


def rehearsal(config: dict) -> dict:
    """The toy the rehearsal swaps in: four layers, 4 heads over 2 of 16, a
    router of 32 columns over 4 experts of 64 and the skip."""
    return {"hidden_size": 128, "num_hidden_layers": 4,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "vocab_size": 512, "num_experts": 4,
            "moe_intermediate_size": 64, "router_hidden_size": 32,
            "layer_types": ["hybrid"] * 4}


# ------------------------------------------------------------- the weights


def tensors(config: dict) -> dict[str, Filled]:
    """The checkpoint's tensors in its order, ``[out, in]`` matrices. A
    matrix is N(0, 1/fan_in); the embedding's fan-in is the hidden size,
    as the head it is (the tied table is read both ways, and logits of
    unit scale are what the limits of ``correct`` are read in); a block of
    the second convolution (``conv_qk.1``, ``[C, hd, k1]``: a grouped
    convolution of ``C / hd`` groups) at ``hd * k1``. Norms, the
    temperatures and the merges' scales are ones; every bias, the merges'
    biases and ``balancing_bias`` (``beta``) are zeros.

    Four fills are not the plain ones, each so that the comparison that
    decides ``correct`` can see what it is there to see (PERF.md, sections
    2 and 6, PR 49):

    - the first convolution (``conv_qk.0``, ``[C, 1, k0]``, depthwise) is
      N(0, 1/k0), not ones: its taps differ, so their order matters;
    - the router's ``depth_scale`` (``gamma``, layers past 0) is
      N(0, 1/GAMMA_FAN_IN), not zeros: the stream of the layer before
      enters every choice;
    - the router's last matrix (``mlp.2``) is filled at a
      ``ROUTER_OUT_FAN_IN_CUT``-th of its 256 inputs: its 17 outputs then
      spread by ~3 and not by ~0.3, so the chosen expert's ``p`` is near 1
      and not near 1/17 and the expert sublayer's sum is of the residual's
      size, as a trained router's is;
    - the attention's output projection (``o_proj``) is filled at
      ``O_FAN_IN_FACTOR`` times its 1 024 inputs: under seeded weights
      every attention of a context of thousands is a near-uniform average,
      the same vector at every position, and at the plain fill that vector
      outgrows what a token itself brings after two layers, every position
      ends on the same logits and a greedy reply is one repeated token, on
      which no precision shows."""
    d = _dims(config)
    D, R, E = d["D"], d["R"], d["E"]

    def matrix(out: int, fan_in: int) -> Filled:
        return Filled((out, fan_in), "normal", fan_in)

    def merge(prefix: str) -> dict:
        return {f"{prefix}.{part}": Filled(
            (D,), "ones" if part.endswith("scale") else "zeros")
            for part in MERGE}

    table = {"model.embed_tokens.weight": matrix(d["V"], D)}
    for li in range(d["L"]):
        p = f"model.layers.{li}."
        a, r = p + "self_attn.", p + "mlp.router."
        table.update({
            p + "input_layernorm.weight": Filled((D,), "ones"),
            a + "q_proj.weight": matrix(d["Cq"], D),
            a + "k_proj.weight": matrix(d["Ck"], D),
            a + "v_proj.weight": matrix(d["Ck"], D),
            a + "conv_qk.0.weight": Filled((d["C"], 1, d["k0"]), "normal",
                                           d["k0"]),
            a + "conv_qk.0.bias": Filled((d["C"],), "zeros"),
            a + "conv_qk.1.weight": Filled((d["C"], d["hd"], d["k1"]),
                                           "normal", d["hd"] * d["k1"]),
            a + "conv_qk.1.bias": Filled((d["C"],), "zeros"),
            a + "temp": Filled((d["Hkv"],), "ones"),
            a + "o_proj.weight": Filled((D, d["Cq"]), "normal",
                                        O_FAN_IN_FACTOR * d["Cq"]),
            **merge(p + "self_attn_merge"),
            p + "post_attention_layernorm.weight": Filled((D,), "ones"),
            r + "down_proj.weight": matrix(R, D),
        })
        if li:
            table[r + "depth_scale"] = Filled((R,), "normal", GAMMA_FAN_IN)
        table[r + "norm.weight"] = Filled((R,), "ones")
        for i, out in enumerate((R, R, E + 1)):
            table[f"{r}mlp.{i}.weight"] = Filled(
                (out, R), "normal",
                R if i < 2 else max(1, R // ROUTER_OUT_FAN_IN_CUT))
            table[f"{r}mlp.{i}.bias"] = Filled((out,), "zeros")
        table[r + "balancing_bias"] = Filled((E + 1,), "zeros")
        for e in range(E):
            for x, shape in (("gate", (d["F"], D)), ("up", (d["F"], D)),
                             ("down", (D, d["F"]))):
                table[f"{p}mlp.experts.{e}.{x}_proj.weight"] = matrix(*shape)
        table.update(merge(p + "mlp_merge"))
    table["model.norm.weight"] = Filled((D,), "ones")
    return table


# ----------------------------------------------------------- the reference


def _rotate(x, d: dict):
    """``x`` [T, h, hd] at positions 0..T-1: the first ``rot`` columns of a
    head turned, rotate-half within them."""
    r, T = d["rot"], x.shape[0]
    inv = 1.0 / (d["theta"] ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., r:]], axis=-1)


def _before(x, n: int):
    """``x`` [T, ...] as it stood ``n`` positions earlier, zeros before
    position 0."""
    return jnp.pad(x, ((n, 0),) + ((0, 0),) * (x.ndim - 1))[:x.shape[0]]


def mix(u, w, d: dict, mode: str):
    """The two convolutions over ``u`` [T, C] → ``c1`` [T, C]: the sequence
    padded once by ``(k0-1) + (k1-1)`` zeros of ``u``, the first convolution
    over all of it but its first ``k0 - 1`` rows, the second over that."""
    T = u.shape[0]
    k0, k1, hd = d["k0"], d["k1"], d["hd"]
    w0 = w["conv0_w"].astype(jnp.float32)[:, 0, :]          # [C, k0]
    padded = jnp.pad(u, ((k0 + k1 - 2, 0), (0, 0)))
    c0 = w["conv0_b"].astype(jnp.float32) + sum(
        w0[:, j] * padded[j:j + T + k1 - 1] for j in range(k0))
    heads = c0.reshape(T + k1 - 1, -1, hd)
    blocks = w["conv1_w"].reshape(-1, hd, hd, k1)    # [head, out, in, k1]
    per_head = jax.vmap(partial(reference.linear, mode=mode),
                        in_axes=(1, 0), out_axes=1)
    c1 = sum(per_head(heads[j:j + T], blocks[..., j]) for j in range(k1))
    return c1.reshape(T, -1) + w["conv1_b"].astype(jnp.float32)


def attention(h, w, d: dict, mode: str):
    """The attention over ``h`` [T, D] (normed; T a multiple of ``BLOCK``)
    → ``W_o out`` [T, D], the queries a block at a time."""
    linear = reference.linear
    T = h.shape[0]
    H, Hkv, hd, g = d["H"], d["Hkv"], d["hd"], d["g"]
    q_in, k_in = linear(h, w["q"], mode), linear(h, w["k"], mode)
    half = d["Ck"] // 2
    v = jnp.concatenate([
        linear(h, w["v"][:half], mode),
        _before(linear(h, w["v"][half:], mode), 1)], axis=-1)
    c1 = mix(jnp.concatenate([q_in, k_in], axis=-1), w, d, mode)
    q_in, k_in = q_in.reshape(T, Hkv, g, hd), k_in.reshape(T, Hkv, 1, hd)
    m_q = 0.5 * (q_in + k_in)
    q = c1[:, :d["Cq"]].reshape(T, Hkv, g, hd) + m_q
    k = c1[:, d["Cq"]:].reshape(T, Hkv, hd) + m_q.mean(axis=2)

    def unit(x):
        return x * jax.lax.rsqrt(jnp.maximum(
            jnp.mean(x * x, axis=-1, keepdims=True), 1e-30))

    q = _rotate(unit(q).reshape(T, H, hd), d).reshape(T, Hkv, g, hd)
    k = _rotate(unit(k) * w["temp"].astype(jnp.float32)[:, None], d)
    v = v.reshape(T, Hkv, hd)

    def block(args):
        qb, start = args
        s = jnp.einsum("qjgd,kjd->jgqk", qb, k,
                       precision=reference.HIGHEST) * hd ** -0.5
        seen = (start + jnp.arange(BLOCK))[:, None] >= jnp.arange(T)[None, :]
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("jgqk,kjd->qjgd", p, v,
                          precision=reference.HIGHEST)

    out = jax.lax.map(block, (q.reshape(T // BLOCK, BLOCK, Hkv, g, hd),
                              jnp.arange(T // BLOCK) * BLOCK))
    return linear(out.reshape(T, -1), w["o"], mode)


def merge(four, x, y):
    s_r, b_r, s_o, b_o = (four[i].astype(jnp.float32) for i in range(4))
    return s_r * (x + b_r) + s_o * (y + b_o)


def route(g, rho, w, eps: float, mode: str):
    """``g`` [T, D] (normed) and the stream of the layer before → ``(this
    layer's rho, chosen [T] of E + 1, its p [T])``."""
    f32 = partial(reference.linear, mode="float32")
    rho = reference.linear(g, w["router_down"], mode) \
        + w["router_gamma"].astype(jnp.float32) * rho
    n = reference.rms_norm(rho, w["router_norm"], eps)
    for i in range(3):
        n = f32(n, w[f"router_w{i}"]) + w[f"router_b{i}"].astype(jnp.float32)
        if i < 2:
            n = jax.nn.gelu(n, approximate=False)
    p = jax.nn.softmax(n, axis=-1)
    chosen = jnp.argmax(p + w["router_bias"].astype(jnp.float32), axis=-1)
    return rho, chosen, jnp.take_along_axis(p, chosen[:, None], axis=1)[:, 0]


def experts(g, chosen, p, w, mode: str):
    """Every expert in turn over the tokens that chose it; the skip (the
    id past the last expert) adds nothing."""
    linear = reference.linear

    def one_more(y, e_and_weights):
        e, gate, up, down = e_and_weights
        mine = jnp.where(chosen == e, p, 0.0)
        out = linear(jax.nn.silu(linear(g, gate, mode))
                     * linear(g, up, mode), down, mode)
        return y + mine[:, None] * out, None

    y, _ = jax.lax.scan(one_more, jnp.zeros_like(g), (
        jnp.arange(w["experts_gate"].shape[0]), w["experts_gate"],
        w["experts_up"], w["experts_down"]))
    return y


def layer_parts(x, rho, w, *, dims: tuple, eps: float, mode: str):
    """One layer over the two streams → ``(x', chosen, y, x'', rho)``: the
    residual after the attention's merge, the router's choice, the expert
    sublayer's sum, the residual after its merge, the router's stream."""
    d = dict(dims)
    rms_norm = reference.rms_norm
    x1 = merge(w["attn_merge"], x, attention(
        rms_norm(x, w["attn_norm"], eps), w, d, mode))
    g = rms_norm(x1, w["mlp_norm"], eps)
    rho, chosen, p = route(g, rho, w, eps, mode)
    y = experts(g, chosen, p, w, mode)
    return x1, chosen, y, merge(w["mlp_merge"], x1, y), rho


@partial(jax.jit, static_argnames=("dims", "eps", "mode"))
def _layer(x, rho, w, **kw):
    return layer_parts(x, rho, w, **kw)[3:]


def _load(ckpt, d: dict, li: int) -> dict:
    p = f"model.layers.{li}."
    a, r = p + "self_attn.", p + "mlp.router."
    names = {"attn_norm": p + "input_layernorm.weight",
             "mlp_norm": p + "post_attention_layernorm.weight",
             "q": a + "q_proj.weight", "k": a + "k_proj.weight",
             "v": a + "v_proj.weight", "o": a + "o_proj.weight",
             "conv0_w": a + "conv_qk.0.weight",
             "conv0_b": a + "conv_qk.0.bias",
             "conv1_w": a + "conv_qk.1.weight",
             "conv1_b": a + "conv_qk.1.bias", "temp": a + "temp",
             "router_down": r + "down_proj.weight",
             "router_norm": r + "norm.weight",
             "router_bias": r + "balancing_bias",
             **{f"router_w{i}": f"{r}mlp.{i}.weight" for i in range(3)},
             **{f"router_b{i}": f"{r}mlp.{i}.bias" for i in range(3)}}
    w = {key: ckpt.tensor(name) for key, name in names.items()}
    w["router_gamma"] = ckpt.tensor(r + "depth_scale") if li \
        else np.zeros((d["R"],), np.float32)
    for name in ("attn_merge", "mlp_merge"):
        w[name] = np.stack([ckpt.tensor(f"{p}self_attn_merge.{part}"
                                        if name == "attn_merge"
                                        else f"{p}mlp_merge.{part}")
                            for part in MERGE])
    for x in ("gate", "up", "down"):
        w[f"experts_{x}"] = np.stack([
            ckpt.tensor(f"{p}mlp.experts.{e}.{x}_proj.weight")
            for e in range(d["E"])])
    return w


@partial(jax.jit, static_argnames=("eps", "mode"))
def _head(x, norm, table, *, eps: float, mode: str):
    return reference.linear(reference.rms_norm(x, norm, eps), table, mode)


def _kept(a):
    """A block of logits where the comparison reads it: in the host's
    memory, under JAX's CPU backend where there is one (a request's
    logits over 262 272 rows are gigabytes, and the device's memory holds
    the reference's weights), else as the array it is."""
    try:
        return jax.device_put(a, jax.devices("cpu")[0])
    except RuntimeError:
        return a


def head_rows(xs, wanted, norm, table, *, eps: float, mode: str):
    """:func:`reference.head_rows` a block of ``HEAD_ROWS`` positions at a
    time, each block's logits moved to the host."""
    out = []
    for x, want in zip(xs, wanted):
        rows = np.asarray(want)
        n = -(-len(rows) // reference.ROWS) * reference.ROWS
        padded = np.concatenate(
            [rows, np.full(n - len(rows), rows[-1], rows.dtype)])
        # blocks of one size: the last one repeats the last position
        at = np.concatenate([padded, np.full(-n % HEAD_ROWS, padded[-1],
                                             padded.dtype)])
        blocks = [_kept(_head(x[at[i:i + HEAD_ROWS]], norm, table, eps=eps,
                              mode=mode))
                  for i in range(0, len(at), HEAD_ROWS)]
        out.append(jnp.concatenate(blocks)[:n])
    return out


def logits(ckpt, sequences: list[list[int]], wanted: list[range],
           mode: str = "float32") -> list[jax.Array]:
    """:func:`reference.logits` for this family: every position through
    the equations at the top of this file, no cache, no tails, one layer's
    weights on the device at a time; the head in blocks of positions, the
    logits kept on the host."""
    cfg = ckpt.config
    d = _dims(cfg)
    kw = dict(dims=tuple(d.items()), eps=float(cfg["rms_norm_eps"]),
              mode=mode)
    xs = reference.embed(ckpt, "model.embed_tokens.weight", sequences)
    T = -(-max(x.shape[0] for x in xs) // LENGTH) * LENGTH
    xs = [jnp.pad(x, ((0, T - x.shape[0]), (0, 0))) for x in xs]
    rhos = [jnp.zeros((T, d["R"]), jnp.float32) for _ in xs]
    for w in reference.layers_ahead(partial(_load, ckpt, d), d["L"]):
        both = jax.block_until_ready(
            [_layer(x, rho, w, **kw) for x, rho in zip(xs, rhos)])
        xs, rhos = [b[0] for b in both], [b[1] for b in both]
        del w
    norm = jax.device_put(ckpt.tensor("model.norm.weight"))
    table = jax.device_put(ckpt.tensor("model.embed_tokens.weight"))
    return head_rows(xs, wanted, norm, table, eps=kw["eps"], mode=mode)


# --------------------------------------------------------------- the costs


def attention_weights(cfg: dict) -> int:
    """Matmul weights of one attention sublayer: the four projections, the
    second convolution's blocks, the output's."""
    d = _dims(cfg)
    return d["D"] * (d["Cq"] + 2 * d["Ck"]) \
        + d["k1"] * (d["H"] + d["Hkv"]) * d["hd"] ** 2 + d["Cq"] * d["D"]


def router_weights(cfg: dict) -> int:
    d = _dims(cfg)
    return d["D"] * d["R"] + 2 * d["R"] ** 2 + d["R"] * (d["E"] + 1)


def expert_weights(cfg: dict) -> int:
    """Matmul weights of one expert."""
    d = _dims(cfg)
    return 3 * d["D"] * d["F"]


def unrouted_weights(cfg: dict) -> int:
    """Matmul weights every token passes through, whatever it chose: every
    layer's attention and router (not the table)."""
    return _dims(cfg)["L"] * (attention_weights(cfg) + router_weights(cfg))


def parameters(cfg: dict) -> int:
    """Everything held, vectors too: a layer's two norms, the depthwise
    convolution and the two convolutions' biases, the temperatures, the
    two merges, the router's ``gamma`` (layers past 0), norm, three biases
    and ``beta``."""
    d = _dims(cfg)
    vectors = 2 * d["D"] + d["C"] * (d["k0"] + 2) + d["Hkv"] + 8 * d["D"] \
        + 4 * d["R"] + 2 * (d["E"] + 1)
    return unrouted_weights(cfg) + d["L"] * (
        d["E"] * expert_weights(cfg) + vectors) - d["R"] \
        + d["V"] * d["D"] + d["D"]


def position_bytes(cfg: dict, itemsize: int = 2) -> int:
    """What one cached position holds over all layers: ``[v | k^]``."""
    d = _dims(cfg)
    return d["L"] * 2 * d["Ck"] * itemsize


def tail_bytes(cfg: dict, itemsize: int = 2) -> int:
    """What a sequence keeps beside its pages over all layers: the last
    ``k0 - 1`` rows of ``u``, ``k1 - 1`` of ``c0``, ``W_v2 h`` of the last
    position."""
    d = _dims(cfg)
    return d["L"] * ((d["k0"] + d["k1"] - 2) * d["C"] + d["Ck"] // 2) \
        * itemsize


def prefill_flops(cfg: dict, tokens: int) -> float:
    """Operations one prefill of ``tokens`` positions needs: 2 a weight a
    token through everything unrouted and through the one expert of a
    token that did not take the skip (an assignment lands on each of the
    router's ``E + 1`` outputs alike); attention over the causal half,
    scores and values of ``hd`` a head a pair; the head for one
    position."""
    d = _dims(cfg)
    T = tokens
    matmul = 2.0 * T * (unrouted_weights(cfg) + d["L"] * d["E"]
                        / (d["E"] + 1) * expert_weights(cfg)) \
        + 2.0 * d["V"] * d["D"]
    pairs = d["L"] * T * (T + 1) / 2
    return matmul + 2.0 * 2 * d["hd"] * d["H"] * pairs


def decode_bytes(cfg: dict, steps: list[dict], lengths: list[int],
                 itemsize: int = 2) -> float:
    """Bytes the decode steps must move: a step, everything unrouted and
    the table (the head) once and each expert that was hit once
    (``experts_hit``, summed over the layers, on the step's span); a
    decoded token, a position's ``[v | k^]`` a layer of each position
    behind it, and its tails read and written."""
    d = _dims(cfg)
    fixed = (unrouted_weights(cfg) + d["V"] * d["D"]) * itemsize
    hit = sum(int(s.get("experts_hit", 0)) for s in steps)
    return float(len(steps)) * fixed \
        + float(hit) * expert_weights(cfg) * itemsize \
        + float(sum(lengths)) * position_bytes(cfg, itemsize) \
        + 2.0 * len(lengths) * tail_bytes(cfg, itemsize)


# ------------------------------------------------------- the family's reader


def kv_share(obs, span: str, attr: str):
    """The compressed page's bytes the window's decode steps read (``attr``
    of every ``span``, as the program names it: filled positions times the
    ``[v | k^]`` every layer keeps of one) over the bytes those steps must
    move in all (:func:`decode_bytes`), in percent. None where the program
    names no such bytes."""
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
