"""The EXAONE-MoE family (``model_type`` ``exaone_moe``), as one chip's
share of an expert-parallel replica holds it.

For layer input ``x`` [T, D], every norm an RMSNorm with a learned weight:

- ``a = attn(x)``; ``x += RMSNorm(a)``. ``q = x Wq`` [T, H, hd], ``k = x Wk``,
  ``v = x Wv`` [T, Hkv, hd]; q and k get an RMSNorm over ``hd`` (one weight
  a layer). In a ``sliding_attention`` layer q and k are rotated (RoPE,
  ``rope_theta``, whole head) and ``i`` attends to ``j`` with ``0 <= i - j <
  window``; in a ``full_attention`` layer nothing is rotated and ``i``
  attends to every ``j <= i``. Scores ``q k / sqrt(hd)``, softmax in
  float32, ``a = out Wo``.
- ``m = mlp(x)``; ``x += RMSNorm(m)``. A ``dense`` layer is a SwiGLU of
  ``intermediate_size``. A ``sparse`` layer: ``s = sigmoid(x Wr)`` in
  float32 in every mode, ``router`` outputs wide; the
  ``num_experts_per_tok`` experts with the largest ``s + b`` are chosen;
  ``w_e = routed_scaling_factor * s_e / sum(chosen s)``; ``mlp(x) =
  sum(chosen, held) w_e E_e(x) + S(x)``, ``E_e`` and ``S`` SwiGLUs of
  ``moe_intermediate_size``.
- A final RMSNorm and the untied head.

**The share.** ``num_experts`` is how many experts are held; the router is
``num_experts * ep_size`` wide, and the held experts are ``[ep_rank *
num_experts, (ep_rank + 1) * num_experts)`` (top-level ``ep_size``,
``ep_rank`` of the configuration, which the program reads from the same
``config.json``). What the absent experts would add is left out here as in
the program, and the partial result goes on to the next layer.
``layer_types``, ``sliding_windows`` and ``mlp_layer_types`` keep their
published length; the first ``num_hidden_layers`` entries count. The
multi-token-prediction layer is left out (it changes no next-token logit).

The costs count what the mathematics needs: the held experts a step reads
are those its ``experts_hit`` attribute says were hit (none where the span
has no such attribute), a window layer reads ``min(length, window - 1)``
cached positions, the head has ``vocab_size`` rows. A prefill's routed
operations are the expected ones (a token's ``num_experts_per_tok``
assignments fall on held experts with probability ``1 / ep_size``).

Imports nothing of the program under test.
"""

from __future__ import annotations

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import reference, xplane
from . import Filled

#: sequences whose bfloat16 forward is run beside the float32 one, to count
#: the top-k choices the two precisions make differently
SHADOWED = 4


def _dims(cfg: dict) -> dict:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    L = cfg["num_hidden_layers"]
    kinds = cfg["layer_types"][:L]
    windows = [int(w) if kind == "sliding_attention" else 0
               for kind, w in zip(kinds, cfg["sliding_windows"][:L])]
    held, ep = cfg["num_experts"], cfg.get("ep_size", 1)
    return dict(
        D=D, H=H, Hkv=cfg["num_key_value_heads"],
        hd=cfg.get("head_dim") or D // H, L=L, V=cfg["vocab_size"],
        I=cfg["intermediate_size"], F=cfg["moe_intermediate_size"],
        Fs=cfg["moe_intermediate_size"] * cfg.get("num_shared_experts", 0),
        held=held, router=held * ep, ep=ep,
        first=cfg.get("ep_rank", 0) * held, K=cfg["num_experts_per_tok"],
        windows=windows,
        sparse=[m == "sparse" for m in cfg["mlp_layer_types"][:L]])


def rehearsal(config: dict) -> dict:
    """The toy the rehearsal swaps in: two periods, window 32, a quarter
    of 16 experts held."""
    return {"hidden_size": 128, "intermediate_size": 256,
            "moe_intermediate_size": 64, "num_hidden_layers": 8,
            "num_attention_heads": 8, "num_key_value_heads": 2,
            "head_dim": 16, "vocab_size": 512, "num_experts": 4,
            "num_experts_per_tok": 4, "ep_size": 4, "ep_rank": 1,
            "sliding_window": 32,
            "sliding_windows": [32, 32, 32, 0] * 2}


# ------------------------------------------------------------- the weights


def tensors(config: dict) -> dict[str, Filled]:
    """Hugging Face's names in the checkpoint's order. A matrix is N(0,
    1/fan_in) over its ``[out, in]`` layout's inputs, a norm ones, the
    selection bias zeros; the embedding's fan-in is 1 (a row is selected,
    nothing is summed), so the first layer's input is of unit scale. The
    experts are one tensor a projection an expert, under their index in
    the whole layer."""
    d = _dims(config)
    D, hd = d["D"], d["hd"]

    def matrix(out: int, fan_in: int) -> Filled:
        return Filled((out, fan_in), "normal", fan_in)

    def swiglu(prefix: str, width: int) -> dict:
        return {prefix + "gate_proj.weight": matrix(width, D),
                prefix + "up_proj.weight": matrix(width, D),
                prefix + "down_proj.weight": matrix(D, width)}

    table = {"model.embed_tokens.weight": Filled((d["V"], D), "normal", 1)}
    for i in range(d["L"]):
        p = f"model.layers.{i}."
        table.update({
            p + "self_attn.q_proj.weight": matrix(d["H"] * hd, D),
            p + "self_attn.k_proj.weight": matrix(d["Hkv"] * hd, D),
            p + "self_attn.v_proj.weight": matrix(d["Hkv"] * hd, D),
            p + "self_attn.o_proj.weight": matrix(D, d["H"] * hd),
            p + "self_attn.q_norm.weight": Filled((hd,), "ones"),
            p + "self_attn.k_norm.weight": Filled((hd,), "ones"),
            p + "post_attn_layernorm.weight": Filled((D,), "ones"),
            p + "post_feedforward_layernorm.weight": Filled((D,), "ones"),
        })
        if not d["sparse"][i]:
            table.update(swiglu(p + "mlp.", d["I"]))
            continue
        table[p + "mlp.gate.weight"] = matrix(d["router"], D)
        table[p + "mlp.gate.e_score_correction_bias"] = Filled(
            (d["router"],), "zeros")
        for e in range(d["first"], d["first"] + d["held"]):
            table.update(swiglu(f"{p}mlp.experts.{e}.", d["F"]))
        if d["Fs"]:
            table.update(swiglu(p + "mlp.shared_experts.", d["Fs"]))
    table.update({"model.norm.weight": Filled((D,), "ones"),
                  "lm_head.weight": matrix(d["V"], D)})
    return table


# ----------------------------------------------------------- the reference


def _swiglu(x, gate, up, down, mode: str):
    linear = reference.linear
    return linear(jax.nn.silu(linear(x, gate, mode)) * linear(x, up, mode),
                  down, mode)


@partial(jax.jit, static_argnames=("H", "Hkv", "eps", "theta", "window",
                                   "K", "scale", "first", "norm_topk",
                                   "mode"))
def _layer(x, w, *, H: int, Hkv: int, eps: float, theta: float, window: int,
           K: int, scale: float, first: int, norm_topk: bool, mode: str):
    """One layer over ``x`` [T, D] → ``(x, chosen [T, K] or None)``."""
    linear, rms_norm = reference.linear, reference.rms_norm
    T, _D = x.shape
    q = linear(x, w["q"], mode).reshape(T, H, -1)
    k = linear(x, w["k"], mode).reshape(T, Hkv, -1)
    v = linear(x, w["v"], mode).reshape(T, Hkv, -1)
    hd = q.shape[-1]
    q, k = rms_norm(q, w["q_norm"], eps), rms_norm(k, w["k_norm"], eps)
    if window:      # a full layer has no positional encoding
        q, k = reference.rope(q, theta), reference.rope(k, theta)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   precision=reference.HIGHEST) / np.sqrt(hd)
    behind = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    seen = (behind >= 0) & (behind < window if window else True)
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("hqk,khd->qhd", p, v,
                   precision=reference.HIGHEST).reshape(T, -1)
    x = x + rms_norm(linear(a, w["o"], mode), w["attn_norm"], eps)

    chosen = None
    if "router" not in w:
        m = _swiglu(x, w["gate"], w["up"], w["down"], mode)
    else:
        score = jax.nn.sigmoid(linear(x, w["router"], "float32"))
        _best, chosen = jax.lax.top_k(
            score + w["router_bias"].astype(jnp.float32), K)
        weight = jnp.take_along_axis(score, chosen, axis=1)
        if norm_topk:
            weight = weight / weight.sum(axis=1, keepdims=True)
        weight = weight * scale
        m = _swiglu(x, w["shared_gate"], w["shared_up"], w["shared_down"],
                    mode) if "shared_gate" in w else jnp.zeros_like(x)
        def one_more(m, e_and_weights):     # the held experts, in turn
            e, gate, up, down = e_and_weights
            mine = jnp.where(chosen == first + e, weight, 0.0).sum(axis=1)
            return m + mine[:, None] * _swiglu(x, gate, up, down, mode), None

        held = w["experts_gate"].shape[0]
        m, _ = jax.lax.scan(one_more, m, (
            jnp.arange(held), w["experts_gate"], w["experts_up"],
            w["experts_down"]))
    return x + rms_norm(m, w["mlp_norm"], eps), chosen


def _load(ckpt, d: dict, i: int) -> dict:
    p = f"model.layers.{i}."
    names = {"q": "self_attn.q_proj", "k": "self_attn.k_proj",
             "v": "self_attn.v_proj", "o": "self_attn.o_proj",
             "q_norm": "self_attn.q_norm", "k_norm": "self_attn.k_norm",
             "attn_norm": "post_attn_layernorm",
             "mlp_norm": "post_feedforward_layernorm"}
    if d["sparse"][i]:
        names.update({"router": "mlp.gate"})
        if d["Fs"]:
            names.update({f"shared_{x}": f"mlp.shared_experts.{x}_proj"
                          for x in ("gate", "up", "down")})
    else:
        names.update({x: f"mlp.{x}_proj" for x in ("gate", "up", "down")})
    w = {key: ckpt.tensor(f"{p}{name}.weight") for key, name in names.items()}
    if d["sparse"][i]:
        w["router_bias"] = ckpt.tensor(
            p + "mlp.gate.e_score_correction_bias")
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
    kw = dict(H=d["H"], Hkv=d["Hkv"], eps=eps,
              theta=float(cfg["rope_parameters"]["rope_theta"]), K=d["K"],
              scale=float(cfg.get("routed_scaling_factor", 1.0)),
              first=d["first"], norm_topk=bool(cfg.get("norm_topk_prob",
                                                       True)))
    xs = reference.embed(ckpt, "model.embed_tokens.weight", sequences)
    shadow = list(xs[:SHADOWED]) if mode == "float32" else []
    differed = on_held = pairs = 0
    layers = reference.layers_ahead(partial(_load, ckpt, d), d["L"])
    for window, w in zip(d["windows"], layers):
        out = [_layer(x, w, window=window, mode=mode, **kw) for x in xs]
        low = [_layer(x, w, window=window, mode="bfloat16", **kw)
               for x in shadow]
        xs = jax.block_until_ready([x for x, _c in out])
        shadow = [x for x, _c in low]
        for (_x, ours), (_y, theirs), seq in zip(out, low, sequences):
            if ours is None:
                continue
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
        print(f"[bench] exaone_moe reference: {differed} of {pairs} (token, "
              f"layer) top-{d['K']} choices differ between bfloat16 and "
              f"float32 over {len(shadow)} sequences, {on_held} of them in "
              "a held expert", flush=True)
    norm = jax.device_put(ckpt.tensor("model.norm.weight"))
    head = jax.device_put(ckpt.tensor("lm_head.weight"))
    return reference.head_rows(xs, wanted, norm, head, eps=eps, mode=mode)


# --------------------------------------------------------------- the costs


def attention_weights(cfg: dict) -> int:
    d = _dims(cfg)
    return 2 * d["D"] * d["H"] * d["hd"] + 2 * d["D"] * d["Hkv"] * d["hd"]


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
        + n_sparse * (3 * d["D"] * d["Fs"] + d["D"] * d["router"])


def parameters(cfg: dict) -> int:
    """Matrices held on this chip (norms and the selection bias apart)."""
    d = _dims(cfg)
    return unrouted_weights(cfg) \
        + sum(d["sparse"]) * d["held"] * expert_weights(cfg) \
        + 2 * d["V"] * d["D"]


def prefill_flops(cfg: dict, tokens: int) -> float:
    """Operations one prefill of ``tokens`` positions needs: 2 a weight a
    token through everything unrouted and through the expected ``K /
    ep_size`` held experts a token a sparse layer; attention over the
    pairs a query sees (the causal half, cut at the window in a window
    layer), ``4 * hd`` a head a pair; the head for one position."""
    d = _dims(cfg)
    T = tokens
    matmul = 2.0 * T * (unrouted_weights(cfg) + sum(d["sparse"])
                        * d["K"] / d["ep"] * expert_weights(cfg)) \
        + 2.0 * d["V"] * d["D"]
    pairs = sum(sum(min(i + 1, w) if w else i + 1 for i in range(T))
                for w in d["windows"])
    return matmul + 4.0 * d["hd"] * d["H"] * pairs


def decode_bytes(cfg: dict, steps: list[dict], lengths: list[int],
                 itemsize: int = 2) -> float:
    """Bytes the decode steps must read: a step, everything unrouted and
    the head once and each held expert that was hit once
    (``experts_hit``, summed over the sparse layers, on the step's span);
    a decoded token, the cached positions its layers see at the KV heads:
    ``length`` in a full layer, ``min(length, window - 1)`` in a window
    layer."""
    d = _dims(cfg)
    fixed = (unrouted_weights(cfg) + d["V"] * d["D"]) * itemsize
    hit = sum(int(s.get("experts_hit", 0)) for s in steps)
    position = 2 * d["Hkv"] * d["hd"] * itemsize        # K and V, a layer
    cached = sum(sum(min(n, w - 1) if w else n for w in d["windows"])
                 for n in lengths)
    return float(len(steps)) * fixed \
        + float(hit) * expert_weights(cfg) * itemsize \
        + float(cached) * position


# ------------------------------------------------------- the family's readers


def _hit_steps(obs, span: str) -> list[dict]:
    return [s for s in obs.window_spans(span)
            if "experts_hit" in s.get("attrs", {})]


def expert_bytes_roofline(obs, span: str, pattern: str):
    """The least time the window's grouped products could take reading the
    experts their steps hit (``experts_hit`` of every ``span``, times one
    expert's bytes, over the peak HBM rate) over the device time of the
    operations matching ``pattern`` inside those spans, in percent."""
    steps = _hit_steps(obs, span)
    if not steps or obs.trace is None or not obs.trace.devices:
        return None
    rx = re.compile(pattern)
    inside = [(s["ts"], s["ts"] + s["dur"]) for s in steps]

    def took(ops) -> float:
        keep = np.asarray([bool(rx.search(n)) for n in ops.names], bool)
        if not keep.any():
            return 0.0
        return xplane.seconds_within(ops.take(keep[ops.name_id]), inside)

    secs = sum(took(ops) for ops in obs.trace.devices.values()) \
        / len(obs.trace.devices)
    if not secs:
        return None
    need = sum(s["attrs"]["experts_hit"] for s in steps) \
        * expert_weights(obs.model) * 2.0
    return 100.0 * need / (obs.peaks["hbm_bytes_per_s"] * obs.chips) / secs


def tokens_per_expert_hit(obs, span: str):
    """Over the window's ``span``s: assignments that landed on a held
    expert (``expert_tokens``) per held expert hit (``experts_hit``): how
    many rows share one read of an expert's weights."""
    steps = _hit_steps(obs, span)
    hit = sum(s["attrs"]["experts_hit"] for s in steps)
    if not hit:
        return None
    return sum(s["attrs"]["expert_tokens"] for s in steps) / hit
