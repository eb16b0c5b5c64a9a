"""The Llama family: ``LlamaForCausalLM`` as Hugging Face names it.

RMSNorm before attention and before the MLP, rotate-half RoPE on every
layer, grouped-query causal attention, SwiGLU, an untied output head. The
reference follows those published equations in float32; the costs count
what the mathematics requires, not what the program happens to execute, so
a roofline share can only be too low, never above 100 %:

- matmuls: every linear layer, 2 operations per weight per token; the
  output head for the one position whose logits are used;
- attention: scores and the weighted sum over the causal half (query i
  sees keys 0..i), ``4 * hd`` operations per head and (query, key) pair.
  The program's einsum over the whole square does twice that;
- a decode step reads every weight once (the embedding only one row per
  sequence, which is left out) and the filled part of the cache at the
  configuration's KV heads, not the padded bucket.

``as_executed_prefill_flops`` is the program's own count (whole square,
head over every position) and exists so that ``tests/test_reduction.py``
can hold these formulas against ``compiled.cost_analysis()``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import reference
from . import Filled

_LAYER_TENSORS = {
    "attn_norm": "input_layernorm.weight",
    "q": "self_attn.q_proj.weight", "k": "self_attn.k_proj.weight",
    "v": "self_attn.v_proj.weight", "o": "self_attn.o_proj.weight",
    "mlp_norm": "post_attention_layernorm.weight",
    "gate": "mlp.gate_proj.weight", "up": "mlp.up_proj.weight",
    "down": "mlp.down_proj.weight",
}


def _dims(cfg: dict):
    D, I = cfg["hidden_size"], cfg["intermediate_size"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or D // H
    return D, I, H, Hkv, hd, cfg["num_hidden_layers"], cfg["vocab_size"]


def rehearsal(config: dict) -> dict:
    """The toy the rehearsal swaps in for the published sizes."""
    return {"hidden_size": 128, "intermediate_size": 256,
            "num_hidden_layers": 2, "num_attention_heads": 8,
            "num_key_value_heads": 4, "vocab_size": 512}


# ------------------------------------------------------------- the weights


def tensors(config: dict) -> dict[str, Filled]:
    """HF tensor name → shape and fill: every matrix N(0, 1/fan_in) over
    its ``[out, in]`` layout's inputs, every norm ones."""
    D, I, H, Hkv, hd, L, V = _dims(config)

    def matrix(out: int, fan_in: int) -> Filled:
        return Filled((out, fan_in), "normal", fan_in)

    norm = Filled((D,), "ones")
    table = {"model.embed_tokens.weight": matrix(V, D)}
    for i in range(L):
        p = f"model.layers.{i}."
        table.update({
            p + "input_layernorm.weight": norm,
            p + "self_attn.q_proj.weight": matrix(H * hd, D),
            p + "self_attn.k_proj.weight": matrix(Hkv * hd, D),
            p + "self_attn.v_proj.weight": matrix(Hkv * hd, D),
            p + "self_attn.o_proj.weight": matrix(D, H * hd),
            p + "post_attention_layernorm.weight": norm,
            p + "mlp.gate_proj.weight": matrix(I, D),
            p + "mlp.up_proj.weight": matrix(I, D),
            p + "mlp.down_proj.weight": matrix(D, I),
        })
    table.update({"model.norm.weight": norm, "lm_head.weight": matrix(V, D)})
    return table


# ----------------------------------------------------------- the reference


@partial(jax.jit, static_argnames=("H", "Hkv", "eps", "theta", "mode"))
def _layer(x, w, *, H: int, Hkv: int, eps: float, theta: float, mode: str):
    linear, rms_norm, rope = (reference.linear, reference.rms_norm,
                              reference.rope)
    T, _D = x.shape
    h = rms_norm(x, w["attn_norm"], eps)
    q = linear(h, w["q"], mode).reshape(T, H, -1)
    k = linear(h, w["k"], mode).reshape(T, Hkv, -1)
    v = linear(h, w["v"], mode).reshape(T, Hkv, -1)
    hd = q.shape[-1]
    q, k = rope(q, theta), rope(k, theta)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   precision=reference.HIGHEST) / np.sqrt(hd)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("hqk,khd->qhd", p, v,
                   precision=reference.HIGHEST).reshape(T, -1)
    x = x + linear(a, w["o"], mode)
    h = rms_norm(x, w["mlp_norm"], eps)
    y = jax.nn.silu(linear(h, w["gate"], mode)) * linear(h, w["up"], mode)
    return x + linear(y, w["down"], mode)


def logits(ckpt, sequences: list[list[int]], wanted: list[range],
           mode: str = "float32") -> list[jax.Array]:
    """:func:`reference.logits` for this family."""
    cfg = ckpt.config
    eps = float(cfg["rms_norm_eps"])
    kw = dict(H=cfg["num_attention_heads"], Hkv=cfg["num_key_value_heads"],
              eps=eps, theta=float(cfg["rope_theta"]), mode=mode)
    xs = reference.embed(ckpt, "model.embed_tokens.weight", sequences)

    def load(i: int) -> dict:
        return {k: ckpt.tensor(f"model.layers.{i}.{name}")
                for k, name in _LAYER_TENSORS.items()}

    for w in reference.layers_ahead(load, cfg["num_hidden_layers"]):
        xs = jax.block_until_ready([_layer(x, w, **kw) for x in xs])
        del w
    norm = jax.device_put(ckpt.tensor("model.norm.weight"))
    head = jax.device_put(ckpt.tensor("lm_head.weight"))
    return reference.head_rows(xs, wanted, norm, head, eps=eps, mode=mode)


# --------------------------------------------------------------- the costs


def layer_weights(cfg: dict) -> int:
    """Matmul weights of one block."""
    D, I, H, Hkv, hd, _L, _V = _dims(cfg)
    return D * H * hd + 2 * D * Hkv * hd + H * hd * D + 3 * D * I


def parameters(cfg: dict) -> int:
    D, _I, _H, _Hkv, _hd, L, V = _dims(cfg)
    return L * (layer_weights(cfg) + 2 * D) + 2 * V * D + D


def prefill_flops(cfg: dict, tokens: int) -> float:
    """Operations one prefill of ``tokens`` positions needs."""
    D, _I, H, _Hkv, hd, L, V = _dims(cfg)
    T = tokens
    matmul = 2.0 * T * L * layer_weights(cfg) + 2.0 * V * D
    attention = L * 4.0 * hd * H * (T * (T + 1) / 2)
    return matmul + attention


def as_executed_prefill_flops(cfg: dict, tokens: int) -> float:
    D, _I, H, _Hkv, hd, L, V = _dims(cfg)
    T = tokens
    return (2.0 * T * L * layer_weights(cfg) + 2.0 * T * V * D
            + L * 4.0 * hd * H * T * T)


def kv_bytes_per_position(cfg: dict, itemsize: int = 2) -> int:
    _D, _I, _H, Hkv, hd, L, _V = _dims(cfg)
    return 2 * L * Hkv * hd * itemsize


def decode_bytes(cfg: dict, steps: list[dict], lengths: list[int],
                 itemsize: int = 2) -> float:
    """Bytes the decode steps must read: the weights once a step, and the
    cached positions behind every decoded token (every layer reads them
    all, so only their sum counts here, and only the number of steps)."""
    D, _I, _H, _Hkv, _hd, L, V = _dims(cfg)
    weights = (L * layer_weights(cfg) + V * D) * itemsize
    return float(len(steps)) * weights + float(sum(lengths)) \
        * kv_bytes_per_position(cfg, itemsize)
