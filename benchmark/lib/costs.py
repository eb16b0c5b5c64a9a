"""What the algorithm needs, from the configuration's shapes: operations
of a prefill and bytes of a decode step. These, over the chip's peaks
(``peaks.json``), are the least time the chip could take; a roofline share
is that over the time the device took.

They count what the mathematics requires, not what the program happens to
execute, so a share can only be too low, never above 100 %:

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


def _dims(cfg: dict):
    D, I = cfg["hidden_size"], cfg["intermediate_size"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or D // H
    return D, I, H, Hkv, hd, cfg["num_hidden_layers"], cfg["vocab_size"]


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


def decode_bytes(cfg: dict, steps: int, positions_read: int,
                 itemsize: int = 2) -> float:
    """Bytes ``steps`` decode steps must read: the weights once a step,
    and ``positions_read`` cached positions in all."""
    D, _I, _H, _Hkv, _hd, L, V = _dims(cfg)
    weights = (L * layer_weights(cfg) + V * D) * itemsize
    return float(steps) * weights + float(positions_read) \
        * kv_bytes_per_position(cfg, itemsize)
