"""Shared numerics for the model families.

Norm statistics run in float32 regardless of activation dtype: bf16 mean/
variance across a wide hidden axis loses enough mantissa to shift logits —
the standard TPU-stable recipe (compute stats in fp32, scale in the
activation dtype).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from demodel_tpu.utils.env import env_bool


def rms_norm(x, weight, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    scale = lax.rsqrt((xf * xf).mean(axis=-1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype) * weight


def layer_norm(x, weight, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return y.astype(x.dtype) * weight + bias


def use_flash_attention() -> bool:
    """Route model attention through the fused pallas kernel
    (ops/flash_attention.py)? Only when ``DEMODEL_FLASH_ATTN`` says so:
    the einsum path lets XLA fuse freely at short sequence, and whether
    flash wins once the score tensor or the GQA-repeated KV cache
    dominates HBM has not been measured on the chip."""
    return env_bool("DEMODEL_FLASH_ATTN")
