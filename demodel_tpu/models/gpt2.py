"""GPT-2 family: learned positions, pre-LN blocks, fused QKV, tied head.

Checkpoint parity with HF ``transformers`` GPT2LMHeadModel is tested in
tests/test_hf_models.py (the HF Conv1D stores weights in ``x @ W``
orientation, which is exactly how this forward consumes them — no
transposes on the load path)."""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from demodel_tpu.models.common import (
    layer_norm, refuse_unsupported, use_flash_attention as _use_flash)
from demodel_tpu.models.hf_loader import Weights


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    layer_norm_epsilon: float = 1e-5
    dtype: str = "float32"

    @classmethod
    def tiny(cls) -> "GPT2Config":
        return cls(vocab_size=256, n_positions=64, n_embd=64, n_layer=2,
                   n_head=4)

    @classmethod
    def from_hf(cls, config: dict) -> "GPT2Config":
        refuse_unsupported(config)
        return cls(
            vocab_size=config.get("vocab_size", 50257),
            n_positions=config.get("n_positions", 1024),
            n_embd=config.get("n_embd", 768),
            n_layer=config.get("n_layer", 12),
            n_head=config.get("n_head", 12),
            layer_norm_epsilon=config.get("layer_norm_epsilon", 1e-5),
        )


def init_params(key, cfg: GPT2Config) -> dict:
    dt = jnp.dtype(cfg.dtype)
    D = cfg.n_embd
    keys = jax.random.split(key, cfg.n_layer + 2)

    def dense(k, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                / np.sqrt(shape[0])).astype(dt)

    layers = []
    for i in range(cfg.n_layer):
        ks = jax.random.split(keys[i], 4)
        layers.append({
            "ln_1": {"w": jnp.ones((D,), dt), "b": jnp.zeros((D,), dt)},
            "c_attn": {"w": dense(ks[0], (D, 3 * D)),
                       "b": jnp.zeros((3 * D,), dt)},
            "c_proj": {"w": dense(ks[1], (D, D)), "b": jnp.zeros((D,), dt)},
            "ln_2": {"w": jnp.ones((D,), dt), "b": jnp.zeros((D,), dt)},
            "mlp_fc": {"w": dense(ks[2], (D, 4 * D)),
                       "b": jnp.zeros((4 * D,), dt)},
            "mlp_proj": {"w": dense(ks[3], (4 * D, D)),
                         "b": jnp.zeros((D,), dt)},
        })
    return {
        "wte": (jax.random.normal(keys[-2], (cfg.vocab_size, D), jnp.float32)
                * 0.02).astype(dt),
        "wpe": (jax.random.normal(keys[-1], (cfg.n_positions, D), jnp.float32)
                * 0.01).astype(dt),
        "layers": layers,
        "ln_f": {"w": jnp.ones((D,), dt), "b": jnp.zeros((D,), dt)},
    }


def param_shardings(cfg: GPT2Config, mesh: Mesh) -> dict:
    tp = int(mesh.shape.get("tp", 1))

    def sh(*spec):
        return NamedSharding(mesh, P(*spec))

    def ln():
        return {"w": sh(None), "b": sh(None)}

    col_ok = (3 * cfg.n_embd) % tp == 0 and (4 * cfg.n_embd) % tp == 0
    layer = {
        "ln_1": ln(),
        "c_attn": {"w": sh(None, "tp") if col_ok else sh(None, None),
                   "b": sh(None)},
        "c_proj": {"w": sh("tp", None) if cfg.n_embd % tp == 0 else sh(None, None),
                   "b": sh(None)},
        "ln_2": ln(),
        "mlp_fc": {"w": sh(None, "tp") if col_ok else sh(None, None),
                   "b": sh(None)},
        "mlp_proj": {"w": sh("tp", None) if col_ok else sh(None, None),
                     "b": sh(None)},
    }
    return {
        "wte": sh(None, None),
        "wpe": sh(None, None),
        "layers": [dict(layer) for _ in range(cfg.n_layer)],
        "ln_f": ln(),
    }


from_hf = GPT2Config.from_hf


def load_params(weights: dict, cfg: GPT2Config, mesh=None) -> dict:
    """The tree of :func:`init_params` from a checkpoint, each leaf with
    the placement it arrived with (``mesh`` lays nothing out)."""
    w = Weights(weights)
    layers = []
    for i in range(cfg.n_layer):
        pre = f"h.{i}."
        layers.append({
            "ln_1": {"w": w.get(pre + "ln_1.weight"),
                     "b": w.get(pre + "ln_1.bias")},
            "c_attn": {"w": w.get(pre + "attn.c_attn.weight"),
                       "b": w.get(pre + "attn.c_attn.bias")},
            "c_proj": {"w": w.get(pre + "attn.c_proj.weight"),
                       "b": w.get(pre + "attn.c_proj.bias")},
            "ln_2": {"w": w.get(pre + "ln_2.weight"),
                     "b": w.get(pre + "ln_2.bias")},
            "mlp_fc": {"w": w.get(pre + "mlp.c_fc.weight"),
                       "b": w.get(pre + "mlp.c_fc.bias")},
            "mlp_proj": {"w": w.get(pre + "mlp.c_proj.weight"),
                         "b": w.get(pre + "mlp.c_proj.bias")},
        })
    return {
        "wte": w.get("wte.weight"),
        "wpe": w.get("wpe.weight"),
        "layers": layers,
        "ln_f": {"w": w.get("ln_f.weight"), "b": w.get("ln_f.bias")},
    }


def forward(params, tokens, cfg: GPT2Config, mesh: Mesh | None = None):
    """tokens [B, T] → logits [B, T, V] (head tied to wte, as HF)."""
    del mesh  # dense attention; sharding comes from param placement
    B, T = tokens.shape
    eps = cfg.layer_norm_epsilon
    x = params["wte"][tokens] + params["wpe"][jnp.arange(T)]
    H = cfg.n_head
    hd = cfg.n_embd // H
    mask = jnp.tril(jnp.ones((T, T), bool))
    for layer in params["layers"]:
        h = layer_norm(x, layer["ln_1"]["w"], layer["ln_1"]["b"], eps)
        qkv = h @ layer["c_attn"]["w"] + layer["c_attn"]["b"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T, H, hd)
        k = k.reshape(B, T, H, hd)
        v = v.reshape(B, T, H, hd)
        if _use_flash():
            from demodel_tpu.ops.flash_attention import flash_attention

            a = flash_attention(q, k, v, causal=True).reshape(B, T, -1)
        else:
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
            scores = jnp.where(mask[None, None], scores, -1e30)
            probs = jax.nn.softmax(scores.astype(jnp.float32),
                                   -1).astype(x.dtype)
            a = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, T, -1)
        x = x + (a @ layer["c_proj"]["w"] + layer["c_proj"]["b"])
        h = layer_norm(x, layer["ln_2"]["w"], layer["ln_2"]["b"], eps)
        h = jax.nn.gelu(h @ layer["mlp_fc"]["w"] + layer["mlp_fc"]["b"],
                        approximate=True)
        x = x + (h @ layer["mlp_proj"]["w"] + layer["mlp_proj"]["b"])
    x = layer_norm(x, params["ln_f"]["w"], params["ln_f"]["b"], eps)
    return x @ params["wte"].T  # tied head
