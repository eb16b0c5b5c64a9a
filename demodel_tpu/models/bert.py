"""BERT encoder family (the client matrix's ``bert-base-uncased`` config —
reference BASELINE config 3 pulls it via ``transformers``).

Post-LN encoder with additive padding masks; parity with HF
``BertModel``'s last_hidden_state is tested in tests/test_hf_models.py,
including fully-padded rows (which must stay finite — the mask adds a
large negative, never -inf, so softmax keeps a valid distribution)."""

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
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    dtype: str = "float32"

    @classmethod
    def tiny(cls) -> "BertConfig":
        return cls(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                   num_attention_heads=4, intermediate_size=128,
                   max_position_embeddings=64)

    @classmethod
    def from_hf(cls, config: dict) -> "BertConfig":
        refuse_unsupported(config)
        return cls(
            vocab_size=config.get("vocab_size", 30522),
            hidden_size=config.get("hidden_size", 768),
            num_hidden_layers=config.get("num_hidden_layers", 12),
            num_attention_heads=config.get("num_attention_heads", 12),
            intermediate_size=config.get("intermediate_size", 3072),
            max_position_embeddings=config.get("max_position_embeddings", 512),
            type_vocab_size=config.get("type_vocab_size", 2),
            layer_norm_eps=config.get("layer_norm_eps", 1e-12),
        )


def init_params(key, cfg: BertConfig) -> dict:
    dt = jnp.dtype(cfg.dtype)
    D, I = cfg.hidden_size, cfg.intermediate_size
    keys = jax.random.split(key, cfg.num_hidden_layers + 3)

    def dense(k, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                / np.sqrt(shape[0])).astype(dt)

    def ln():
        return {"w": jnp.ones((D,), dt), "b": jnp.zeros((D,), dt)}

    layers = []
    for i in range(cfg.num_hidden_layers):
        ks = jax.random.split(keys[i], 6)
        layers.append({
            "q": {"w": dense(ks[0], (D, D)), "b": jnp.zeros((D,), dt)},
            "k": {"w": dense(ks[1], (D, D)), "b": jnp.zeros((D,), dt)},
            "v": {"w": dense(ks[2], (D, D)), "b": jnp.zeros((D,), dt)},
            "attn_out": {"w": dense(ks[3], (D, D)), "b": jnp.zeros((D,), dt)},
            "attn_ln": ln(),
            "inter": {"w": dense(ks[4], (D, I)), "b": jnp.zeros((I,), dt)},
            "out": {"w": dense(ks[5], (I, D)), "b": jnp.zeros((D,), dt)},
            "out_ln": ln(),
        })
    return {
        "word_emb": (jax.random.normal(keys[-3], (cfg.vocab_size, D),
                                       jnp.float32) * 0.02).astype(dt),
        "pos_emb": (jax.random.normal(keys[-2], (cfg.max_position_embeddings,
                                                 D), jnp.float32)
                    * 0.02).astype(dt),
        "type_emb": (jax.random.normal(keys[-1], (cfg.type_vocab_size, D),
                                       jnp.float32) * 0.02).astype(dt),
        "emb_ln": ln(),
        "layers": layers,
    }


def param_shardings(cfg: BertConfig, mesh: Mesh) -> dict:
    tp = int(mesh.shape.get("tp", 1))

    def sh(*spec):
        return NamedSharding(mesh, P(*spec))

    def ln():
        return {"w": sh(None), "b": sh(None)}

    ok_d = cfg.hidden_size % tp == 0
    ok_i = cfg.intermediate_size % tp == 0
    layer = {
        "q": {"w": sh(None, "tp") if ok_d else sh(None, None), "b": sh(None)},
        "k": {"w": sh(None, "tp") if ok_d else sh(None, None), "b": sh(None)},
        "v": {"w": sh(None, "tp") if ok_d else sh(None, None), "b": sh(None)},
        "attn_out": {"w": sh("tp", None) if ok_d else sh(None, None),
                     "b": sh(None)},
        "attn_ln": ln(),
        "inter": {"w": sh(None, "tp") if ok_i else sh(None, None),
                  "b": sh(None)},
        "out": {"w": sh("tp", None) if ok_i else sh(None, None),
                "b": sh(None)},
        "out_ln": ln(),
    }
    return {
        "word_emb": sh(None, None),
        "pos_emb": sh(None, None),
        "type_emb": sh(None, None),
        "emb_ln": ln(),
        "layers": [dict(layer) for _ in range(cfg.num_hidden_layers)],
    }


from_hf = BertConfig.from_hf


def load_params(weights: dict, cfg: BertConfig, mesh=None) -> dict:
    """The tree of :func:`init_params` from a checkpoint, each leaf with
    the placement it arrived with (``mesh`` lays nothing out)."""
    w = Weights(weights)

    def lin(name):
        return {"w": w.get(name + ".weight", transpose=True),
                "b": w.get(name + ".bias")}

    def ln(name):
        return {"w": w.get(name + ".weight"), "b": w.get(name + ".bias")}

    layers = []
    for i in range(cfg.num_hidden_layers):
        pre = f"encoder.layer.{i}."
        layers.append({
            "q": lin(pre + "attention.self.query"),
            "k": lin(pre + "attention.self.key"),
            "v": lin(pre + "attention.self.value"),
            "attn_out": lin(pre + "attention.output.dense"),
            "attn_ln": ln(pre + "attention.output.LayerNorm"),
            "inter": lin(pre + "intermediate.dense"),
            "out": lin(pre + "output.dense"),
            "out_ln": ln(pre + "output.LayerNorm"),
        })
    return {
        "word_emb": w.get("embeddings.word_embeddings.weight"),
        "pos_emb": w.get("embeddings.position_embeddings.weight"),
        "type_emb": w.get("embeddings.token_type_embeddings.weight"),
        "emb_ln": ln("embeddings.LayerNorm"),
        "layers": layers,
    }


def encode(params, tokens, cfg: BertConfig, attention_mask=None,
           token_type_ids=None, mesh: Mesh | None = None):
    """tokens [B, T] → last hidden state [B, T, D]."""
    del mesh
    B, T = tokens.shape
    eps = cfg.layer_norm_eps
    if token_type_ids is None:
        token_type_ids = jnp.zeros_like(tokens)
    x = (params["word_emb"][tokens] + params["pos_emb"][jnp.arange(T)]
         + params["type_emb"][token_type_ids])
    x = layer_norm(x, params["emb_ln"]["w"], params["emb_ln"]["b"], eps)
    H = cfg.num_attention_heads
    hd = cfg.hidden_size // H
    if attention_mask is None:
        bias = jnp.zeros((B, 1, 1, T), jnp.float32)
    else:
        bias = jnp.where(attention_mask[:, None, None, :] > 0, 0.0, -1e30)
    for layer in params["layers"]:
        q = (x @ layer["q"]["w"] + layer["q"]["b"]).reshape(B, T, H, hd)
        k = (x @ layer["k"]["w"] + layer["k"]["b"]).reshape(B, T, H, hd)
        v = (x @ layer["v"]["w"] + layer["v"]["b"]).reshape(B, T, H, hd)
        if attention_mask is None and _use_flash():
            # bidirectional full-length attention maps to the fused
            # kernel directly; per-example masks keep the einsum path
            # (they need per-batch validity the kernel does not model)
            from demodel_tpu.ops.flash_attention import flash_attention

            a = flash_attention(q, k, v, causal=False).reshape(B, T, -1)
        else:
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
            scores = scores.astype(jnp.float32) + bias
            probs = jax.nn.softmax(scores, -1).astype(x.dtype)
            a = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, T, -1)
        a = a @ layer["attn_out"]["w"] + layer["attn_out"]["b"]
        x = layer_norm(x + a, layer["attn_ln"]["w"], layer["attn_ln"]["b"],
                       eps)
        h = jax.nn.gelu(x @ layer["inter"]["w"] + layer["inter"]["b"],
                        approximate=False)
        h = h @ layer["out"]["w"] + layer["out"]["b"]
        x = layer_norm(x + h, layer["out_ln"]["w"], layer["out_ln"]["b"], eps)
    return x


#: what a pulled BERT runs: the encoder
forward = encode
