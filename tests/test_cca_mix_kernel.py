"""The Pallas kernel that makes a decode step's compressed convolutional
mixing one device operation (``ops/cca_mix.py``), under Pallas' TPU
interpreter on the CPU at ZAYA1's published widths (8 query heads over 2 of
128, kernels of 2 and 2, and a longer pair): its page rows, padded queries
and tails against ``models/zaya._plain_rows``, the portable form it stands
in for in a program lowered for a TPU, and which stays its oracle.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from demodel_tpu.models import zaya
from demodel_tpu.ops import cca_mix


def _case(cfg, rows: int, seed: int):
    """A layer's weights with every vector stirred, a step's projection,
    tails and positions for ``rows`` rows."""
    one = dataclasses.replace(cfg, num_hidden_layers=1)
    params = zaya.init_params(jax.random.key(seed), one)
    keys = jax.random.split(jax.random.key(seed + 1), 4)
    named = zaya.unpack(params["layers"]["vectors"], one)
    named = {name: a + 0.3 * jax.random.normal(
        jax.random.fold_in(keys[0], i), a.shape)
        for i, (name, a) in enumerate(named.items())}
    # the factor under the query heads is one by construction
    named["temp"] = named["temp"].at[:, :cfg.q_dim].set(1.0)
    w = {"conv1_w": params["layers"]["conv1_w"][0],
         **{k: v[0] for k, v in named.items()}}
    dt = jnp.dtype(cfg.dtype)
    qkv = jax.random.normal(keys[1], (rows, cfg.mixed + cfg.kv_dim)).astype(dt)
    tail = jax.random.normal(keys[2], (rows, cfg.tail_dim)).astype(dt)
    positions = jax.random.randint(keys[3], (rows, 1), 0, 3000)
    return w, qkv, tail, zaya._turns(positions, cfg)


@pytest.mark.parametrize("rows", [64, 3])
@pytest.mark.parametrize("k0,k1", [(2, 2), (3, 1)])
def test_the_kernel_is_the_plain_form(rows, k0, k1):
    cfg = zaya.ZayaConfig(num_hidden_layers=1, cca_time0=k0, cca_time1=k1,
                          hidden_size=256, vocab_size=64, num_experts=2,
                          moe_intermediate_size=64, router_hidden_size=32,
                          dtype="bfloat16")
    w, qkv, tail, turns = _case(cfg, rows, seed=rows + k0)
    want = zaya._plain_rows(w, qkv, tail, turns, cfg)
    got = cca_mix.step_rows(
        qkv, tail, jnp.stack([*(w[f"conv0_w.{j}"] for j in range(k0)),
                              w["conv0_b"], w["conv1_b"], w["temp"]]),
        w["conv1_w"], turns, H=cfg.num_attention_heads,
        Hkv=cfg.num_key_value_heads, k0=k0, k1=k1, rotary=cfg.rotary,
        interpret=pltpu.InterpretParams())
    P = cfg.page_dim
    for name, mine, its in zip(("page", "queries", "tail"), got, want):
        assert mine.shape == its.shape and mine.dtype == its.dtype, name
        # normalised heads of order 1 in bfloat16: a rounding apart
        np.testing.assert_allclose(
            np.asarray(mine, np.float32), np.asarray(its, np.float32),
            rtol=2e-2, atol=2e-2, err_msg=name)
    page, wide, kept = (np.asarray(a, np.float32) for a in got)
    # the values and the rows handed on unmixed are copies, to the bit
    np.testing.assert_array_equal(page[:, :cfg.kv_dim],
                                  np.asarray(want[0], np.float32)[:, :cfg.kv_dim])
    np.testing.assert_array_equal(kept[:, -cfg.kv_dim // 2:],
                                  np.asarray(qkv, np.float32)[:, -cfg.kv_dim // 2:])
    # a query head is zero outside its own key head's columns
    wide = wide.reshape(rows, cfg.num_attention_heads, P)
    g = cfg.num_attention_heads // cfg.num_key_value_heads
    for i in range(cfg.num_attention_heads):
        own = slice(cfg.kv_dim + (i // g) * 128, cfg.kv_dim + (i // g + 1) * 128)
        mask = np.ones(P, bool)
        mask[own] = False
        assert not wide[:, i, mask].any()
        assert np.abs(wide[:, i, own]).max() > 0.5
