"""``models/common.attend``: the one attention over new keys and a paged
cache, against a dense float32 reference written here."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from demodel_tpu.models.common import attend

B, T, HKV, HD = 3, 5, 2, 8
BS, M = 4, 3            # block_tokens, blocks of past a row


def _dense(q, keys, values, qpos, kpos, seen, window):
    """Row by row, head by head, in float32: ``keys`` / ``values`` [B, S,
    Hkv, hd] at ``kpos`` [B, S], of which a query sees those ``seen`` [B,
    S] that lie 0..window-1 behind it."""
    Bq, Tq, H, hd = q.shape
    out = np.zeros((Bq, Tq, H, hd), np.float32)
    for b in range(Bq):
        for t in range(Tq):
            behind = qpos[b, t] - kpos[b]
            keep = seen[b] & (behind >= 0)
            if window:
                keep &= behind < window
            for h in range(H):
                kv = h // (H // keys.shape[2])
                s = keys[b, keep, kv] @ q[b, t, h] * hd ** -0.5
                w = np.exp(s - s.max())
                out[b, t, h] = (w / w.sum()) @ values[b, keep, kv]
    return out.reshape(Bq, Tq, H * hd)


@pytest.mark.parametrize("group", [1, 8], ids=["mha", "gqa8"])
@pytest.mark.parametrize("window", [0, 128, 3], ids=["full", "w128", "w3"])
@pytest.mark.parametrize("paged", [False, True], ids=["alone", "paged"])
def test_attend_matches_a_dense_reference(paged, window, group):
    """New keys alone (a prefill) and behind a paged past with a ragged
    ``live`` (a decode step and a chunk of several queries), with and
    without a window, with 1 and 8 query heads a KV head. Row 0 of the
    paged case has length 0: it sees only its own new keys, whatever its
    slots hold."""
    rng = np.random.default_rng(11)
    H = HKV * group
    q, k, v = (rng.normal(size=(B, T, h, HD)).astype(np.float32)
               for h in (H, HKV, HKV))
    lengths = np.asarray([0, 5, 11]) if paged else np.zeros(B, int)
    positions = lengths[:, None] + np.arange(T)[None, :]
    past = None
    keys, values, kpos = k, v, positions
    seen = np.ones((B, T), bool)
    if paged:
        pk, pv = (rng.normal(size=(B, M, HKV, BS, HD)).astype(np.float32)
                  for _ in range(2))
        slots = np.broadcast_to(np.arange(M * BS), (B, M * BS))
        live = slots < lengths[:, None]
        past = (jnp.asarray(pk), jnp.asarray(pv), jnp.asarray(slots),
                jnp.asarray(live))

        def flat(a):    # [B, M, Hkv, BS, hd] -> [B, M * BS, Hkv, hd]
            return a.transpose(0, 1, 3, 2, 4).reshape(B, M * BS, HKV, HD)

        keys = np.concatenate([flat(pk), k], axis=1)
        values = np.concatenate([flat(pv), v], axis=1)
        kpos = np.concatenate([slots, positions], axis=1)
        seen = np.concatenate([live, seen], axis=1)
    got = attend(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                 jnp.asarray(positions), window=window, past=past)
    assert got.shape == (B, T, H * HD) and got.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got), _dense(q, keys, values, positions, kpos, seen,
                                window), rtol=2e-5, atol=2e-5)


def test_probabilities_are_in_the_models_dtype():
    """float32 softmax, then the model's dtype for the value products: a
    bfloat16 call returns bfloat16 and stays near the float32 one."""
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 1, h, HD)), jnp.bfloat16)
               for h in (4, 2, 2))
    pos = jnp.asarray([[4], [2]])
    got = attend(q, k, v, pos)
    want = attend(*(a.astype(jnp.float32) for a in (q, k, v)), pos)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=3e-2)
