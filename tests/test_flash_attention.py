"""Flash attention kernel parity vs the einsum reference (interpret mode
on CPU; the same pallas program compiles for the TPU MXU)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from demodel_tpu.ops.flash_attention import flash_attention, reference_attention


def _mk(B, Sq, Sk, H, G, D, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Sk, G, D), dtype)
    v = jax.random.normal(ks[2], (B, Sk, G, D), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_reference(causal):
    q, k, v = _mk(2, 64, 64, 4, 4, 32)
    got = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    want = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_gqa_heads():
    """8 query heads over 2 kv heads — the index-map fold, no repeat."""
    q, k, v = _mk(1, 32, 32, 8, 2, 16, seed=3)
    got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    want = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_ragged_lengths_padded_and_masked():
    """Sq/Sk not multiples of the blocks: zero-padding must not leak into
    the softmax (key-validity mask) and the output slices back exactly."""
    q, k, v = _mk(2, 48, 80, 4, 4, 32, seed=5)
    got = flash_attention(q, k, v, causal=False, block_q=32, block_k=32)
    want = reference_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_decode_window_alignment():
    """Sq < Sk (decode with KV cache): the causal diagonal aligns the
    last query to the last key."""
    q, k, v = _mk(1, 8, 72, 4, 4, 32, seed=7)
    got = flash_attention(q, k, v, causal=True, block_q=8, block_k=24)
    want = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_bf16_io_fp32_accum():
    q, k, v = _mk(1, 64, 64, 2, 2, 64, dtype=jnp.bfloat16, seed=9)
    got = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    assert got.dtype == jnp.bfloat16
    want = reference_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                               v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(want), rtol=2e-2, atol=2e-2)


def test_flash_dynamic_kv_len():
    """A traced kv_len (decode over a mostly-empty cache) masks the
    unfilled tail and aligns the causal window to the filled prefix."""
    q, k, v = _mk(1, 4, 96, 4, 4, 32, seed=13)
    filled = 40  # cache capacity 96, only 40 slots valid
    got = jax.jit(lambda q_, k_, v_, n: flash_attention(
        q_, k_, v_, kv_len=n, causal=True, block_q=4, block_k=16))(
            q, k, v, jnp.int32(filled))
    want = reference_attention(q, k[:, :filled], v[:, :filled], causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_per_batch_kv_len():
    """Ragged batched decode: each example carries its own filled-cache
    length; rows match per-example reference attention."""
    q, k, v = _mk(3, 4, 64, 4, 2, 32, seed=15)
    lens = jnp.asarray([17, 64, 40], jnp.int32)
    got = flash_attention(q, k, v, kv_len=lens, causal=True,
                          block_q=4, block_k=16)
    for b, n in enumerate([17, 64, 40]):
        want = reference_attention(q[b:b + 1], k[b:b + 1, :n],
                                   v[b:b + 1, :n], causal=True)
        np.testing.assert_allclose(np.asarray(got[b:b + 1]),
                                   np.asarray(want), rtol=2e-5, atol=2e-5)


def test_flash_fully_masked_rows_are_zero():
    """A query row with ZERO visible keys inside a live K block (negative
    causal_offset pushes early queries before every key) must emit zeros,
    not mean(V): with every score at NEG_INF the online-softmax m_new
    stays NEG_INF and exp(s - m_new) == 1 unless masked probabilities are
    zeroed explicitly (advisor r4)."""
    q, k, v = _mk(1, 16, 16, 2, 2, 32, seed=21)
    # offset -8: queries 0..7 see no keys at all; query i>=8 sees i-8+1
    got = np.asarray(flash_attention(q, k, v, causal=True,
                                     causal_offset=jnp.int32(-8),
                                     block_q=8, block_k=8))
    assert np.all(got[:, :8] == 0.0), "fully-masked rows must be zeros"
    # visible rows still match the reference restricted to their window
    want = np.asarray(reference_attention(q, k, v, causal=True,
                                          causal_offset=jnp.int32(-8)))
    np.testing.assert_allclose(got[:, 8:], want[:, 8:],
                               rtol=2e-5, atol=2e-5)


def test_flash_q_longer_than_kv_tail_rows_zero():
    """Sq > kv_len with default alignment: queries beyond the filled
    prefix end up below the diagonal with no visible key — zeros, and
    finite values for the valid prefix."""
    q, k, v = _mk(1, 12, 16, 2, 2, 32, seed=23)
    # kv_len=4, default causal_offset = kv_len - Sq = -8: queries 8..11
    # see keys 0..3; queries 0..7 see none
    got = np.asarray(flash_attention(q, k, v, kv_len=jnp.int32(4),
                                     causal=True, block_q=4, block_k=8))
    assert np.all(got[:, :8] == 0.0)
    assert np.all(np.isfinite(got))
    want = np.asarray(reference_attention(q, k, v, kv_len=jnp.int32(4),
                                          causal=True))
    np.testing.assert_allclose(got[:, 8:], want[:, 8:],
                               rtol=2e-5, atol=2e-5)


def test_llama_decode_cache_parity_with_flash(monkeypatch):
    """DEMODEL_FLASH_ATTN=1 on the cached decode path: same logits as
    the einsum cache attention, step by step."""
    from demodel_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.key(4), cfg)
    prompt = jnp.asarray(
        np.arange(1 * 12, dtype=np.int32).reshape(1, 12) % cfg.vocab_size)

    def decode(n_steps):
        cache = llama.init_cache(cfg, batch=1, max_len=32)
        logits, cache = llama.forward_with_cache(params, prompt, cfg,
                                                 cache, 0)
        outs = [logits[:, -1:]]
        pos = prompt.shape[1]
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        for _ in range(n_steps):
            logits, cache = llama.forward_with_cache(params, tok, cfg,
                                                     cache, pos)
            outs.append(logits[:, -1:])
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            pos += 1
        return jnp.concatenate(outs, axis=1)

    base = decode(3)
    monkeypatch.setenv("DEMODEL_FLASH_ATTN", "1")
    flash = decode(3)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(base),
                               rtol=2e-4, atol=2e-4)


def test_llama_forward_parity_with_flash(monkeypatch):
    """DEMODEL_FLASH_ATTN=1 must not change llama's forward numerics."""
    from demodel_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.key(0), cfg)
    tokens = jnp.asarray(
        np.arange(2 * 24, dtype=np.int32).reshape(2, 24) % cfg.vocab_size)
    base = llama.forward(params, tokens, cfg)
    monkeypatch.setenv("DEMODEL_FLASH_ATTN", "1")
    flash = llama.forward(params, tokens, cfg)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(base),
                               rtol=2e-5, atol=2e-5)


def test_gpt2_bert_forward_parity_with_flash(monkeypatch):
    """The same flag routes GPT-2 (causal) and BERT (bidirectional,
    unmasked) attention through the kernel without numeric drift."""
    from demodel_tpu.models import bert, gpt2

    gcfg = gpt2.GPT2Config.tiny()
    gparams = gpt2.init_params(jax.random.key(1), gcfg)
    gtok = jnp.asarray(
        np.arange(2 * 20, dtype=np.int32).reshape(2, 20) % gcfg.vocab_size)
    bcfg = bert.BertConfig.tiny()
    bparams = bert.init_params(jax.random.key(2), bcfg)
    btok = jnp.asarray(
        np.arange(2 * 16, dtype=np.int32).reshape(2, 16) % bcfg.vocab_size)

    gbase = gpt2.forward(gparams, gtok, gcfg)
    bbase = bert.encode(bparams, btok, bcfg)
    monkeypatch.setenv("DEMODEL_FLASH_ATTN", "1")
    gflash = gpt2.forward(gparams, gtok, gcfg)
    bflash = bert.encode(bparams, btok, bcfg)
    np.testing.assert_allclose(np.asarray(gflash), np.asarray(gbase),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(bflash), np.asarray(bbase),
                               rtol=2e-5, atol=2e-5)


def test_flash_default_policy(monkeypatch):
    """Flash is OFF unless the env turns it on — for the model attention
    and for the ring separately. Nothing else decides: no backend probe,
    no record file."""
    from demodel_tpu.models.common import use_flash_attention
    from demodel_tpu.ops.ring_attention import _use_flash_ring

    monkeypatch.delenv("DEMODEL_FLASH_ATTN", raising=False)
    monkeypatch.delenv("DEMODEL_FLASH_RING", raising=False)
    assert use_flash_attention() is False
    assert _use_flash_ring() is False
    monkeypatch.setenv("DEMODEL_FLASH_ATTN", "1")
    assert use_flash_attention() is True
    assert _use_flash_ring() is False  # separate switches
    monkeypatch.setenv("DEMODEL_FLASH_RING", "1")
    assert _use_flash_ring() is True
    monkeypatch.setenv("DEMODEL_FLASH_ATTN", "0")
    assert use_flash_attention() is False


def test_flash_grad_matches_reference():
    """custom_vjp recompute backward: grads equal the reference's."""
    q, k, v = _mk(1, 32, 32, 2, 2, 16, seed=11)

    def loss_flash(q_, k_, v_):
        return (flash_attention(q_, k_, v_, causal=True, block_q=16, block_k=16) ** 2).sum()

    def loss_ref(q_, k_, v_):
        return (reference_attention(q_, k_, v_, causal=True) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
