"""On-device dequant vs the normative numpy decoders: the jnp math every
format runs as, and the K-quants' interpret-mode pallas oracle of it.

Random packed bytes (every bit pattern is a valid block) exercise the full
bit-layout space; end-to-end cases additionally run encode → GGUF container
→ decode_raw → dequant and compare against the reference decode of the same
bytes."""

import numpy as np
import pytest

import jax.numpy as jnp

from demodel_tpu.formats import gguf
from demodel_tpu.ops import dequant as dq

@pytest.fixture(autouse=True)
def _force_pallas(monkeypatch):
    """Run the K-quant math through its pallas oracle (interpret mode);
    Q8_0/Q4_0 have no kernel and run the math itself."""
    monkeypatch.setenv("DEMODEL_FORCE_PALLAS", "1")


_FNS = {
    gguf.GGML_Q8_0: dq.dequant_q8_0,
    gguf.GGML_Q4_0: dq.dequant_q4_0,
    gguf.GGML_Q2_K: dq.dequant_q2_k,
    gguf.GGML_Q3_K: dq.dequant_q3_k,
    gguf.GGML_Q4_K: dq.dequant_q4_k,
    gguf.GGML_Q5_K: dq.dequant_q5_k,
    gguf.GGML_Q6_K: dq.dequant_q6_k,
}

_BLOCK_BYTES = {
    gguf.GGML_Q8_0: gguf.Q8_0_BLOCK_BYTES,
    gguf.GGML_Q4_0: gguf.Q4_0_BLOCK_BYTES,
    **gguf.K_BLOCK_BYTES,
}


def _random_blocks(ggml_type: int, nblocks: int, seed: int = 0) -> bytes:
    """Random packed blocks with a sane f16 scale field (random exponents
    would overflow f32 accumulation and mask real layout bugs)."""
    rng = np.random.default_rng(seed)
    bpb = _BLOCK_BYTES[ggml_type]
    raw = rng.integers(0, 256, (nblocks, bpb), dtype=np.uint8)
    blk = gguf.QK if ggml_type in (gguf.GGML_Q8_0, gguf.GGML_Q4_0) else gguf.QK_K
    x = rng.standard_normal(nblocks * blk).astype(np.float32)
    enc = np.frombuffer(gguf.encode(x, ggml_type), np.uint8).reshape(nblocks,
                                                                     bpb)
    # keep encoded scale fields, randomize the quant payloads
    out = enc.copy()
    if ggml_type == gguf.GGML_Q8_0:
        out[:, 2:] = raw[:, 2:]
    elif ggml_type == gguf.GGML_Q4_0:
        out[:, 2:] = raw[:, 2:]
    elif ggml_type == gguf.GGML_Q2_K:
        out[:, 0:80] = raw[:, 0:80]
    elif ggml_type == gguf.GGML_Q3_K:
        out[:, 0:108] = raw[:, 0:108]
    elif ggml_type in (gguf.GGML_Q4_K, gguf.GGML_Q5_K):
        out[:, 4:] = raw[:, 4:]
    elif ggml_type == gguf.GGML_Q6_K:
        out[:, 0:208] = raw[:, 0:208]
    return out.tobytes()


def _compare(ggml_type: int, nblocks: int):
    blk = gguf.QK if ggml_type in (gguf.GGML_Q8_0, gguf.GGML_Q4_0) else gguf.QK_K
    raw = _random_blocks(ggml_type, nblocks, seed=nblocks)
    t = gguf.GGUFTensor("t", ggml_type, (nblocks * blk,), 0, len(raw))
    parts = gguf.decode_raw(t, raw)
    ref = gguf.REF_DEQUANT[ggml_type](*parts)
    got = np.asarray(_FNS[ggml_type](*[jnp.asarray(p) for p in parts],
                                     jnp.float32))
    np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("nblocks", [8, 64, 2048])
def test_q8_0_matches_reference(nblocks):
    _compare(gguf.GGML_Q8_0, nblocks)


@pytest.mark.parametrize("nblocks", [8, 64, 2048])
def test_q4_0_matches_reference(nblocks):
    _compare(gguf.GGML_Q4_0, nblocks)


@pytest.mark.parametrize("nblocks", [1, 7, 300])
def test_q2_k_pallas_matches_reference(nblocks):
    _compare(gguf.GGML_Q2_K, nblocks)


@pytest.mark.parametrize("nblocks", [1, 7, 300])
def test_q3_k_pallas_matches_reference(nblocks):
    _compare(gguf.GGML_Q3_K, nblocks)


@pytest.mark.parametrize("nblocks", [1, 7, 300])
def test_q4_k_pallas_matches_reference(nblocks):
    _compare(gguf.GGML_Q4_K, nblocks)


@pytest.mark.parametrize("nblocks", [1, 7, 300])
def test_q5_k_pallas_matches_reference(nblocks):
    _compare(gguf.GGML_Q5_K, nblocks)


@pytest.mark.parametrize("nblocks", [1, 7, 300])
def test_q6_k_pallas_matches_reference(nblocks):
    _compare(gguf.GGML_Q6_K, nblocks)


def test_odd_block_counts():
    """Block counts of no convenient multiple decode the same."""
    for nb in (1, 3, 9):
        _compare(gguf.GGML_Q8_0, nb)
        _compare(gguf.GGML_Q4_0, nb)


def _e2e(ggml_type: int, shape=(8, 256)):
    rng = np.random.default_rng(10 + ggml_type)
    x = rng.standard_normal(shape).astype(np.float32)
    blob = gguf.serialize({"w": x}, {"w": ggml_type})
    idx = gguf.parse(blob)
    t = idx.tensors["w"]
    raw = blob[t.start:t.start + t.nbytes]
    arr = np.asarray(dq.dequant_gguf_tensor(t, gguf.decode_raw(t, raw),
                                            jnp.float32))
    ref = gguf.REF_DEQUANT[ggml_type](*gguf.decode_raw(t, raw)).reshape(shape)
    np.testing.assert_allclose(arr, ref, atol=1e-4)
    # and the decode approximates the source within quantization error
    assert np.abs(arr - x).max() / np.abs(x).max() < 0.3


def test_dequant_gguf_tensor_end_to_end():
    _e2e(gguf.GGML_Q8_0)
    _e2e(gguf.GGML_Q4_0)


@pytest.mark.parametrize("ggml_type", [gguf.GGML_Q4_K, gguf.GGML_Q6_K])
def test_k_quant_gguf_tensor_end_to_end(ggml_type):
    _e2e(ggml_type)


@pytest.mark.parametrize("ggml_type",
                         [gguf.GGML_Q2_K, gguf.GGML_Q3_K, gguf.GGML_Q5_K])
def test_new_k_quants_gguf_tensor_end_to_end(ggml_type):
    _e2e(ggml_type)
