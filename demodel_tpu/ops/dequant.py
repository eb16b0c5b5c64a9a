"""On-device GGUF dequantization.

The HBM sink ships the *quantized* payload over the host→device link and
widens on device (SURVEY.md §2.3 "Sharded HBM placement"): for Q8_0 that is
a 3.8× link saving over shipping f32. Every format runs as fused jnp math
— the transform is bandwidth-bound elementwise work, which is XLA's job:

- **Q8_0 / Q4_0**: the Pallas kernels these once had compiled under
  Mosaic with bit-identical output and lost to this math on the v5e at
  every size tried (PR 21: a 4096×4096 tensor under jit, 1.86 ms against
  0.53 ms for Q8_0 and 1.89 against 1.05 for Q4_0; called eagerly per
  tensor, as the sink does, 120–165 ms a call whatever the size).
  A kernel that comes back has those numbers to beat.
- **K-quants (Q2_K…Q6_K)**: the bit-unpacking layouts (12/16-byte
  operands, rank-1 scale vectors) are lane-hostile and their
  one-super-block kernels do not satisfy Mosaic's tiling rules on real
  TPU. The kernels remain as an interpret-only parity oracle under
  DEMODEL_FORCE_PALLAS.

Bit layouts follow the llama.cpp/ggml block spec; the numpy decoders in
:mod:`demodel_tpu.formats.gguf` (``REF_DEQUANT``) are the normative
reference this math is tested against (tests/test_dequant.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from demodel_tpu.formats import gguf


def _force_pallas() -> bool:
    """DEMODEL_FORCE_PALLAS=1 runs the K-quant math through its
    interpret-mode pallas oracle (the kernel parity tests set it; the
    interpreter executes the grid in Python)."""
    import os

    return os.environ.get("DEMODEL_FORCE_PALLAS", "").strip() == "1"


# --------------------------------------------------------------- Q8_0/Q4_0


def _q8_0_math(d, qs, out_dtype):
    return (d.astype(jnp.float32)[:, None]
            * qs.astype(jnp.float32)).astype(out_dtype)


def dequant_q8_0(d, qs, out_dtype=jnp.bfloat16):
    """d: (nb,) f16, qs: (nb, 32) i8 → flat (nb*32,) out_dtype."""
    return _q8_0_math(jnp.asarray(d), jnp.asarray(qs), out_dtype).reshape(-1)


def _q4_0_math(d, qs, out_dtype):
    qs = qs.astype(jnp.int32)
    lo = (qs & 0xF) - 8
    hi = (qs >> 4) - 8
    q = jnp.concatenate([lo, hi], axis=-1).astype(jnp.float32)
    return (d.astype(jnp.float32)[:, None] * q).astype(out_dtype)


def dequant_q4_0(d, qs, out_dtype=jnp.bfloat16):
    """d: (nb,) f16, qs: (nb, 16) u8 → flat (nb*32,) out_dtype."""
    return _q4_0_math(jnp.asarray(d), jnp.asarray(qs), out_dtype).reshape(-1)


# ----------------------------------------------------------------- K-quants
#
# One pallas kernel per format, gridded one super-block (256 elems) per
# step; the shared jnp math mirrors formats.gguf's numpy reference loops
# vectorized over the block axis.


def _q2_k_math(d, dmin, scales, qs, out_dtype):
    nb = d.shape[0]
    df = d.astype(jnp.float32)
    mf = dmin.astype(jnp.float32)
    scales = scales.astype(jnp.int32)
    qs = qs.astype(jnp.int32)
    cols = []
    for half in range(2):
        q = qs[:, half * 32:(half + 1) * 32]
        for j in range(4):
            grp = (q >> (2 * j)) & 3
            for sub in range(2):
                is_ = half * 8 + 2 * j + sub
                sc = scales[:, is_]
                dl = df * (sc & 0xF).astype(jnp.float32)
                ml = mf * (sc >> 4).astype(jnp.float32)
                seg = grp[:, sub * 16:(sub + 1) * 16].astype(jnp.float32)
                cols.append(dl[:, None] * seg - ml[:, None])
    # cols are in y-order by construction: (half, j, sub)
    return jnp.concatenate(cols, axis=1).reshape(nb, 256).astype(out_dtype)


def _q3_k_scales(scales):
    """jnp port of formats.gguf.unpack_q3k_scales (12B → 16 6-bit - 32)."""
    s = scales.astype(jnp.uint32)

    def dword(i):
        return (s[:, 4 * i] | (s[:, 4 * i + 1] << 8) | (s[:, 4 * i + 2] << 16)
                | (s[:, 4 * i + 3] << 24))

    raw0, raw1, tmp = dword(0), dword(1), dword(2)
    kmask1, kmask2 = 0x03030303, 0x0F0F0F0F
    aux0 = (raw0 & kmask2) | (((tmp >> 0) & kmask1) << 4)
    aux1 = (raw1 & kmask2) | (((tmp >> 2) & kmask1) << 4)
    aux2 = ((raw0 >> 4) & kmask2) | (((tmp >> 4) & kmask1) << 4)
    aux3 = ((raw1 >> 4) & kmask2) | (((tmp >> 6) & kmask1) << 4)
    bytes_ = []
    for aux in (aux0, aux1, aux2, aux3):
        for shift in (0, 8, 16, 24):
            bytes_.append((aux >> shift) & 0xFF)
    sc = jnp.stack(bytes_, axis=1).astype(jnp.int32)
    sc = jnp.where(sc >= 128, sc - 256, sc)  # int8 reinterpret
    return sc - 32


def _q3_k_math(d, scales, hmask, qs, out_dtype):
    nb = d.shape[0]
    df = d.astype(jnp.float32)
    sc = _q3_k_scales(scales)
    hmask = hmask.astype(jnp.int32)
    qs = qs.astype(jnp.int32)
    cols = []
    for half in range(2):
        q = qs[:, half * 32:(half + 1) * 32]
        for j in range(4):
            grp_i = half * 4 + j
            low = (q >> (2 * j)) & 3
            hbit = (hmask >> grp_i) & 1
            qv = low - jnp.where(hbit != 0, 0, 4)
            for sub in range(2):
                is_ = half * 8 + 2 * j + sub
                dl = df * sc[:, is_].astype(jnp.float32)
                seg = qv[:, sub * 16:(sub + 1) * 16].astype(jnp.float32)
                cols.append(dl[:, None] * seg)
    return jnp.concatenate(cols, axis=1).reshape(nb, 256).astype(out_dtype)


def _k4_scales(scales):
    """jnp port of unpack_k4_scales: (nb,12) u8 → (sc, m) each (nb,8)."""
    q = scales.astype(jnp.int32)
    sc, m = [], []
    for j in range(8):
        if j < 4:
            sc.append(q[:, j] & 63)
            m.append(q[:, j + 4] & 63)
        else:
            sc.append((q[:, j + 4] & 0xF) | (((q[:, j - 4] >> 6) & 3) << 4))
            m.append((q[:, j + 4] >> 4) | (((q[:, j] >> 6) & 3) << 4))
    return jnp.stack(sc, axis=1), jnp.stack(m, axis=1)


def _q4_k_math(d, dmin, scales, qs, out_dtype):
    nb = d.shape[0]
    df = d.astype(jnp.float32)
    mf = dmin.astype(jnp.float32)
    sc, mn = _k4_scales(scales)
    qs = qs.astype(jnp.int32)
    cols = []
    for j in range(4):
        q = qs[:, 32 * j:32 * (j + 1)]
        d1 = df * sc[:, 2 * j].astype(jnp.float32)
        m1 = mf * mn[:, 2 * j].astype(jnp.float32)
        d2 = df * sc[:, 2 * j + 1].astype(jnp.float32)
        m2 = mf * mn[:, 2 * j + 1].astype(jnp.float32)
        cols.append(d1[:, None] * (q & 0xF).astype(jnp.float32) - m1[:, None])
        cols.append(d2[:, None] * (q >> 4).astype(jnp.float32) - m2[:, None])
    return jnp.concatenate(cols, axis=1).reshape(nb, 256).astype(out_dtype)


def _q5_k_math(d, dmin, scales, qh, qs, out_dtype):
    nb = d.shape[0]
    df = d.astype(jnp.float32)
    mf = dmin.astype(jnp.float32)
    sc, mn = _k4_scales(scales)
    qh = qh.astype(jnp.int32)
    qs = qs.astype(jnp.int32)
    cols = []
    for j in range(4):
        q = qs[:, 32 * j:32 * (j + 1)]
        h1 = (qh >> (2 * j)) & 1
        h2 = (qh >> (2 * j + 1)) & 1
        q1 = (q & 0xF) + (h1 << 4)
        q2 = (q >> 4) + (h2 << 4)
        d1 = df * sc[:, 2 * j].astype(jnp.float32)
        m1 = mf * mn[:, 2 * j].astype(jnp.float32)
        d2 = df * sc[:, 2 * j + 1].astype(jnp.float32)
        m2 = mf * mn[:, 2 * j + 1].astype(jnp.float32)
        cols.append(d1[:, None] * q1.astype(jnp.float32) - m1[:, None])
        cols.append(d2[:, None] * q2.astype(jnp.float32) - m2[:, None])
    return jnp.concatenate(cols, axis=1).reshape(nb, 256).astype(out_dtype)


def _q6_k_math(d, sc, ql, qh, out_dtype):
    nb = d.shape[0]
    df = d.astype(jnp.float32)
    scf = sc.astype(jnp.float32)
    ql = ql.astype(jnp.int32)
    qh = qh.astype(jnp.int32)
    cols = []
    for half in range(2):
        l1 = ql[:, half * 64:half * 64 + 32]
        l2 = ql[:, half * 64 + 32:half * 64 + 64]
        h = qh[:, half * 32:half * 32 + 32]
        q1 = ((l1 & 0xF) | (((h >> 0) & 3) << 4)) - 32
        q2 = ((l2 & 0xF) | (((h >> 2) & 3) << 4)) - 32
        q3 = ((l1 >> 4) | (((h >> 4) & 3) << 4)) - 32
        q4 = ((l2 >> 4) | (((h >> 6) & 3) << 4)) - 32
        for qv, col in ((q1, 0), (q2, 32), (q3, 64), (q4, 96)):
            for subi in range(2):
                is_ = half * 8 + col // 16 + subi
                dl = df * scf[:, is_]
                seg = qv[:, subi * 16:(subi + 1) * 16].astype(jnp.float32)
                cols.append(dl[:, None] * seg)
    return jnp.concatenate(cols, axis=1).reshape(nb, 256).astype(out_dtype)


def _k_quant_call(math_fn, parts, out_dtype, part_widths):
    """Run a K-quant math fn as a pallas kernel, one super-block per grid
    step, or as plain fused jnp.

    On REAL TPU the math path is used: K-quant bit-unpacking is
    lane-hostile (1-wide sublane blocks, 12/16-byte operands, rank-1
    scale vectors) and the one-super-block-per-step kernel layout does
    not satisfy Mosaic's tiling rules — the fused XLA elementwise graph
    is the right tool for this bandwidth-bound transform. The kernels
    remain exercised under DEMODEL_FORCE_PALLAS (interpret-mode kernel
    tests), keeping the math/kernel parity oracle alive."""
    nb = parts[0].shape[0]
    if nb == 0:
        return jnp.zeros((0,), out_dtype)
    if not _force_pallas():
        return math_fn(*parts, out_dtype).reshape(-1)

    def kernel(*refs):
        ins, o_ref = refs[:-1], refs[-1]
        o_ref[...] = math_fn(*[r[...] for r in ins], out_dtype)

    in_specs = []
    for p, w in zip(parts, part_widths):
        if w is None:
            in_specs.append(pl.BlockSpec((1,), lambda i: (i,)))
        else:
            in_specs.append(pl.BlockSpec((1, w), lambda i: (i, 0)))
    out = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, gguf.QK_K), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, gguf.QK_K), out_dtype),
        # ALWAYS interpreted: this one-super-block layout is exactly
        # what Mosaic rejects on real TPU (round-5 on-chip compile), so
        # a forced run on a TPU host must not hand it to the compiler
        interpret=True,
    )(*parts)
    return out.reshape(-1)


def dequant_q2_k(d, dmin, scales, qs, out_dtype=jnp.bfloat16):
    return _k_quant_call(_q2_k_math, (d, dmin, scales, qs), out_dtype,
                         (None, None, 16, 64))


def dequant_q3_k(d, scales, hmask, qs, out_dtype=jnp.bfloat16):
    return _k_quant_call(_q3_k_math, (d, scales, hmask, qs), out_dtype,
                         (None, 12, 32, 64))


def dequant_q4_k(d, dmin, scales, qs, out_dtype=jnp.bfloat16):
    return _k_quant_call(_q4_k_math, (d, dmin, scales, qs), out_dtype,
                         (None, None, 12, 128))


def dequant_q5_k(d, dmin, scales, qh, qs, out_dtype=jnp.bfloat16):
    return _k_quant_call(_q5_k_math, (d, dmin, scales, qh, qs), out_dtype,
                         (None, None, 12, 32, 128))


def dequant_q6_k(d, sc, ql, qh, out_dtype=jnp.bfloat16):
    return _k_quant_call(_q6_k_math, (d, sc, ql, qh), out_dtype,
                         (None, 16, 128, 64))


# ------------------------------------------------------------- whole tensor

_FNS = {
    gguf.GGML_Q8_0: dequant_q8_0,
    gguf.GGML_Q4_0: dequant_q4_0,
    gguf.GGML_Q2_K: dequant_q2_k,
    gguf.GGML_Q3_K: dequant_q3_k,
    gguf.GGML_Q4_K: dequant_q4_k,
    gguf.GGML_Q5_K: dequant_q5_k,
    gguf.GGML_Q6_K: dequant_q6_k,
}


def dequant_gguf_tensor(t: gguf.GGUFTensor, decoded,
                        out_dtype=jnp.bfloat16) -> jax.Array:
    """Whole-tensor dequant (the sink's non-shardwise fallback path)."""
    if t.ggml_type in (gguf.GGML_F32, gguf.GGML_F16):
        return jnp.asarray(np.asarray(decoded)).astype(out_dtype)
    fn = _FNS[t.ggml_type]
    flat = fn(*[jnp.asarray(p) for p in decoded], out_dtype)
    return flat.reshape(t.shape)
