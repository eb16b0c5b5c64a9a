"""A decode step's compressed convolutional mixing as one device operation:
the Pallas TPU kernel :func:`demodel_tpu.models.zaya._step_rows` runs in
the place of its plain form in a program lowered for a TPU.

Between a layer's one projection ``h @ [W_q | W_k | W_v1 | W_v2]`` and its
attention lie the two convolutions' new position, the mean, ten heads'
norms, temperatures and rotary, the page row ``[v | k^]``, the queries
padded to the page's width and the tail the next step reads: some forty
small fusions, slices and copies in the compiler's hands, a third of what a
layer of the step costs in device operations, though they move a few
hundred kilobytes. A traced window keeps only so many operations (PERF.md,
section 5), so the step is written for few: here everything of a row lies
in fast memory once and is written out once.

- no grid: a step's rows (the batch bucket's, padded to the sublanes'
  sixteen) are one block; every slice is of whole lanes (a head is ``hd``
  columns, a multiple of 128: the caller asks for the kernel only then);
- the arithmetic is the plain form's: the first convolution in float32,
  rounded as the tail keeps it; the second a product of those rounded
  values and the blocks in their own dtype with float32 accumulation (what
  the plain form's float32 product of the same values gives); mean, norm,
  temperature and rotary in float32, rounded once. Rotary is ``x * c +
  roll(x, r/2) * s1 + roll(x, -r/2) * s2`` under three tables a position
  (:func:`demodel_tpu.models.zaya._turns`), which turns the first ``r``
  columns of a head, rotate-half, without a slice narrower than a lane
  tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows a block is padded to: a bfloat16 tile's sublanes
ROWS = 16


def _kernel(qkv_ref, tail_ref, vec_ref, w1_ref, turns_ref,
            page_ref, wide_ref, kept_ref, *, H: int, Hkv: int, hd: int,
            k0: int, k1: int, rotary: int):
    f32 = jnp.float32
    C, g, kv = (H + Hkv) * hd, H // Hkv, Hkv * hd
    half, P = kv // 2, 2 * kv
    dt = page_ref.dtype
    c, s1, s2 = turns_ref[0], turns_ref[1], turns_ref[2]

    def head(ref, i, at=0):
        return ref[:, at + i * hd:at + (i + 1) * hd]

    def vec(row, i):        # a vector's columns under head i, [1, hd]
        return vec_ref[row, i]

    def mixed(i):
        """Head ``i``: ``(its unmixed columns, the second convolution's,
        both float32)``; its rows of the next tail are written."""
        ups = [head(tail_ref, i, j * C) for j in range(k0 - 1)] \
            + [head(qkv_ref, i)]
        # the first convolution's new position, rounded as the tail keeps it
        c0 = vec(k0, i) + sum(vec(j, i) * ups[j].astype(f32)
                              for j in range(k0))
        c0s = [head(tail_ref, i, (k0 - 1 + j) * C) for j in range(k1 - 1)] \
            + [c0.astype(dt)]
        for j, row in enumerate(ups[1:] + c0s[1:]):
            kept_ref[:, j * C + i * hd:j * C + (i + 1) * hd] = row
        return ups[-1].astype(f32), vec(k0 + 1, i) + sum(
            jnp.dot(c0s[j], w1_ref[j, i], preferred_element_type=f32)
            for j in range(k1))

    def finish(x, i):
        """Head ``i``'s mean-added columns to what attention reads."""
        x = x * lax.rsqrt(jnp.maximum(
            jnp.sum(x * x, axis=-1, keepdims=True) / hd, 1e-30))
        x = x * vec(k0 + 2, i)
        return (x * c + pltpu.roll(x, shift=rotary // 2, axis=1) * s1
                + pltpu.roll(x, shift=hd - rotary // 2, axis=1) * s2
                ).astype(dt)

    wide_ref[...] = jnp.zeros_like(wide_ref)
    page_ref[:, :half] = qkv_ref[:, C:C + half]
    page_ref[:, half:kv] = tail_ref[:, (k0 + k1 - 2) * C:]
    kept_ref[:, (k0 + k1 - 2) * C:] = qkv_ref[:, C + half:]
    for j in range(Hkv):
        u_k, c1_k = mixed(H + j)
        m_k = jnp.zeros_like(u_k)
        for i in range(j * g, (j + 1) * g):
            u_q, c1_q = mixed(i)
            m_q = 0.5 * (u_q + u_k)
            m_k = m_k + m_q
            wide_ref[:, i * P + kv + j * hd:i * P + kv + (j + 1) * hd] = \
                finish(c1_q + m_q, i)
        page_ref[:, kv + j * hd:kv + (j + 1) * hd] = finish(
            c1_k + m_k / g, H + j)


@functools.partial(jax.jit, static_argnames=("H", "Hkv", "k0", "k1", "rotary",
                                             "interpret"))
def step_rows(qkv, tail, vecs, conv1_w, turns, *, H: int, Hkv: int, k0: int,
              k1: int, rotary: int, interpret=False):
    """One position a row: ``qkv`` [N, C + kv] (the projection's ``[q~ | k~
    | v_now | v_next]``), ``tail`` [N, (k0 + k1 - 2) C + kv / 2] the rows'
    tails, ``vecs`` [k0 + 3, C] float32 (the first convolution's taps, its
    bias, the second's bias, a factor a column: the temperatures under the
    key heads; handed to the kernel a head a tile, so that a head's columns
    of a vector are a tile of their own), ``conv1_w`` [k1, heads, hd, hd],
    ``turns`` [3, N, hd] →
    ``(page rows [N, 2 kv], padded queries [N, H * 2 kv], tails [N, as
    they came])`` in ``qkv``'s dtype."""
    N = qkv.shape[0]
    hd = conv1_w.shape[-1]
    kv = Hkv * hd
    pad = -N % ROWS
    if pad:
        qkv, tail = (jnp.pad(a, ((0, pad), (0, 0))) for a in (qkv, tail))
        turns = jnp.pad(turns, ((0, 0), (0, pad), (0, 0)))
    rows = N + pad
    out = pl.pallas_call(
        functools.partial(_kernel, H=H, Hkv=Hkv, hd=hd, k0=k0, k1=k1,
                          rotary=rotary),
        out_shape=[jax.ShapeDtypeStruct((rows, 2 * kv), qkv.dtype),
                   jax.ShapeDtypeStruct((rows, H * 2 * kv), qkv.dtype),
                   jax.ShapeDtypeStruct(tail.shape, qkv.dtype)],
        name="cca_mix_step",
        interpret=interpret,
    )(qkv, tail, vecs.reshape(vecs.shape[0], -1, 1, hd), conv1_w, turns)
    return tuple(a[:N] for a in out) if pad else tuple(out)
