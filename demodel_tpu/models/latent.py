"""Latent attention (MLA) over one cached vector a position: what the
families that keep such a cache share (:mod:`demodel_tpu.models.axk1`,
:mod:`demodel_tpu.models.longcat_flash`).

``c_q = RMSNorm(W_qa x)``; head ``i`` of ``H`` has ``[q_nope_i | q_rope_i]
= W_qb c_q``. ``[c_kv | k_r] = W_kva x``, ``c_kv <- RMSNorm(c_kv)``, and
``[k_nope_i | v_i] = W_kvb c_kv``. ``q_rope_i`` and ``k_r``, which every
head shares, are rotated (adjacent columns a pair). ``s_i(t, u) = scale
(q_nope_i(t) k_nope_i(u) + q_rope_i(t) k_r(u))``, a causal softmax in
float32, ``o_i = sum p_i v_i``, ``Attn = W_o [o_1 .. o_H]``.

A family differs in what :class:`Geometry` holds: the rotary's inverse
frequencies and the factor on cos and sin (plain, or YaRN's), the scores'
scale, the norms' ``eps``. A scale on the output of either latent norm
(``(hidden / rank) ** 0.5``) multiplies a norm's output ahead of a linear
map without bias, so a family that has one folds it into that norm's
weight when it loads; the folded weight is held in float32 (such a scale is
no bfloat16 number, and a weight of ones would carry one rounding into
every column), and the normalised vector is rounded once, after it.

**Two paths for one set of weights.** ``W_kvb`` is held split by head,
``w_uk`` and ``w_uv`` ``[H, rank, 128]`` each, and ``W_qb`` as its unrotated
and its rotary rows, ``q_b_nope`` and ``q_b_rope`` ``[out, in]``.
:func:`expanded` (a prompt) makes keys and values a head from ``c_kv`` and
hands the prompt's ``[c_kv | k_r | 0]`` to the caller for the pool.
:func:`absorbed` (a step) folds ``w_uk`` into the queries and attends over
the one cached vector, whose first ``rank`` columns are also the values:
multi-query attention of ``H`` heads, ``w_uv`` on what comes out. No key or
value of a head is ever written.

**The page** is ``[c_kv | k_r]`` and zeros up to a multiple of
:data:`LANES`: the TPU holds an array whose innermost dimension is no
multiple of its 128 lanes with another dimension innermost (the blocks),
and every program that took the pool would first copy all of it into the
order it reads. In the order it is read the tiles pad 576 to 640 anyway;
the page says so and the queries carry zeros there.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from demodel_tpu.models.common import attend, rms_norm
from demodel_tpu.models.hf_loader import Weights, folder, head_splitter
from demodel_tpu.utils.metrics import HUB

HUB.inc("gen_latent_kv_bytes_total", 0)

#: the TPU's lanes: the page's width is the latent's rounded up to them
LANES = 128


class Geometry(NamedTuple):
    """What a family's configuration fixes of its latent attention."""
    heads: int
    rank: int               # of c_kv: the values' width in the page
    rope: int               # rotary columns of a query head and of k_r
    inv: tuple[float, ...]  # inverse frequency of each rotary column pair
    factor: float           # on cos and sin
    scale: float            # of the scores
    eps: float              # of the two latent norms

    @property
    def latent_dim(self) -> int:
        """What a position keeps a layer: ``[c_kv | k_rope]``."""
        return self.rank + self.rope

    @property
    def page_dim(self) -> int:
        """The page's columns a position: :attr:`latent_dim` and zeros up
        to a multiple of :data:`LANES`."""
        return -(-self.latent_dim // LANES) * LANES


def rotate(x, positions, geo: Geometry):
    """``x`` [B, T, h, rope] at ``positions`` [B, T]: adjacent columns ``(2j,
    2j + 1)`` are a pair, as the checkpoint holds them; the result has the
    pairs' first halves before their second (every rotated query meets
    keys rotated here, so the order drops out of the scores)."""
    ang = positions[..., None].astype(jnp.float32) * np.asarray(
        geo.inv, np.float32)                                # [B, T, r/2]
    cos = (jnp.cos(ang) * geo.factor)[:, :, None, :]
    sin = (jnp.sin(ang) * geo.factor)[:, :, None, :]
    a, b = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def project(layer, x, geo: Geometry, positions):
    """What both paths share: the queries ``(q_nope, q_rope)`` [B, T, H,
    128 | 64], rotated, and the position's cached vector ``[c_kv | k_r |
    0]`` [B, T, 1, page], normalised, rotated and as wide as the page."""
    B, T, _D = x.shape
    H, C = geo.heads, geo.rank
    c_q = rms_norm(x @ layer["q_a_proj"], layer["q_a_norm"],
                   geo.eps).astype(x.dtype)
    q_nope = jnp.einsum("btq,nq->btn", c_q, layer["q_b_nope"]).reshape(
        B, T, H, -1)
    q_rope = jnp.einsum("btq,nq->btn", c_q, layer["q_b_rope"]).reshape(
        B, T, H, -1)
    kv = x @ layer["kv_a_proj"]
    c_kv = rms_norm(kv[..., :C], layer["kv_a_norm"], geo.eps).astype(x.dtype)
    k_r = rotate(kv[..., None, C:], positions, geo)
    return (q_nope, rotate(q_rope, positions, geo),
            jnp.concatenate([c_kv[:, :, None, :], k_r, jnp.zeros(
                (B, T, 1, geo.page_dim - geo.latent_dim), x.dtype)],
                axis=-1))


def expanded(layer, x, geo: Geometry, positions):
    """A prompt's attention, ``x`` [B, T, D] → ``(out, latent [B, T, 1,
    page])``: keys and values a head from ``c_kv``, ``H`` heads of 192 |
    128."""
    B, T, _D = x.shape
    H, C = geo.heads, geo.rank
    q_nope, q_rope, latent = project(layer, x, geo, positions)
    c_kv = latent[:, :, 0, :C]
    k_nope = jnp.einsum("btc,hcd->bthd", c_kv, layer["w_uk"])
    v = jnp.einsum("btc,hcd->bthd", c_kv, layer["w_uv"])
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        latent[..., C:geo.latent_dim], (B, T, H, geo.rope))], axis=-1)
    out = attend(jnp.concatenate([q_nope, q_rope], axis=-1), k, v, positions,
                 scale=geo.scale)
    return out @ layer["o_proj"], latent


def absorbed(layer, x, geo: Geometry, positions, past):
    """A step's attention over the latent page, ``x`` [B, 1, D] → ``(out,
    latent [B, 1, 1, page])``: ``w_uk`` folded into the queries (zeros
    where the page has them), ``w_uv`` applied to what the probabilities
    weigh of ``c_kv``; ``past`` has no values of its own
    (``common.attend``)."""
    B, T, _D = x.shape
    H, C = geo.heads, geo.rank
    q_nope, q_rope, latent = project(layer, x, geo, positions)
    with jax.named_scope("attn.latent.absorb"):
        q = jnp.concatenate(
            [jnp.einsum("bthd,hcd->bthc", q_nope, layer["w_uk"]), q_rope,
             jnp.zeros((B, T, H, geo.page_dim - geo.latent_dim), x.dtype)],
            axis=-1)
        o = attend(q, latent, latent[..., :C], positions, past=past,
                   scale=geo.scale)
        out = jnp.einsum("bthc,hcd->bthd", o.reshape(B, T, H, C),
                         layer["w_uv"])
    return out.reshape(B, T, -1) @ layer["o_proj"], latent


def attention(layer, x, geo: Geometry, positions, past):
    """One latent attention under its scope: :func:`absorbed` over a
    layer's ``past`` (a step), :func:`expanded` where there is none (a
    prompt)."""
    with jax.named_scope("attn.latent"):
        if past is None:
            return expanded(layer, x, geo, positions)
        return absorbed(layer, x, geo, positions, past)


def matrices(dense, D: int, Q: int, geo: Geometry, nope: int,
             vd: int) -> dict:
    """One attention's matrices for a family's ``init_params``
    (``dense(*shape, fan_in=)`` its seeded matrix); the family adds the two
    latent norms, ``q_a_norm`` and ``kv_a_norm``."""
    H, C, rope = geo.heads, geo.rank, geo.rope
    return {
        "q_a_proj": dense(D, Q),
        # [out, in], as the checkpoint holds them and the chip's
        # compiler lays them out for both programs
        "q_b_nope": dense(H * nope, Q, fan_in=Q),
        "q_b_rope": dense(H * rope, Q, fan_in=Q),
        "kv_a_proj": dense(D, C + rope),
        "w_uk": dense(H, C, nope), "w_uv": dense(H, C, vd),
        "o_proj": dense(H * vd, D),
    }


def observe(positions, spec, geo: Geometry, dtype) -> dict:
    """``latent_bytes`` for a step's span: the positions of the latent page
    the step's rows read (a prefill: wrote) of one paging layer, times the
    ``[c_kv | k_rope]`` each of the ``spec``'s paging layers keeps of one
    (the page's zeros are not the latent's bytes). The counter is counted
    here."""
    moved = int(positions) * spec.layers * geo.latent_dim \
        * jnp.dtype(dtype).itemsize
    HUB.inc("gen_latent_kv_bytes_total", moved)
    return {"latent_bytes": moved}


def load_attention(w: Weights, a: str, heads: int, nope: int, sh: dict,
                   scales: tuple = (None, None)) -> dict:
    """One latent attention's tensors under the prefix ``a`` → the nine
    leaves this module reads (``sh`` their shardings by leaf).
    ``kv_b_proj`` (a head's ``nope`` key rows before its value rows) is
    split by head into ``w_uk`` and ``w_uv``, ``q_b_proj`` (a head's
    ``nope`` unrotated rows before its rotary ones) into the two kinds of
    row. ``scales``: what a family multiplies the normalised
    ``c_q`` and ``c_kv`` by, folded into the two norms' weights in float32
    (None: the weight as the checkpoint holds it)."""
    def lin(name, leaf):
        return w.get(a + name, transpose=True, sharding=sh.get(leaf))

    def norm(name, leaf, scale):
        if scale is None:
            return w.get(a + name, sharding=sh.get(leaf))
        return folder(scale, sh.get(leaf))(w.get(a + name))

    w_uk, w_uv = head_splitter(heads, nope, True, sh.get("w_uk"))(
        w.get(a + "kv_b_proj.weight"))
    q_b_nope, q_b_rope = head_splitter(
        heads, nope, False, sh.get("q_b_nope"))(w.get(a + "q_b_proj.weight"))
    return {
        "q_a_proj": lin("q_a_proj.weight", "q_a_proj"),
        "q_a_norm": norm("q_a_layernorm.weight", "q_a_norm", scales[0]),
        "q_b_nope": q_b_nope, "q_b_rope": q_b_rope,
        "kv_a_proj": lin("kv_a_proj_with_mqa.weight", "kv_a_proj"),
        "kv_a_norm": norm("kv_a_layernorm.weight", "kv_a_norm", scales[1]),
        "w_uk": w_uk, "w_uv": w_uv,
        "o_proj": lin("o_proj.weight", "o_proj"),
    }
