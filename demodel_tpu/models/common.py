"""Shared numerics for the model families.

Norm statistics run in float32 regardless of activation dtype: bf16 mean/
variance across a wide hidden axis loses enough mantissa to shift logits —
the standard TPU-stable recipe (compute stats in fp32, scale in the
activation dtype).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from demodel_tpu.ops import latent_tiles, paged_tiles
from demodel_tpu.utils.env import env_bool


def refuse_unsupported(config: dict, fields=(
        "rope_scaling", "sliding_window", "attention_bias"),
        only: dict | None = None) -> None:
    """For a family's ``from_hf``: refuse a ``config.json`` whose ``fields``
    are set (non-null, non-false), or whose key of ``only`` has another
    value than the one the family implements (``a.b`` is key ``b`` of the
    group ``a``; a key left out has that value), which change numerics in
    ways that family does not implement — rather than drift."""
    for fld in fields:
        v = config.get(fld)
        if v not in (None, False):
            raise ValueError(
                f"config field {fld}={v!r} is not supported by this stack")
    for fld, value in (only or {}).items():
        group, _, key = fld.rpartition(".")
        v = (config.get(group) or {} if group else config).get(key, value)
        if v != value:
            raise ValueError(
                f"config field {fld}={v!r} is not supported by this stack "
                f"(only {value!r})")


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


def attend(q, k, v, positions, *, window: int = 0, past=None,
           scale: float | None = None):
    """Causal attention of new queries over their own keys and, when
    ``past`` is given, over a paged cache read where it lies: q [B, T, H,
    hd], k [B, T, Hkv, hd] and v [B, T, Hkv, vd] at ``positions`` [B, T] →
    [B, T, H * vd]. The values' width ``vd`` need not be the keys' (a
    latent layer's expanded heads have keys of 192 and values of 128, its
    absorbed ones keys of 576 and values of 512).
    The query heads are grouped by KV head in the products, so k and v are
    never repeated. ``window`` 0 sees every earlier key; otherwise a key
    ``window`` or more behind is not seen. ``past`` is ``(k, v, kpos,
    live)``: cached keys and values as the pool holds them, [B, m, Hkv,
    block_tokens, hd] (``kvcache.Paged.read``), the positions [B, m *
    block_tokens] of their slots and which of those hold the row's own
    (a row with none live, a pad row, sees only its new key). A past whose
    ``v`` is None comes from a page of one array: its values are the first
    ``vd`` columns of its keys, taken from the blocks gathered for the
    scores and not gathered again. One softmax
    in float32 over cached and new keys, probabilities in q's dtype. The
    scores are scaled by ``scale``, ``hd ** -0.5`` where none is given.

    A paged past wider than two tiles a row comes as the tiles its rows
    have filled (``kvcache.Tiles``, from ``Paged.filled``) and the same
    softmax runs over those alone, a chunk of tiles a trip, one running
    softmax a row carried between the trips: the rectangle is the case in
    which nothing can be skipped. A program lowered for a TPU runs a
    kernel that reads the tiles from the pool in the loop's place, one for
    a page of one array under one cached head and one for pages of keys
    and values apart (:func:`_over_tiles`)."""
    B, T, H, hd = q.shape
    Hkv, vd = k.shape[2], v.shape[-1]
    scale = hd ** -0.5 if scale is None else scale
    q = q.reshape(B, T, Hkv, H // Hkv, hd)

    def masked(s, kpos, live=True):
        """Scores [B, Hkv, g, T, S] in float32, a key at ``kpos`` [B, S]
        kept where the query sees it."""
        behind = positions[:, :, None] - kpos[:, None, :]
        keep = (behind >= 0) & (behind < window if window else True) & live
        return jnp.where(keep[:, None, None],
                         (s * scale).astype(jnp.float32), -1e30)

    s_new = masked(jnp.einsum("bqkgd,bskd->bkgqs", q, k), positions)
    if hasattr(past, "chunk"):
        assert not window, "a window's blocks are as wide as they are filled"
        out = _over_tiles(q, s_new, v, past, scale)
    elif past is None:
        p = jax.nn.softmax(s_new, axis=-1).astype(q.dtype)
        out = jnp.einsum("bkgqs,bskd->bqkgd", p, v)
    else:
        pk, pv, kpos, live = past
        if pv is None:
            pv = pk[..., :vd]
        m, c = pk.shape[1], pk.shape[3]
        s_past = jnp.einsum("bqkgd,bmkcd->bkgqmc", q, pk).reshape(
            B, Hkv, H // Hkv, T, m * c)
        # one softmax over cached and new keys, without copying the cached
        # blocks next to the new row
        p = jax.nn.softmax(jnp.concatenate(
            [masked(s_past, kpos, live[:, None, :]), s_new], axis=-1),
            axis=-1).astype(q.dtype)
        out = jnp.einsum("bkgqmc,bmkcd->bqkgd",
                         p[..., :m * c].reshape(*p.shape[:4], m, c), pv) \
            + jnp.einsum("bkgqs,bskd->bqkgd", p[..., m * c:], v)
    return out.reshape(B, T, H * vd)


def _over_tiles(q, s_new, v, tiles, scale):
    """:func:`attend` over the filled tiles of a paged past (the rows'
    cached prefixes, so every query sees every position its row holds): q
    [B, T, Hkv, g, hd], ``s_new`` the masked float32 scores of the new
    keys [B, Hkv, g, T, T], ``v`` their values. One loop, which carries
    **one running softmax a row** in float32 (the values its probabilities
    weigh, cast to q's dtype before that product; its largest score; the
    sum of the exponentials below it). A trip takes a chunk of tiles, has
    those three for each tile and combines them into their rows: the
    filled tiles lie in row order, so the sum over a row's tiles of a
    chunk is a product with the chunk's membership ``[rows, tiles]``.
    After the loop the carry meets the new keys as it is; a row with no
    tile filled sees its new keys only. Nothing the loop holds is as large
    as the table's capacity. The values are ``vd`` wide, as ``v`` is;
    tiles with no ``v`` of their own give the first ``vd`` columns of
    their keys.

    A trip gathers its chunk of tiles into one array before its products
    (XLA fuses no gather into the product that reads it; keys and values
    apart are two gathers, the values' after the keys are done with where
    both do not fit fast memory, ``tiles.apart``: the loop's concern
    alone). A Pallas kernel does without: it follows the index itself and
    copies each tile's blocks from the pool into fast memory, the same
    arithmetic, the same carry out. Both kinds of page have theirs: one
    array under one cached head :mod:`demodel_tpu.ops.latent_tiles`, keys
    and values apart under any number of heads
    :mod:`demodel_tpu.ops.paged_tiles`. Where the page has one
    (``tiles.in_place``, the one statement of it: not a pool that lies
    on several chips, which only the loop is partitioned for, nor a tile
    too large for the kernel's buffers), which of kernel and loop a
    program holds is the platform's it is lowered for
    (``lax.platform_dependent``: the kernel on a TPU, the loop everywhere
    else, where it is also the kernel's oracle), nothing else's."""
    B, T, Hkv, g, hd = q.shape
    vd = v.shape[-1]
    n = tiles.chunk_tiles
    f32 = jnp.float32

    def partials(i, rows):
        """Chunk ``i``'s tiles under the queries of their ``rows`` [n]
        (taken once the keys are gathered): ``(values [n, Hkv, g, T, vd],
        largest score, sum [n, Hkv, g, T, 1])``, each tile's own."""
        ids, live = tiles.chunk(i)
        pk = tiles.blocks(tiles.k, ids)
        m, c = pk.shape[1], pk.shape[3]
        s = jnp.einsum("nqkgd,nmkcd->nkgqmc",
                       q.at[rows].get(mode="promise_in_bounds"),
                       pk).reshape(n, Hkv, g, T, m * c)
        keep = live[:, None, None, None, :]
        s = jnp.where(keep, (s * scale).astype(f32), -1e30)
        top = s.max(axis=-1, keepdims=True)
        p = jnp.where(keep, jnp.exp(s - top), 0.0)
        pq = p.astype(q.dtype)
        if tiles.apart:
            # the values are gathered when the keys are done with
            ids, pq = lax.optimization_barrier((ids, pq))
        o = jnp.einsum("nkgqmc,nmkcd->nkgqd",
                       pq.reshape(n, Hkv, g, T, m, c),
                       # a page of one array: columns of the keys in hand
                       pk[..., :vd] if tiles.v is None
                       else tiles.blocks(tiles.v, ids),
                       preferred_element_type=f32)
        return o, top, p.sum(axis=-1, keepdims=True)

    def trip(i, carry):
        values, tops, sums = carry              # [B, Hkv, g, T, vd | 1 | 1]
        rows = tiles.rows(i)
        o, top, total = partials(i, rows)
        # whose a tile is, [B, n, 1, 1, 1, 1]; a tile past the filled ones
        # is its row's too, with the least score and no sum: no weight
        own = (rows[None, :] == jnp.arange(B)[:, None])[
            ..., None, None, None, None]
        new = jnp.maximum(tops, jnp.where(own, top[None], -1e30).max(axis=1))
        w = jnp.where(own, jnp.exp(top[None] - new[:, None]), 0.0)
        old = jnp.exp(tops - new)
        return (old * values + jnp.einsum(
                    "bnkgq,nkgqd->bkgqd", w[..., 0], o,
                    precision=lax.Precision.HIGHEST),
                new, old * sums + (w * total[None]).sum(axis=1))

    def loop():
        return lax.fori_loop(
            jnp.uint32(0), tiles.trips, trip,
            (jnp.zeros((B, Hkv, g, T, vd), f32),
             jnp.full((B, Hkv, g, T, 1), -1e30, f32),
             jnp.zeros((B, Hkv, g, T, 1), f32)))

    def in_place():
        rows = q.transpose(0, 2, 3, 1, 4)
        if tiles.v is None:
            carry = latent_tiles.over_filled_tiles(
                rows.reshape(B, g * T, hd), tiles, scale, vd)
        else:
            carry = paged_tiles.over_filled_tiles(
                rows.reshape(B, Hkv, g * T, hd), tiles, scale)
        return tuple(a.reshape(B, Hkv, g, T, -1) for a in carry)

    with jax.named_scope("attn.tiles"):
        if tiles.in_place:
            # on a TPU a kernel reads the tiles from the pool, and the
            # loop is its oracle
            values, tops, sums = lax.platform_dependent(
                tpu=in_place, default=loop)
        else:
            values, tops, sums = loop()
        top = jnp.maximum(tops, s_new.max(axis=-1, keepdims=True))
        w = jnp.exp(tops - top)
        p_new = jnp.exp(s_new - top)
        total = w * sums + p_new.sum(axis=-1, keepdims=True)
        out = w * values + jnp.einsum(
            "bkgqs,bskd->bkgqd", p_new.astype(q.dtype), v,
            preferred_element_type=f32)
        # [B, T, Hkv, g, vd]
        return (out / total).astype(q.dtype).transpose(0, 3, 1, 2, 4)


def use_flash_attention() -> bool:
    """Route model attention through the fused pallas kernel
    (ops/flash_attention.py)? Only when ``DEMODEL_FLASH_ATTN`` says so:
    the einsum path lets XLA fuse freely at short sequence, and whether
    flash wins once the score tensor or the GQA-repeated KV cache
    dominates HBM has not been measured on the chip."""
    return env_bool("DEMODEL_FLASH_ATTN")
