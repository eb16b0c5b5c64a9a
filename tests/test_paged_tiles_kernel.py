"""The Pallas kernel that reads the filled tiles of pages of keys and values
apart where they lie (``ops/paged_tiles.py``), under Pallas' TPU interpreter
on the CPU, at the two published shapes of the cells that run it, cut in
rows and depth only: Phi-4-mini-flash's 10 cached pairs of 128 under 4
query heads each and Qwen3-Next's 2 cached heads of 256 under 8, blocks of
16 positions. Its ``(values, largest score, sum)`` against the loop of
``models/common._over_tiles``, which stays as the portable form and as the
kernel's oracle, and the whole attention that comes of it against a plain
float32 softmax over the rectangle.

Both branches of ``_over_tiles``' ``lax.platform_dependent`` are run at the
seam itself: the test puts a function in its place that calls the TPU's
branch (the kernel, interpreted) and the default one (the loop), keeps what
each returned and hands on the kernel's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from demodel_tpu.models import common
from demodel_tpu.ops import paged_tiles
from demodel_tpu.serve import kvcache

BLOCK = 16
TILE = kvcache.TILE_BLOCKS * BLOCK          # 256 positions
SLOTS = 4 * kvcache.TILE_BLOCKS             # four tiles a row: 1 024 positions

#: ``(cached heads, query heads over each, a head's width)``
SHAPES = {"phi-4-mini-flash": (10, 4, 128), "qwen3-next": (2, 8, 256)}

#: cached positions a row; four rows of four tiles are a capacity of 16
#: tiles, all of them one chunk of the loop's: the filled ones lie first
CASES = {
    # a pad row of the batch bucket: no tile, its table names the scratch
    # block; it must come out as the loop's initial carry
    "a-row-of-no-tile": [300, 0, 700, 0],
    # one position of a second tile, and a first tile partly filled
    "a-last-tile-partly-live": [257, 100, 1, 513],
    "rows-of-unequal-depth": [1, 1024, 256, 770],
    # 11 of 16 tiles filled: the list repeats its last filled tile after
    "a-capacity-past-the-filled-tiles": [600, 700, 520, 768],
}


def _pages(lengths, shape, dtype, seed):
    """Pools of two layers whose blocks hold seeded keys and values, a
    table of shuffled blocks (a pad row's names the scratch block),
    queries and the new position's key and value."""
    Hkv, g, hd = shape
    B = len(lengths)
    rng = np.random.default_rng(seed)
    nb = B * SLOTS
    k, v = rng.normal(size=(2, 2, nb + 1, Hkv, BLOCK, hd))
    table = rng.permutation(nb).reshape(B, SLOTS)
    table[[n == 0 for n in lengths]] = nb
    q = rng.normal(size=(B, 1, Hkv * g, hd)) * 0.3
    new_k, new_v = rng.normal(size=(2, B, 1, Hkv, hd))
    return (jnp.asarray(q, dtype), jnp.asarray(new_k, dtype),
            jnp.asarray(new_v, dtype),
            kvcache.Paged(jnp.asarray(k, dtype), jnp.asarray(v, dtype),
                          jnp.asarray(table, jnp.int32)))


def _plain(q, new_k, new_v, cache, layer, lengths, shape):
    """One float32 softmax a row and query head over the row's cached
    positions and the new one, from the rectangle the table names."""
    Hkv, g, hd = shape
    q, new_k, new_v, k, v = (np.asarray(a, np.float64) for a in (
        q, new_k, new_v, cache.k, cache.v))
    out = np.zeros((len(lengths), Hkv, g, hd))
    for b, n in enumerate(lengths):
        at = np.asarray(cache.table[b])
        for h in range(Hkv):
            # [blocks, positions a block, hd] of one head, in table order
            keys = np.concatenate(
                [k[layer, at, h].reshape(-1, hd)[:n], new_k[b, :, h]])
            values = np.concatenate(
                [v[layer, at, h].reshape(-1, hd)[:n], new_v[b, :, h]])
            s = q[b, 0].reshape(Hkv, g, hd)[h] @ keys.T * hd ** -0.5
            p = np.exp(s - s.max(axis=-1, keepdims=True))
            out[b, h] = p / p.sum(axis=-1, keepdims=True) @ values
    return out.reshape(len(lengths), -1)


def _both(monkeypatch):
    """``_over_tiles`` runs the kernel (interpreted) and the loop, and goes
    on with the kernel's; returns where both carries are kept."""
    seen = {}

    def both(*args, tpu, default):
        seen["kernel"], seen["loop"] = tpu(*args), default(*args)
        return seen["kernel"]

    monkeypatch.setattr(common.lax, "platform_dependent", both)
    monkeypatch.setattr(
        paged_tiles, "over_filled_tiles", functools.partial(
            paged_tiles.over_filled_tiles,
            interpret=pltpu.InterpretParams()))
    return seen


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_the_loop(monkeypatch, case, shape, dtype):
    Hkv, g, hd = SHAPES[shape]
    lengths = CASES[case]
    q, new_k, new_v, cache = _pages(lengths, SHAPES[shape], dtype,
                                    seed=len(case) + Hkv)
    seen = _both(monkeypatch)
    n = jnp.asarray(lengths, jnp.int32)
    tiles = cache.past(1, cache.filled(n))
    assert tiles.v is not None and tiles.in_place
    filled = sum(-(-m // TILE) for m in lengths)
    assert filled < tiles.row.shape[0] == 16 and int(tiles.trips) == 1
    out = common.attend(q, new_k, new_v, n[:, None], past=tiles)

    # the carry, kernel against loop: the same arithmetic, in another
    # order of additions (and in bfloat16 a score a rounding apart here
    # and there, which moves a probability by up to 2^-8 of itself)
    tight = dtype == "float32"
    for name, mine, its in zip(("values", "tops", "sums"),
                               seen["kernel"], seen["loop"]):
        assert mine.shape == its.shape == (
            len(lengths), Hkv, g, 1, hd if name == "values" else 1)
        assert mine.dtype == its.dtype == jnp.float32
        np.testing.assert_allclose(
            mine, its, rtol=2e-5 if tight else 2e-2,
            atol=2e-4 if tight else (6e-2 if name == "values" else 2e-2),
            err_msg=name)
    # a row with no filled tile is the loop's initial carry, not what the
    # buffers held
    for b, m in enumerate(lengths):
        if m == 0:
            values, tops, sums = (np.asarray(a[b]) for a in seen["kernel"])
            assert not values.any() and not sums.any()
            assert (tops == np.float32(-1e30)).all()

    # the whole attention against a plain float32 softmax
    np.testing.assert_allclose(
        np.asarray(out, np.float32).reshape(len(lengths), -1),
        _plain(q, new_k, new_v, cache, 1, lengths, SHAPES[shape]),
        rtol=1e-4 if tight else 3e-2, atol=1e-4 if tight else 3e-2)


def test_attend_end_to_end_over_several_chunks(monkeypatch):
    """Through ``attend`` under one ``jit``, as a family's step calls it:
    six rows of Phi-4-mini-flash's heads in bfloat16 whose 17 filled tiles
    the loop takes in three chunks of 8; the program that holds the kernel
    and the one that holds the loop give the same attention, and both the
    plain softmax's."""
    shape = SHAPES["phi-4-mini-flash"]
    lengths = [1024, 0, 1024, 17, 770, 1000]
    q, new_k, new_v, cache = _pages(lengths, shape, "bfloat16", seed=50)
    monkeypatch.setattr(
        paged_tiles, "over_filled_tiles", functools.partial(
            paged_tiles.over_filled_tiles,
            interpret=pltpu.InterpretParams()))
    n = jnp.asarray(lengths, jnp.int32)

    def step(q, new_k, new_v, n, k, v, table):
        paged = kvcache.Paged(k, v, table)
        tiles = paged.past(1, paged.filled(n))
        assert tiles.chunk_tiles == 8 and tiles.row.shape == (24,)
        assert -(-sum(-(-m // TILE) for m in lengths) // 8) == 3
        return common.attend(q, new_k, new_v, n[:, None], past=tiles)

    out = {}
    for branch in ("tpu", "default"):
        monkeypatch.setattr(
            common.lax, "platform_dependent",
            lambda *args, tpu, default, branch=branch:
            (tpu if branch == "tpu" else default)(*args))
        out[branch] = np.asarray(jax.jit(step)(
            q, new_k, new_v, n, cache.k, cache.v, cache.table), np.float32)
    plain = _plain(q, new_k, new_v, cache, 1, lengths, shape)
    np.testing.assert_allclose(out["tpu"], out["default"], rtol=3e-2,
                               atol=3e-2)
    for branch in out:
        np.testing.assert_allclose(out[branch].reshape(len(lengths), -1),
                                   plain, rtol=3e-2, atol=3e-2)
