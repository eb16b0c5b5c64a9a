"""The Pallas kernel that reads a latent page's filled tiles where they lie
(``ops/latent_tiles.py``), under Pallas' TPU interpreter on the CPU, at the
page's real 640 columns, blocks of 16 positions and 64 heads: its ``(values,
largest score, sum)`` against the loop of
``models/common._over_tiles``, which stays as the portable form and as the
kernel's oracle, and the whole attention that comes of it against a plain
float32 softmax over the rectangle.

Both branches of ``_over_tiles``' ``lax.platform_dependent`` are run at the
seam itself: the test puts a function in its place that calls the TPU's
branch (the kernel, interpreted) and the default one (the loop), keeps what
each returned and hands on the kernel's.

And at the shape a compressed convolutional attention gives it
(``models/zaya.py``, PR 49): 8 query rows as wide as a page of 512 columns,
``[v 256 | k^ 256]``, query head ``i`` zero outside the 128 columns of its
own key head, the first 256 columns the values.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from demodel_tpu.models import common
from demodel_tpu.ops import latent_tiles
from demodel_tpu.serve import kvcache

HEADS, PAGE, VALUES, LATENT, BLOCK = 64, 640, 512, 576, 16
TILE = kvcache.TILE_BLOCKS * BLOCK          # 256 positions
SLOTS = 4 * kvcache.TILE_BLOCKS             # four tiles a row: 1 024 positions
SCALE = 0.11

#: cached positions a row; six rows of four tiles are a capacity of 24
#: tiles, which the loop takes in chunks of 8
CASES = {
    # a pad row of the batch bucket: no tile, its table names the scratch
    # block; it must come out as the loop's initial carry
    "a-pad-row": [300, 0, 700, 0, 256, 0],
    # one position of a second tile, and a first tile partly filled
    "a-tile-partly-filled": [257, 100, 1, 255, 513, 16],
    # three tiles a row: row 2's lie at 6, 7, 8 of the list and row 5's at
    # 15, 16, 17, across what was a chunk's edge
    "across-a-chunks-edge": [600, 700, 520, 768, 513, 640],
    "a-table-at-its-capacity": [1024] * 6,
    "rows-of-unequal-depth": [1, 1024, 256, 511, 0, 770],
}


#: ``(query rows, the page's columns, of them values, of them filled)``
LATENT_PAGE = (HEADS, PAGE, VALUES, LATENT)
COMPRESSED_PAGE = (8, 512, 256, 512)


def _page(lengths, dtype, seed, shape=LATENT_PAGE):
    """A pool of two layers whose blocks hold seeded latents (zeros in the
    page's last 64 columns), a table of shuffled blocks (a pad row's names
    the scratch block), queries and the new position's latent."""
    HEADS, PAGE, _VALUES, LATENT = shape
    B = len(lengths)
    rng = np.random.default_rng(seed)
    nb = B * SLOTS
    k = rng.normal(size=(2, nb + 1, 1, BLOCK, PAGE))
    k[..., LATENT:] = 0
    table = rng.permutation(nb).reshape(B, SLOTS)
    table[[n == 0 for n in lengths]] = nb
    q = rng.normal(size=(B, 1, HEADS, PAGE)) * 0.3
    q[..., LATENT:] = 0
    new = rng.normal(size=(B, 1, 1, PAGE))
    new[..., LATENT:] = 0
    return (jnp.asarray(q, dtype), jnp.asarray(new, dtype),
            kvcache.Paged(jnp.asarray(k, dtype), None,
                          jnp.asarray(table, jnp.int32)))


def _plain(q, new, cache, layer, lengths, shape=LATENT_PAGE):
    """One float32 softmax a row over its cached positions and the new one,
    from the rectangle the table names."""
    HEADS, PAGE, VALUES, _LATENT = shape
    q, new, k = (np.asarray(a, np.float64) for a in (q, new, cache.k))
    out = np.zeros((len(lengths), HEADS * VALUES))
    for b, n in enumerate(lengths):
        keys = np.concatenate(
            [k[layer, np.asarray(cache.table[b])].reshape(-1, PAGE)[:n],
             new[b, 0]])
        s = q[b, 0] @ keys.T * SCALE
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        out[b] = (p / p.sum(axis=-1, keepdims=True)
                  @ keys[:, :VALUES]).reshape(-1)
    return out


def _own_key_head(q, groups: int = 2):
    """Queries as ``models/zaya.py`` pads them: nothing under the values'
    columns, and of the keys' only the query head's own key head's."""
    B, T, H, P = q.shape
    width = P // 2 // groups
    head = np.arange(H)[:, None] // (H // groups)
    column = np.arange(P)[None, :]
    own = (column >= P // 2 + head * width) \
        & (column < P // 2 + (head + 1) * width)
    return jnp.where(jnp.asarray(own), q, 0)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case,shape", [
    *((case, LATENT_PAGE) for case in sorted(CASES)),
    ("a-tile-partly-filled", COMPRESSED_PAGE),
    ("rows-of-unequal-depth", COMPRESSED_PAGE),
], ids=[*sorted(CASES), "8-rows-of-512-a-tile-partly-filled",
        "8-rows-of-512-rows-of-unequal-depth"])
def test_the_kernel_is_the_loop(monkeypatch, case, shape, dtype):
    HEADS, _PAGE, VALUES, _LATENT = shape
    lengths = CASES[case]
    q, new, cache = _page(lengths, dtype, seed=len(case), shape=shape)
    if shape == COMPRESSED_PAGE:
        q = _own_key_head(q)
    seen = {}

    def both(*args, tpu, default):
        seen["kernel"], seen["loop"] = tpu(*args), default(*args)
        return seen["kernel"]

    monkeypatch.setattr(common.lax, "platform_dependent", both)
    monkeypatch.setattr(
        latent_tiles, "over_filled_tiles", functools.partial(
            latent_tiles.over_filled_tiles,
            interpret=pltpu.InterpretParams()))
    n = jnp.asarray(lengths, jnp.int32)
    tiles = cache.past(1, cache.filled(n))
    assert tiles.v is None
    assert int(tiles.trips) == -(-sum(-(-m // TILE) for m in lengths) // 8)
    out = common.attend(q, new, new[..., :VALUES], n[:, None], past=tiles,
                        scale=SCALE)

    # the carry, kernel against loop: the same arithmetic, in another
    # order of additions (and in bfloat16 a score a rounding apart here
    # and there, which moves a probability by up to 2^-8 of itself)
    tight = dtype == "float32"
    for name, mine, its in zip(("values", "tops", "sums"),
                               seen["kernel"], seen["loop"]):
        assert mine.shape == its.shape == (
            len(lengths), 1, HEADS, 1, VALUES if name == "values" else 1)
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
        _plain(q, new, cache, 1, lengths, shape),
        rtol=1e-4 if tight else 3e-2, atol=1e-4 if tight else 3e-2)
