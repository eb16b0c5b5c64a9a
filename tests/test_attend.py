"""``models/common.attend``: the one attention over new keys and a paged
cache, against a dense float32 reference written here. A narrow past is a
rectangle ``(k, v, kpos, live)``; a wide one the tiles its rows have filled
(``kvcache.Paged.filled`` / ``past``), gathered a chunk at a time by one
loop that carries one running softmax a row, over pages of keys and values
and over a latent page of one array."""

from __future__ import annotations

import itertools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from demodel_tpu.models.common import attend
from demodel_tpu.serve import kvcache

B, T, HKV, HD = 3, 5, 2, 8
VD = 6                  # a latent page's values: the keys' first columns
BS, M = 4, 3            # block_tokens, blocks of past a row

# the wide past: blocks of 2 positions, so a tile holds 32 and a row's table
# of 64 slots four tiles (128 positions); one row of each length that matters
WIDE_BS, WIDE_SLOTS = 2, 4 * kvcache.TILE_BLOCKS
TILE = kvcache.TILE_BLOCKS * WIDE_BS
WIDE_LENGTHS = np.asarray([0, 1, TILE - 1, TILE, TILE + 1,
                           WIDE_SLOTS * WIDE_BS, 0, 2 * TILE + 5])


def _dense(q, keys, values, qpos, kpos, seen, window, scale=None):
    """Row by row, head by head, in float32: ``keys`` [B, S, Hkv, hd] and
    ``values`` [B, S, Hkv, vd] at ``kpos`` [B, S], of which a query sees
    those ``seen`` [B, S] that lie 0..window-1 behind it."""
    Bq, Tq, H, hd = q.shape
    vd = values.shape[-1]
    scale = hd ** -0.5 if scale is None else scale
    out = np.zeros((Bq, Tq, H, vd), np.float32)
    for b in range(Bq):
        for t in range(Tq):
            behind = qpos[b, t] - kpos[b]
            keep = seen[b] & (behind >= 0)
            if window:
                keep &= behind < window
            for h in range(H):
                kv = h // (H // keys.shape[2])
                s = keys[b, keep, kv] @ q[b, t, h] * scale
                w = np.exp(s - s.max())
                out[b, t, h] = (w / w.sum()) @ values[b, keep, kv]
    return out.reshape(Bq, Tq, H * vd)


def _flat(a):
    """[B, m, Hkv, bs, hd] -> [B, m * bs, Hkv, hd]."""
    Bq, m, Hkv, bs, hd = a.shape
    return a.transpose(0, 1, 3, 2, 4).reshape(Bq, m * bs, Hkv, hd)


def _wide_cache(rng, rows: int, dtype=np.float32, layers: int = 2,
                latent: bool = False):
    """A pool of ``layers`` whose blocks are dealt to ``rows`` rows in no
    order, as ``kvcache.Paged``: pages of K and V at ``HKV`` heads, or a
    ``latent`` page of one array, one head all query heads share."""
    blocks = rows * WIDE_SLOTS
    k, v = (rng.normal(size=(layers, blocks + 1, 1 if latent else HKV,
                             WIDE_BS, HD)).astype(np.float32)
            for _ in range(2))
    table = rng.permutation(blocks).reshape(rows, WIDE_SLOTS)
    return kvcache.Paged(jnp.asarray(k, dtype),
                         None if latent else jnp.asarray(v, dtype),
                         jnp.asarray(table, jnp.int32))


def _rectangle(cache, layer, lengths):
    S = cache.table.shape[1] * cache.block_tokens
    kpos = jnp.broadcast_to(jnp.arange(S), (len(lengths), S))
    return (*cache.read(layer, cache.table), kpos,
            kpos < jnp.asarray(lengths)[:, None])


def _float32(text: str) -> list[tuple[int, ...]]:
    """The shapes of the float32 arrays a lowered text names."""
    return [tuple(int(d) for d in dims.split("x"))
            for dims in re.findall(r"tensor<([0-9x]+)xf32>", text)]


CASES = [pytest.param(paged, window, group, None, id=f"{paged}-{wid}-{gid}")
         for (group, gid), (window, wid), paged in itertools.product(
             ((1, "mha"), (8, "gqa8")),
             ((0, "full"), (128, "w128"), (3, "w3")), ("alone", "paged"))]
# the tiles of a wide past, for one query a row (a decode step) and for
# several; one trip of the loop over all 32 of them, or up to eight of four
# tiles each
CASES += [pytest.param(f"{how}-{chunk}", 0, group, scale,
                       id=f"{how}-chunk{chunk}-g{group}-{sid}")
          for how, chunk, group, (scale, sid) in itertools.product(
              ("step", "queries"), (32, 4), (1, 8),
              ((None, "scale"), (0.25, "own")))]
# a latent page of one array (values: the first VD columns of the keys)
# under 8 query heads. With four tiles a trip the rows of 2 and of 4 tiles
# straddle two trips.
CASES += [pytest.param(f"latent-{how}-{chunk}", 0, 8, scale,
                       id=f"latent-{how}-chunk{chunk}-g8-{sid}")
          for how, chunk, (scale, sid) in itertools.product(
              ("step", "queries"), (32, 4),
              ((None, "scale"), (0.25, "own")))]


@pytest.mark.parametrize("paged,window,group,scale", CASES)
def test_attend_matches_a_dense_reference(paged, window, group, scale,
                                          monkeypatch):
    """New keys alone (a prefill) and behind a paged past with a ragged
    ``live`` (a decode step and a chunk of several queries), with and
    without a window, with 1 and 8 query heads a KV head. Row 0 of the
    paged case has length 0: it sees only its own new keys, whatever its
    slots hold. A wide past comes as its filled tiles, one query a row or
    five (rows of 0, 1, a tile less one, a tile, a tile and one and the
    whole width in one batch: rows that have filled no tile, as a pad row
    of the bucket has none, and one that ends a tile exactly) and is held
    to the rectangle over the same pages besides."""
    rng = np.random.default_rng(11)
    how, _, chunk = paged.rpartition("-") if "-" in paged else (paged, "", "")
    latent = how.startswith("latent")
    kvh, vd = (1, VD) if latent else (HKV, HD)
    H = kvh * group
    rows = len(WIDE_LENGTHS) if chunk else B
    Tq = 1 if how.endswith("step") else T
    q, k, v = (rng.normal(size=(rows, Tq, h, HD)).astype(np.float32)
               for h in (H, kvh, kvh))
    if latent:
        v = k[..., :VD]
    lengths = np.asarray([0, 5, 11]) if how == "paged" else \
        WIDE_LENGTHS if chunk else np.zeros(B, int)
    positions = lengths[:, None] + np.arange(Tq)[None, :]
    past = rectangle = None
    keys, values, kpos = k, v, positions
    seen = np.ones((rows, Tq), bool)
    if how == "paged":
        pk, pv = (rng.normal(size=(B, M, HKV, BS, HD)).astype(np.float32)
                  for _ in range(2))
        slots = np.broadcast_to(np.arange(M * BS), (B, M * BS))
        live = slots < lengths[:, None]
        past = (jnp.asarray(pk), jnp.asarray(pv), jnp.asarray(slots),
                jnp.asarray(live))
    elif chunk:
        monkeypatch.setattr(kvcache, "TILE_CHUNK", int(chunk))
        cache = _wide_cache(rng, rows, latent=latent)
        assert cache.wide
        past = cache.past(1, cache.filled(jnp.asarray(lengths)))
        assert past.chunk_tiles == int(chunk)
        rectangle = _rectangle(cache, 1, lengths)
        pk, pv, slots, live = (a if a is None else np.asarray(a)
                               for a in rectangle)
        if latent:
            pv = pk[..., :VD]
    if past is not None:
        keys = np.concatenate([_flat(pk), k], axis=1)
        values = np.concatenate([_flat(pv), v], axis=1)
        kpos = np.concatenate([slots, positions], axis=1)
        seen = np.concatenate([live, seen], axis=1)
    new = (jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
           jnp.asarray(positions))
    got = attend(*new, window=window, past=past, scale=scale)
    assert got.shape == (rows, Tq, H * vd) and got.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got), _dense(q, keys, values, positions, kpos, seen,
                                window, scale), rtol=2e-5, atol=2e-5)
    if rectangle is not None:
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(attend(*new, past=rectangle,
                                               scale=scale)),
            rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("group", [2, 8], ids=["g2", "g8"])
@pytest.mark.parametrize("fast", [kvcache.FAST_BYTES, 0],
                         ids=["together", "apart"])
@pytest.mark.parametrize("chunk", [64, 4])
def test_tiles_past_a_rows_length_are_not_read(chunk, fast, group,
                                               monkeypatch):
    """Every block of a tile wholly past its row's length, and every block
    no row's table names, holds NaN: the tiles give the finite result the
    clean pool gives, to the bit; the rectangle, which multiplies what it
    masked by zero, does not. The same where a chunk of keys and one of
    values would not fit fast memory together and a trip gathers the
    values when it is done with the keys, under two and eight query heads
    a KV head (a tile past the filled ones repeats the last filled one and
    weighs nothing in its row)."""
    monkeypatch.setattr(kvcache, "TILE_CHUNK", chunk)
    monkeypatch.setattr(kvcache, "FAST_BYTES", fast)
    rng = np.random.default_rng(3)
    lengths = WIDE_LENGTHS
    rows = len(lengths)
    clean = _wide_cache(rng, rows)
    owned = np.zeros(clean.k.shape[1], bool)
    for row, n in zip(np.asarray(clean.table), lengths):
        owned[row[:-(-n // TILE) * kvcache.TILE_BLOCKS]] = True
    assert 0 < owned.sum() < owned.size - 1

    def poisoned(a):
        return jnp.where(owned[None, :, None, None, None], a, jnp.nan)

    dirty = clean._replace(k=poisoned(clean.k), v=poisoned(clean.v))
    q, k, v = (jnp.asarray(rng.normal(size=(rows, 1, h, HD)), jnp.float32)
               for h in (group * HKV, HKV, HKV))
    pos = jnp.asarray(lengths)[:, None]

    def over(cache):
        past = cache.past(0, cache.filled(jnp.asarray(lengths)))
        assert past.apart == (fast == 0)
        return np.asarray(attend(q, k, v, pos, past=past))

    got = over(dirty)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, over(clean))
    np.testing.assert_allclose(got, np.asarray(attend(
        q, k, v, pos, past=_rectangle(clean, 0, lengths))),
        rtol=1e-5, atol=1e-5)
    assert np.isnan(np.asarray(attend(
        q, k, v, pos, past=_rectangle(dirty, 0, lengths)))).any()


@pytest.mark.parametrize("past", ["alone", "rectangle", "tiles-g2",
                                  "tiles-g8"])
def test_probabilities_are_in_the_models_dtype(past):
    """float32 softmax, then the model's dtype for the value products: a
    bfloat16 call returns bfloat16 and stays near the float32 one, over
    new keys alone and over both kinds of past, the rectangle and the
    tiles of the same wide pages (under two query heads a KV head and
    under eight)."""
    rng = np.random.default_rng(5)
    rows = 2 if past == "alone" else len(WIDE_LENGTHS)
    q, k, v = (jnp.asarray(rng.normal(size=(rows, 1, h, HD)), jnp.bfloat16)
               for h in (16 if past == "tiles-g8" else 4, 2, 2))
    pos = jnp.asarray([[4], [2]]) if past == "alone" \
        else jnp.asarray(WIDE_LENGTHS)[:, None]

    def pages(dtype):
        if past == "alone":
            return None
        cache = _wide_cache(np.random.default_rng(7), rows, dtype)
        if past == "rectangle":
            return _rectangle(cache, 0, WIDE_LENGTHS)
        return cache.past(0, cache.filled(jnp.asarray(WIDE_LENGTHS)))

    got = attend(q, k, v, pos, past=pages(jnp.bfloat16))
    want = attend(*(a.astype(jnp.float32) for a in (q, k, v)), pos,
                  past=pages(jnp.float32))
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=3e-2)


@pytest.mark.parametrize("latent,group", [(False, 2), (False, 8), (True, 8)],
                         ids=["kv-g2", "kv-g8", "latent-g8"])
def test_nothing_of_the_tables_capacity_is_kept_in_float32(latent, group,
                                                           monkeypatch):
    """The lowered text of :func:`attend` over the tiles of five rows, 20
    tiles of capacity taken four a trip, in bfloat16: the loop carries its
    running softmax a row, ``[5, Hkv, g, T, vd | 1 | 1]`` in float32, and
    no float32 array of the program has an axis of 20 (a loop that left
    its partials a tile of the capacity kept ``[20, Hkv, g, T, vd + 2]``
    through its trips and gathered it a row at the end)."""
    monkeypatch.setattr(kvcache, "TILE_CHUNK", 4)
    rows = 5
    lengths = jnp.asarray(WIDE_LENGTHS[:rows])
    capacity = rows * WIDE_SLOTS // kvcache.TILE_BLOCKS
    cache = _wide_cache(np.random.default_rng(2), rows, jnp.bfloat16,
                        latent=latent)
    kvh, vd = (1, VD) if latent else (HKV, HD)
    q, k, v = (jnp.zeros((rows, 1, h, w), jnp.bfloat16)
               for h, w in ((kvh * group, HD), (kvh, HD), (kvh, vd)))

    def step(q, k, v, lengths, pk, pv, table):
        paged = kvcache.Paged(pk, pv, table)
        tiles = paged.past(1, paged.filled(lengths))
        assert tiles.row.shape == (capacity,) and tiles.chunk_tiles == 4
        return attend(q, k, v, lengths[:, None], past=tiles)

    text = jax.jit(step).lower(q, k, v, lengths, *cache[:3]).as_text()
    (loop,) = re.findall(r"stablehlo\.while\(.*\) : (.*)\n", text)
    assert _float32(loop) == [(rows, kvh, group, 1, vd)] \
        + [(rows, kvh, group, 1, 1)] * 2
    assert not [dims for dims in _float32(text) if capacity in dims]
