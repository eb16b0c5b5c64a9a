"""The filled tiles of a latent page, read where they lie: the Pallas TPU
kernel a latent attention's decode step runs in the place of the chunk's
gather and its loop (:func:`demodel_tpu.models.common._over_tiles`).

A latent page is ONE array, ``[layers x blocks, 1, block_tokens, 640]``: a
position's one cached vector is every head's key, and its first ``vd``
columns are every head's value. A wide decode step reads, for each row,
the tiles its row has filled (``kvcache.Tiles``: 16 blocks a tile, listed
flat in row order). XLA cannot fuse a gather into the product that reads
it, so the loop wrote a chunk of tiles (42 MB) and read it again, a trip;
here the pool stays in HBM, the kernel follows the index itself and copies
each tile's blocks straight into fast memory, the next tile's copies
started before this tile's products.

- grid ``(rows,)``: a step of the grid is one row of the batch, its
  queries ``[heads, 640]`` and its carry resident; inside, a loop over the
  row's filled tiles, so the cost follows the filled tiles and not the
  table's capacity. The flat list is in row order: the tile after a row's
  last is the next row's first, and is prefetched across the grid's steps;
- two buffers of a tile each, one semaphore a buffer: a tile's 16 copies
  signal it and ONE wait takes the buffer's bytes off it (sixteen waits
  cost the same on the chip and three times the kernel's text to trace and
  lower). The wrapper is a ``jit`` of its own, so a program traces and
  lowers the kernel once and every latent attention of the step calls it:
  lowered a layer, it added 2.4-4.4 s a decode program to a warm set-up
  (PERF.md, Findings, PR 45);
- scalar prefetch: the tiles' block ids, where each row's tiles start in
  the list and how many it has filled, how many positions of each tile are
  its row's (a prefix), the count of filled tiles;
- the arithmetic is ``_over_tiles``' own, ``partials`` then ``trip``:
  scores in the queries' dtype, scaled there, masked in float32; a tile's
  exponentials below ITS largest score, cast to the queries' dtype before
  the product with the tile's first ``vd`` columns, accumulated in
  float32; then the tile joins its row's running ``(values, largest score,
  sum)`` in float32. A row with no filled tile comes out as the loop's
  initial carry.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: lanes of the kernel's second result: a row's largest scores fill the
#: first half, its sums the second (a result one lane wide would be padded
#: to this anyway)
STAT_LANES = 128


def _kernel(ids_ref, first_ref, filled_ref, live_ref, count_ref,   # SMEM
            q_ref, pool_ref, values_ref, stats_ref, buf, sems, *,
            scale: float, vd: int, blocks: int, block_tokens: int):
    b = pl.program_id(0)
    span = blocks * block_tokens
    count = count_ref[0]

    def start(tile, slot):
        """A tile's blocks, each from where it lies in the pool to its
        place in ``buf[slot]``, all on the buffer's one semaphore."""
        for i in range(blocks):
            pltpu.make_async_copy(
                pool_ref.at[ids_ref[tile * blocks + i], 0], buf.at[slot, i],
                sems.at[slot]).start()

    def wait(slot):
        """One wait for the buffer's bytes, whichever blocks they came
        from (the source of this copy is never read: it gives the size)."""
        pltpu.make_async_copy(pool_ref.at[pl.ds(0, blocks), 0], buf.at[slot],
                              sems.at[slot]).wait()

    @pl.when((b == 0) & (count > 0))
    def _():
        start(0, 0)

    q = q_ref[0]                                        # [R, hd]
    R = q.shape[0]
    f32 = jnp.float32
    values_ref[0] = jnp.zeros((R, vd), f32)

    def tile(j, carry):
        top, total = carry                              # [R, 1] each
        t = first_ref[b] + j
        slot = t % 2

        @pl.when(t + 1 < count)
        def _():
            start(t + 1, 1 - slot)

        wait(slot)
        k = buf[slot].reshape(span, buf.shape[-1])      # [span, hd]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=f32)
        # the loop's scores: the product in q's dtype, scaled there
        s = (s.astype(q.dtype) * scale).astype(f32)
        keep = lax.broadcasted_iota(jnp.int32, (R, span), 1) < live_ref[t]
        s = jnp.where(keep, s, NEG_INF)
        mine = s.max(axis=-1, keepdims=True)
        p = jnp.where(keep, jnp.exp(s - mine), 0.0)
        o = jnp.dot(p.astype(q.dtype), k[:, :vd], preferred_element_type=f32)
        new = jnp.maximum(top, mine)
        w, old = jnp.exp(mine - new), jnp.exp(top - new)
        values_ref[0] = old * values_ref[0] + w * o
        return new, old * total + w * p.sum(axis=-1, keepdims=True)

    top, total = lax.fori_loop(
        0, filled_ref[b], tile,
        (jnp.full((R, 1), NEG_INF, f32), jnp.zeros((R, 1), f32)))
    half = lax.broadcasted_iota(jnp.int32, (R, STAT_LANES), 1) \
        < STAT_LANES // 2
    stats_ref[0] = jnp.where(half, top, total)


@functools.partial(jax.jit, static_argnames=("scale", "vd", "interpret"))
def over_filled_tiles(q, tiles, scale: float, vd: int, *, interpret=False):
    """The running softmax of ``q`` [B, R, hd] (a row's ``R`` queries, all
    of which see every position their row holds) over the filled tiles of
    a page of one array (``tiles.k`` [N, 1, block_tokens, hd], ``tiles.v``
    None): ``(values [B, R, vd], largest score [B, R, 1], sum [B, R, 1])``
    in float32, what ``_over_tiles``' loop carries out of its last trip."""
    B, R, hd = q.shape
    held, one, block_tokens, width = tiles.k.shape
    blocks = tiles.ids.shape[1]
    assert tiles.v is None and one == 1 and width == hd and held >= blocks, \
        (tiles.k.shape, hd)
    # where a row's tiles lie in the list, and how many of them
    mine = tiles.own >= 0
    first = jnp.maximum(tiles.own[:, 0], 0).astype(jnp.int32)
    filled = mine.sum(axis=1, dtype=jnp.int32)
    # a tile's live positions are a prefix of it; none past the filled
    live = tiles.live.sum(axis=1, dtype=jnp.int32)
    count = filled.sum(dtype=jnp.int32).reshape(1)
    f32 = jnp.float32
    values, stats = pl.pallas_call(
        functools.partial(_kernel, scale=scale, vd=vd, blocks=blocks,
                          block_tokens=block_tokens),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, R, hd), lambda b, *_: (b, 0, 0)),
                # the pool stays where it lies
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((1, R, vd), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec((1, R, STAT_LANES), lambda b, *_: (b, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, blocks, block_tokens, hd), tiles.k.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=[jax.ShapeDtypeStruct((B, R, vd), f32),
                   jax.ShapeDtypeStruct((B, R, STAT_LANES), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="latent_filled_tiles",
        interpret=interpret,
    )(tiles.ids.astype(jnp.int32).reshape(-1), first, filled, live, count,
      q, tiles.k)
    half = STAT_LANES // 2
    return values, stats[..., :1], stats[..., half:half + 1]
