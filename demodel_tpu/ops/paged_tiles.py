"""The filled tiles of pages of keys and values apart, read where they lie:
the Pallas TPU kernel a decode step's attention over whole rows runs in the
place of the chunk's two gathers and the loop around them
(:func:`demodel_tpu.models.common._over_tiles`).

The pool holds keys and values as two arrays, ``[layers x blocks, Hkv,
block_tokens, hd]`` each: ``Hkv`` cached heads of their own, ``g`` query
heads over each (4 over 10 pairs of 128 in Phi-4-mini-flash, 8 over 2 heads
of 256 in Qwen3-Next). A wide decode step reads, for each row, the tiles its
row has filled (``kvcache.Tiles``: 16 blocks a tile, listed flat in row
order). XLA fuses no gather into the product that reads it, so the loop
wrote a chunk of keys and then one of values (42 MB each) and read them
again, a trip, a reading layer; here both arrays stay in HBM, the kernel
follows the index itself and copies a tile's K blocks and V blocks, each
from where it lies, into fast memory, the next tile's copies started before
this tile's products.

- grid ``(rows,)``: a step of the grid is one row of the batch, its queries
  ``[Hkv, g x T, hd]`` and its carry resident; inside, a loop over the
  row's filled tiles, so the cost follows the filled tiles and not the
  table's capacity. The flat list is in row order: the tile after a row's
  last is the next row's first, and is prefetched across the grid's steps;
- two buffers of a tile for the keys and two for the values, ``[blocks,
  Hkv, block_tokens, hd]`` as a block lies in the pool (one copy a block,
  all its heads), one semaphore a buffer: a tile's 16 copies signal it and
  ONE wait takes the buffer's bytes off it. The values' copies start when
  the keys' do: fast memory holds both (``kvcache._in_place`` sends no
  tile here whose four buffers pass ``kvcache.KERNEL_BYTES``, and no
  pool that lies on several chips). The wrapper is a ``jit`` of its
  own, so a program traces and lowers the kernel once and every reading
  layer of the step calls it;
- scalar prefetch: the tiles' block ids (a layer's offset already in
  them), where each row's tiles start in the list and how many it has
  filled, how many positions of each tile are its row's (a prefix), the
  count of filled tiles;
- a tile's products are a head's at a time, that head's ``g x T`` queries
  against its 256 keys and its probabilities against its 256 values, read
  out of the buffers by head; everything between them is done for all the
  heads at once;
- the arithmetic is ``_over_tiles``' own, ``partials`` then ``trip``:
  scores in the queries' dtype, scaled there, masked in float32; a tile's
  exponentials below ITS largest score, cast to the queries' dtype before
  the product with the tile's values, accumulated in float32; then the tile
  joins its row's running ``(values, largest score, sum)`` in float32. A
  row with no filled tile comes out as the loop's initial carry.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MASKED = -1e30
#: lanes of the kernel's second result: a query's largest score fills the
#: first half, its sum the second (a result one lane wide would be padded
#: to this anyway)
LANES = 128


def _kernel(ids_ref, first_ref, filled_ref, live_ref, count_ref,   # SMEM
            q_ref, k_ref, v_ref, values_ref, stats_ref,
            kbuf, vbuf, ksem, vsem, *, scale: float, blocks: int):
    b = pl.program_id(0)
    _, Hkv, R, hd = q_ref.shape
    block_tokens = kbuf.shape[3]
    span = blocks * block_tokens
    count = count_ref[0]
    f32 = jnp.float32

    def start(tile, slot):
        """A tile's K blocks and V blocks, each from where it lies in its
        pool to its place in the slot's buffers, on the buffers' own
        semaphores."""
        for i in range(blocks):
            at = ids_ref[tile * blocks + i]
            pltpu.make_async_copy(k_ref.at[at], kbuf.at[slot, i],
                                  ksem.at[slot]).start()
            pltpu.make_async_copy(v_ref.at[at], vbuf.at[slot, i],
                                  vsem.at[slot]).start()

    def wait(buf, sem, slot):
        """One wait for the buffer's bytes, whichever blocks they came
        from (this copy is never made: it gives the size)."""
        pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                              sem.at[slot]).wait()

    @pl.when((b == 0) & (count > 0))
    def _():
        start(0, 0)

    q = q_ref[0]                                        # [Hkv, R, hd]
    values_ref[0] = jnp.zeros(values_ref.shape[1:], f32)

    def tile(j, carry):
        top, total = carry                              # [Hkv, R, 1] each
        t = first_ref[b] + j
        slot = t % 2

        @pl.when(t + 1 < count)
        def _():
            start(t + 1, 1 - slot)

        wait(kbuf, ksem, slot)
        s = jnp.stack([
            lax.dot_general(q[h], kbuf[slot, :, h].reshape(span, hd),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=f32)
            for h in range(Hkv)])                       # [Hkv, R, span]
        # the loop's scores: the product in q's dtype, scaled there
        s = (s.astype(q.dtype) * scale).astype(f32)
        keep = lax.broadcasted_iota(jnp.int32, s.shape, 2) < live_ref[t]
        s = jnp.where(keep, s, MASKED)
        mine = s.max(axis=-1, keepdims=True)
        p = jnp.where(keep, jnp.exp(s - mine), 0.0)
        pq = p.astype(q.dtype)
        wait(vbuf, vsem, slot)
        o = jnp.stack([
            jnp.dot(pq[h], vbuf[slot, :, h].reshape(span, hd),
                    preferred_element_type=f32)
            for h in range(Hkv)])                       # [Hkv, R, hd]
        new = jnp.maximum(top, mine)
        w, old = jnp.exp(mine - new), jnp.exp(top - new)
        values_ref[0] = old * values_ref[0] + w * o
        return new, old * total + w * p.sum(axis=-1, keepdims=True)

    top, total = lax.fori_loop(
        0, filled_ref[b], tile,
        (jnp.full((Hkv, R, 1), MASKED, f32), jnp.zeros((Hkv, R, 1), f32)))
    half = lax.broadcasted_iota(jnp.int32, (Hkv, R, LANES), 2) < LANES // 2
    stats_ref[0] = jnp.where(half, top, total)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def over_filled_tiles(q, tiles, scale: float, *, interpret=False):
    """The running softmax of ``q`` [B, Hkv, R, hd] (a row's ``R`` queries a
    cached head, all of which see every position their row holds) over the
    filled tiles of pages of keys and values apart (``tiles.k`` and
    ``tiles.v`` [N, Hkv, block_tokens, hd]): ``(values [B, Hkv, R, hd],
    largest score [B, Hkv, R, 1], sum [B, Hkv, R, 1])`` in float32, what
    ``_over_tiles``' loop carries out of its last trip."""
    B, Hkv, R, hd = q.shape
    _, heads, block_tokens, width = tiles.k.shape
    blocks = tiles.ids.shape[1]
    assert tiles.v is not None and tiles.v.shape == tiles.k.shape \
        and (heads, width) == (Hkv, hd), (tiles.k.shape, q.shape)
    # where a row's tiles lie in the list, and how many of them
    first = jnp.maximum(tiles.own[:, 0], 0).astype(jnp.int32)
    filled = (tiles.own >= 0).sum(axis=1, dtype=jnp.int32)
    # a tile's live positions are a prefix of it; none past the filled
    live = tiles.live.sum(axis=1, dtype=jnp.int32)
    count = filled.sum(dtype=jnp.int32).reshape(1)
    f32 = jnp.float32
    buffers = pltpu.VMEM((2, blocks, Hkv, block_tokens, hd), tiles.k.dtype)
    values, stats = pl.pallas_call(
        functools.partial(_kernel, scale=scale, blocks=blocks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, Hkv, R, hd), lambda b, *_: (b, 0, 0, 0)),
                # the pools stay where they lie
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((1, Hkv, R, hd), lambda b, *_: (b, 0, 0, 0)),
                pl.BlockSpec((1, Hkv, R, LANES), lambda b, *_: (b, 0, 0, 0)),
            ],
            scratch_shapes=[buffers, buffers,
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, R, hd), f32),
                   jax.ShapeDtypeStruct((B, Hkv, R, LANES), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_filled_tiles",
        interpret=interpret,
    )(tiles.ids.astype(jnp.int32).reshape(-1), first, filled, live, count,
      q, tiles.k, tiles.v)
    half = LANES // 2
    return values, stats[..., :1], stats[..., half:half + 1]
