"""The held experts' grouped product, each hit expert streamed once: the
Pallas TPU kernel :func:`demodel_tpu.models.experts._slab` runs in the place
of ``jax.lax.ragged_dot`` in a program lowered for a TPU.

``rows`` [M, Kd] are sorted by group, ``sizes[e]`` of them to expert ``e``
of ``weights`` [E, Kd, Nd]; rows past the groups' end belong to nobody and
what comes out there is whatever the memory held (the caller drops it). The
work is a list of *visits*: a row tile of ``tm`` rows under one expert that
has rows in it. A tile that holds rows of three experts is visited three
times, a group that crosses a tile's end twice; an expert with no row is in
no visit, so its weights are never fetched.

- grid ``(Nd tiles, visits, Kd tiles)``, the visits' count the data's (a
  call in which nothing landed runs no step). Scalar prefetch: each visit's
  expert and row tile, the groups' offsets. The weights' ``index_map``
  follows the visit's expert, so the pipeline copies the next tile of
  ``[tk, tn]`` (the next expert's first among them) while this one is
  multiplied; float32 accumulation over the ``Kd`` tiles in a scratch, then
  the rows of the visit's group, and only those, are stored;
- the tiles are :func:`tiling`'s, from ``(M, Kd, Nd)`` and the dtype
  alone: a weight tile is the expert's whole matrix, or as many whole rows
  of ``[Kd, Nd]`` (one contiguous run of the stacked array) as
  :data:`WEIGHT_TILE_BYTES` holds; the row tile follows the call's rows;
- the wrapper is a ``jit`` of its own: a program traces and lowers the
  kernel once a shape (gate beside up, down) and every sparse layer calls
  it (PERF.md, Findings, PR 45).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: bytes of one weight tile in fast memory (two are in flight)
WEIGHT_TILE_BYTES = 4 << 20
#: what the kernel may hold in fast memory: two weight tiles, the
#: accumulator, two row tiles and two result tiles
VMEM_LIMIT_BYTES = 64 << 20


def row_tile(M: int) -> int:
    """Rows a tile, from the call's rows: an eighth of them, within a
    quarter and twice of what the matrix unit takes at once. A decode
    bucket's landed rows (all at the front) then sit in one or two tiles
    and each hit expert is read once; a slab's groups of tens to hundreds
    of rows cross few tile ends. A call of fewer rows than the least tile
    (a small batch bucket) is one tile."""
    return min(256, max(32, _pow2(-(-M // 8))), M)


def tiling(M: int, Kd: int, Nd: int, dtype) -> tuple[int, int, int]:
    """``(tm, tk, tn)`` of a call's shape. A weight tile is the expert's
    whole matrix where :data:`WEIGHT_TILE_BYTES` hold it (then a group that
    crosses a row tile's end finds its expert still in fast memory: the
    pipeline copies nothing whose index has not moved); else whole rows of
    it, as many as fit and divide ``Kd``; 128-column panels of those where
    a row of 128 is already too long."""
    item = jnp.dtype(dtype).itemsize
    tn = Nd
    if Nd * 128 * item > WEIGHT_TILE_BYTES:
        tn = WEIGHT_TILE_BYTES // (128 * item) // 128 * 128
    tk = Kd
    if Kd % 128 == 0:
        fit = max(128, WEIGHT_TILE_BYTES // (tn * item))
        tk = max(t for t in range(128, Kd + 1, 128)
                 if Kd % t == 0 and t <= fit)
    return row_tile(M), tk, tn


def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def visits(sizes, M: int, tm: int):
    """The visits of a call, from its groups' ``sizes`` [E]: ``(expert [V],
    row tile [V], offsets [E + 1], count)`` with ``V`` the most a call of
    this shape can make (what lies past ``count`` is never visited)."""
    E = sizes.shape[0]
    ends = jnp.cumsum(sizes.astype(jnp.int32))
    first = (ends - sizes) // tm
    mine = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(mine)
    v = jnp.arange(-(-M // tm) + E - 1, dtype=jnp.int32)[:, None]
    # whose a visit is, one-hot: sums where gathers would be
    whose = (v >= (upto - mine)[None, :]) & (v < upto[None, :])
    expert = jnp.where(whose, jnp.arange(E, dtype=jnp.int32)[None, :],
                       0).sum(axis=1)
    tile = v[:, 0] + jnp.where(whose, (first - (upto - mine))[None, :],
                               0).sum(axis=1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return expert, tile, offsets, upto[-1]


def reads(sizes, tm: int) -> int:
    """Whole experts' worth of weights calls with these ``sizes`` ([E], or
    a call a row of [calls, E]; on the host) fetch: their visits."""
    ends = sizes.cumsum(axis=-1)
    tiles = (ends - 1) // tm - (ends - sizes) // tm + 1
    return int(tiles[sizes > 0].sum())


def _kernel(expert_ref, tile_ref, offsets_ref, x_ref, w_ref, o_ref, acc, *,
            tm: int):
    v, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jnp.dot(x_ref[...], w_ref[...],
                        preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        e = expert_ref[v]
        row = tile_ref[v] * tm + lax.broadcasted_iota(
            jnp.int32, o_ref.shape, 0)
        mine = (row >= offsets_ref[e]) & (row < offsets_ref[e + 1])
        o_ref[...] = jnp.where(mine, acc[...].astype(o_ref.dtype),
                               o_ref[...])


@functools.partial(jax.jit, static_argnames=(
    "preferred_element_type", "tiles", "interpret"))
def grouped_dot(rows, weights, sizes, preferred_element_type=None, *,
                tiles=None, interpret=False):
    """``lax.ragged_dot(rows, weights, sizes, preferred_element_type=...)``
    for rows within the groups; the rest is left as it lay. ``tiles``
    puts ``(tm, tk, tn)`` in the rule's place (a test's small shapes, a
    measurement's sweep)."""
    M, Kd = rows.shape
    E, _, Nd = weights.shape
    assert weights.shape[1] == Kd and sizes.shape == (E,), \
        (rows.shape, weights.shape, sizes.shape)
    out = jnp.dtype(preferred_element_type or rows.dtype)
    tm, tk, tn = tiles or tiling(M, Kd, Nd, rows.dtype)
    assert Kd % tk == 0, (Kd, tk)
    expert, tile, offsets, count = visits(sizes, M, tm)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(Nd, tn), count, Kd // tk),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n, v, k, e, t, o: (t[v], k)),
                pl.BlockSpec((None, tk, tn),
                             lambda n, v, k, e, t, o: (e[v], k, n)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda n, v, k, e, t, o: (t[v], n)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((M, Nd), out),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="moe_grouped",
        interpret=interpret,
    )(expert, tile, offsets, rows, weights)
