"""The grouped product's Pallas TPU kernel (``demodel_tpu/ops/grouped.py``)
on the CPU, under Pallas' TPU interpreter: the rows within the groups
against ``jax.lax.ragged_dot``, which stays the path of every other platform
and is the kernel's oracle; ``experts.held_part`` with the kernel in the
place of ``lax.platform_dependent``'s choice against ``tests/test_experts``'
plain loop; the tiling rule at the published shapes and what ``reads``
counts of it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.experimental.pallas import tpu as pltpu

from demodel_tpu.models import experts
from demodel_tpu.ops import grouped
from tests import test_experts as plainly

#: name → (M, E, Kd, Nd, sizes, tiles): ``tiles`` None is the rule's
SHAPES = {
    # a decode call: 160 assignment rows, 9 landed on 3 of 8 experts
    "decode-most-rows-past-the-end": (
        160, 8, 128, 256, [0, 4, 0, 0, 3, 0, 2, 0], None),
    "an-empty-group-between-full-ones": (
        96, 3, 128, 128, [32, 0, 32], (32, 128, 128)),
    # 20 + 30 rows: the second group crosses row 32
    "a-group-straddles-a-row-tile": (
        64, 4, 128, 128, [20, 30, 0, 5], (32, 128, 128)),
    "every-row-in-one-group": (64, 4, 128, 128, [0, 0, 64, 0], None),
    "no-row-landed": (64, 4, 128, 128, [0, 0, 0, 0], None),
    "sizes-sum-to-the-rows": (
        128, 5, 256, 128, [17, 40, 0, 41, 30], (32, 128, 128)),
    # Kd two tiles of 128, Nd one and a part of a tile; rows 2.2 tiles
    "tiles-that-divide-and-do-not": (
        72, 3, 256, 200, [30, 12, 25], (32, 128, 128)),
    # nothing a multiple of anything: the rule gives whole dimensions
    "widths-under-a-tile": (48, 8, 16, 24, [1, 2, 3, 4, 5, 6, 7, 8], None),
    # a small batch bucket: the call is one tile of its own rows
    "fewer-rows-than-the-least-tile": (12, 4, 128, 128, [2, 0, 1, 3], None),
}


def _operands(M, E, Kd, Nd, dtype, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((M, Kd), np.float32)
    w = rng.standard_normal((E, Kd, Nd), np.float32) * Kd ** -0.5
    return jnp.asarray(x, dtype), jnp.asarray(w, dtype)


@pytest.mark.parametrize("result", [None, "float32"],
                         ids=["rows-dtype", "float32-out"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", SHAPES)
def test_kernel_is_ragged_dot_within_the_groups(shape, dtype, result):
    M, E, Kd, Nd, sizes, tiles = SHAPES[shape]
    x, w = _operands(M, E, Kd, Nd, dtype)
    s = jnp.asarray(sizes, jnp.int32)
    want = lax.ragged_dot(x, w, s, preferred_element_type=result)
    got = grouped.grouped_dot(x, w, s, result, tiles=tiles,
                              interpret=pltpu.InterpretParams())
    assert got.shape == want.shape == (M, Nd) and got.dtype == want.dtype
    n = sum(sizes)
    tight = dtype == "float32"
    np.testing.assert_allclose(
        np.asarray(got[:n], np.float32), np.asarray(want[:n], np.float32),
        rtol=1e-5 if tight else 2e-2, atol=1e-5 if tight else 2e-2)


@pytest.fixture
def kernel(monkeypatch):
    """``experts`` as a program lowered for a TPU holds it: the kernel
    (interpreted) where ``lax.platform_dependent`` chooses, and what the
    other branch would have given beside it."""
    seen = []

    def both(*args, tpu, default):
        seen.append((tpu(*args), default(*args), args[2]))
        return seen[-1][0]

    monkeypatch.setattr(experts.lax, "platform_dependent", both)
    monkeypatch.setattr(grouped, "grouped_dot", functools.partial(
        grouped.grouped_dot, interpret=pltpu.InterpretParams()))
    monkeypatch.setattr(experts, "SLAB", plainly.SLAB)
    return seen


@pytest.mark.parametrize("case", plainly.CASES, ids=lambda c: c.__name__[1:])
def test_held_part_with_the_kernel_is_the_plain_loop(kernel, case):
    *args, first = case()
    want, want_tokens = plainly.plain(*args, first)
    got, tokens = experts.held_part(*map(jnp.asarray, args), first)
    assert got.dtype == jnp.float32
    np.testing.assert_array_equal(tokens, want_tokens)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    # straight-line code calls the two products once; both branches agree
    # on every row that has a group (the loop's calls are traced once)
    assert len(kernel) == 2
    if args[2].size <= plainly.SLAB:
        for mine, its, sizes in kernel:
            n = int(sizes.sum())
            np.testing.assert_allclose(mine[:n], its[:n], rtol=0, atol=2e-5)


#: the grouped products of the benchmark's four expert families, gate
#: beside up then down: (M, E, Kd, Nd) → the rule's (tm, tk, tn)
PUBLISHED = {
    "axk1-step": ((512, 12, 7168, 4096), (512, 12, 2048, 7168)),
    "kexaone-step": ((256, 16, 6144, 4096), (256, 16, 2048, 6144)),
    "longcat-step": ((768, 16, 6144, 4096), (768, 16, 2048, 6144)),
    "qwen3next-step": ((160, 128, 2048, 1024), (160, 128, 512, 2048)),
    "axk1-slab": ((4096, 12, 7168, 4096), (4096, 12, 2048, 7168)),
    "qwen3next-slab": ((4096, 128, 2048, 1024), (4096, 128, 512, 2048)),
}


@pytest.mark.parametrize("name", PUBLISHED)
def test_tiling_at_the_published_shapes(name):
    """Both products of a layer walk the same row tiles (one set of visits
    describes both), a weight tile is whole rows of the expert's matrix or
    128-column panels of them, divides ``Kd``, and two of them with the
    accumulator and the row and result tiles fit what the kernel asks of
    fast memory."""
    (tm, tk, tn), (tm2, tk2, tn2) = (
        grouped.tiling(M, Kd, Nd, jnp.bfloat16)
        for M, _E, Kd, Nd in PUBLISHED[name])
    assert tm == tm2 and tm % 16 == 0
    for (M, E, Kd, Nd), (tk, tn) in zip(PUBLISHED[name],
                                        ((tk, tn), (tk2, tn2))):
        assert Kd % tk == 0 and tk % 128 == 0
        assert tn == Nd or tn % 128 == 0
        assert tk * tn * 2 <= grouped.WEIGHT_TILE_BYTES
        held = 2 * tk * tn * 2 + tm * tn * 4 + 2 * tm * tk * 2 \
            + 2 * tm * tn * 4
        assert held < grouped.VMEM_LIMIT_BYTES // 2, held


def test_visits_follow_the_groups():
    """An expert with no row is in no visit; a group that crosses a tile's
    end is visited once a tile; ``reads`` is the visits' count."""
    sizes = np.array([20, 0, 30, 0, 5], np.int64)       # rows 0-19, 20-49,
    expert, tile, offsets, count = grouped.visits(      # 50-54 at tm 32
        jnp.asarray(sizes, jnp.int32), 64, 32)
    assert int(count) == grouped.reads(sizes, 32) == 4
    assert expert[:4].tolist() == [0, 2, 2, 4]
    assert tile[:4].tolist() == [0, 0, 1, 1]
    assert offsets.tolist() == [0, 20, 20, 50, 50, 55]
    assert expert.shape == tile.shape == (2 + 5 - 1,)
    assert grouped.reads(np.zeros(5, np.int64), 32) == 0
    assert grouped.reads(np.array([64, 64]), 32) == 4
    # each hit expert once where a tile holds every landed row
    assert grouped.reads(np.array([3, 0, 9, 1, 0, 7]), 32) == 4
