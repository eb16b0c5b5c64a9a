"""The held-experts layer on its own (``demodel_tpu/models/experts.py``):
``held_part`` against a plain float32 loop over the assignments, at shapes
on both sides of :data:`experts.SLAB` (cut to 64 here so that the slabs'
loop runs at sizes the CPU is quick with), and what ``observe`` counts.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from demodel_tpu.models import experts
from demodel_tpu.ops import grouped
from demodel_tpu.utils.metrics import HUB, labeled

SLAB = 64
D, F = 16, 8


def plain(x, live, chosen, weights, gate_up, down, first):
    """One assignment after another, float32."""
    x, weights, gate_up, down = (np.asarray(a, np.float32)
                                 for a in (x, weights, gate_up, down))
    live, chosen = np.asarray(live), np.asarray(chosen)
    E = down.shape[0]
    y = np.zeros((x.shape[0], down.shape[2]), np.float32)
    tokens = np.zeros(E, np.int32)
    for n, k in np.ndindex(*chosen.shape):
        e = chosen[n, k] - first
        if live[n] and 0 <= e < E:
            h = x[n] @ gate_up[e]
            h = h[:F] / (1 + np.exp(-h[:F])) * h[F:]
            y[n] += weights[n, k] * (h @ down[e])
            tokens[e] += 1
    return y, tokens


def _inputs(N, K, E, width, seed=0):
    """``N`` tokens that choose ``K`` of ``width`` experts, ``E`` of them
    held: ``(x, live, chosen, weights, gate_up, down)``."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((N, D), np.float32)
    scores = rng.standard_normal((N, width), np.float32)
    chosen = np.argsort(-scores, axis=1)[:, :K].astype(np.int32)
    weights = rng.uniform(0.05, 1.0, (N, K)).astype(np.float32)
    gate_up = rng.standard_normal((E, D, 2 * F), np.float32) * D ** -0.5
    down = rng.standard_normal((E, F, D), np.float32) * F ** -0.5
    return x, np.ones(N, bool), chosen, weights, gate_up, down


def _under():           # N * K = 48: one slab, the straight-line code
    return *_inputs(6, 8, 8, 32), 0


def _equal():           # N * K = 64 = SLAB: still one slab
    return *_inputs(8, 8, 8, 32), 0


def _over_k8():         # 320 assignments, ~80 land: two slabs
    return *_inputs(40, 8, 8, 32), 0


def _over_k10():        # 400 assignments, no multiple of the slab
    return *_inputs(40, 10, 8, 32), 0


def _none_land():       # the held experts are past the router's choices
    return *_inputs(40, 10, 8, 32), 32


def _all_land():        # the router is as wide as what is held: 400 rows,
    return *_inputs(40, 10, 16, 16), 0     # seven slabs, more than any bound


def _one_expert():      # every token's only held choice is expert 2
    x, live, chosen, weights, gate_up, down = _inputs(100, 8, 8, 32)
    chosen = np.where(chosen < 8, chosen + 8, chosen)
    chosen[:, 3] = 2
    return x, live, chosen, weights, gate_up, down, 0


def _straddle():        # groups of 40 and 40: the second crosses row 64
    x, live, chosen, weights, gate_up, down = _inputs(40, 8, 8, 32)
    chosen = np.where(chosen < 8, chosen + 8, chosen)
    chosen[:, 0], chosen[:, 5] = 0, 1
    return x, live, chosen, weights, gate_up, down, 0


def _pad_rows():        # a third of the rows are a bucket's pad rows
    x, live, chosen, weights, gate_up, down = _inputs(48, 10, 8, 16)
    live[::3] = False
    return x, live, chosen, weights, gate_up, down, 0


def _first():           # the held experts are 8 .. 15 of 32
    return *_inputs(40, 10, 8, 32), 8


CASES = [_under, _equal, _over_k8, _over_k10, _none_land, _all_land,
         _one_expert, _straddle, _pad_rows, _first]


@pytest.fixture
def slab(monkeypatch):
    monkeypatch.setattr(experts, "SLAB", SLAB)


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__[1:])
def test_held_part_is_the_plain_loop(slab, case):
    *args, first = case()
    want, want_tokens = plain(*args, first)
    got, tokens = jax.jit(experts.held_part, static_argnums=6)(*args, first)
    assert got.dtype == jnp.float32
    np.testing.assert_array_equal(tokens, want_tokens)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    if case is _none_land:
        assert not tokens.any() and not np.asarray(got).any()
    if case is _all_land:           # no assignment is dropped
        assert tokens.sum() == args[2].size > 6 * SLAB
    if case is _one_expert:
        assert tokens[2] == tokens.sum() == 100 > SLAB
    if case is _straddle:
        assert tokens[:2].tolist() == [40, 40]


@pytest.mark.parametrize("case", [_under, _over_k10, _all_land],
                         ids=lambda c: c.__name__[1:])
def test_split_over_ep_is_one_chip(slab, case):
    from demodel_tpu.parallel.mesh import make_mesh

    *args, first = case()
    mesh = make_mesh(2, ep=2, tp=1)
    want, want_tokens = jax.jit(experts.held_part, static_argnums=6)(
        *args, first)
    got, tokens = jax.jit(
        lambda *a: experts.routed(*a, first, mesh))(*args)
    np.testing.assert_array_equal(tokens, want_tokens)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


@pytest.mark.parametrize("case", [_under, _equal],
                         ids=lambda c: c.__name__[1:])
def test_one_slab_is_straight_line(slab, case):
    """Up to a slab of assignments the program has no loop and no branch
    on its data (a decode step's and a short prompt's are straight-line
    code): what ``case`` it holds is the platform's choice of each of the
    two grouped products (``lax.platform_dependent``: an index that is a
    constant in a program lowered for one platform, then the one branch
    that platform has)."""
    *args, first = case()
    text = jax.jit(experts.held_part, static_argnums=6).lower(
        *args, first).as_text()
    assert "while" not in text and "stablehlo.if" not in text
    assert text.count("stablehlo.case") == 2 * 2
    assert len(re.findall(r'"stablehlo.case"\(%c', text)) == 2
    *args, first = _over_k10()
    assert "stablehlo.while" in jax.jit(
        experts.held_part, static_argnums=6).lower(*args, first).as_text()


def test_observe_counts_the_rows_computed():
    """``expert_rows``: all of a layer's assignments up to a slab of them,
    else the landed ones rounded up to whole slabs."""
    rows = labeled("gen_moe_assignments_total", held="true")
    before = HUB.snapshot()
    step = np.array([[3, 0, 2], [0, 0, 0]], np.int32)   # 16 rows x K 10
    assert experts.observe(step, 2 * 160) == {
        "expert_tokens": 5, "experts_hit": 2, "expert_rows": 320,
        "expert_reads": 0}
    prompt = np.array([[4000, 96, 1], [0, 0, 0], [9000, 0, 600]], np.int32)
    attrs = experts.observe(prompt, 3 * 38400)
    assert attrs["expert_rows"] == (2 + 0 + 3) * experts.SLAB
    assert attrs["expert_tokens"] == 13697
    after = HUB.snapshot()
    assert after["gen_moe_rows_computed_total"] \
        - before.get("gen_moe_rows_computed_total", 0) == 320 + 5 * 4096
    assert after[rows] - before.get(rows, 0) == 5 + 13697


def _tiles_read(sizes, rows: int, tm: int) -> int:
    """Row by row: the distinct experts under each row tile of each slab
    of a layer's sorted assignment rows."""
    whose = np.repeat(np.arange(len(sizes)), sizes)
    total = 0
    for lo in range(0, len(whose), min(rows, experts.SLAB)):
        slab = whose[lo:lo + min(rows, experts.SLAB)]
        total += sum(len(set(slab[t:t + tm])) for t in range(0, len(slab), tm))
    return total


@pytest.mark.parametrize("tokens,rows", [
    ([[3, 0, 2], [0, 0, 0]], 160),              # a step: a tile holds all
    ([[200, 0, 130], [1, 1, 1]], 512),          # groups longer than a tile
    ([[4000, 96, 1], [0, 0, 0], [9000, 0, 600]], 38400),    # slabs
], ids=["a-step", "long-groups", "a-prompt-in-slabs"])
def test_observe_counts_the_experts_the_kernel_reads(tokens, rows):
    """``expert_reads``: on a TPU the visits of the kernel's tiling, a layer
    and a slab at a time (an expert whose group crosses a row tile's end is
    read once a tile); 0 where the program holds ``lax.ragged_dot``."""
    tokens = np.array(tokens, np.int32)
    name = "gen_moe_expert_reads_total"
    before = HUB.snapshot()[name]
    assert experts.observe(tokens, len(tokens) * rows)["expert_reads"] == 0
    assert HUB.snapshot()[name] == before
    attrs = experts.observe(tokens, len(tokens) * rows, platform="tpu")
    tm = grouped.row_tile(min(rows, experts.SLAB))
    want = sum(_tiles_read(sizes, rows, tm) for sizes in tokens)
    assert attrs["expert_reads"] == want >= attrs["experts_hit"]
    if rows == 160:
        assert want == attrs["experts_hit"] == 2
    assert HUB.snapshot()[name] - before == want
