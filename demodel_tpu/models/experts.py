"""The held experts' part of a routed layer: what every expert-parallel
family of this stack shares.

A family scores and chooses in its own way (sigmoid and a selection bias,
softmax) and adds what every chip computes alike (a shared expert) itself;
what is the same is told here. The layer is one chip's share of an
expert-parallel replica: the router is as wide as the whole layer, the
checkpoint holds ``E`` of its experts, ``first .. first + E - 1``, and the
layer computes their part of the result for the assignments that fall on
them: no capacity, no dropped token. What the absent experts would add is
left out and the partial result goes on (there is no exchange on one chip,
and nothing stands in for one). Under a mesh with an ``ep`` axis the held
experts are split once more over that axis and the parts are summed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from demodel_tpu.models.hf_loader import Weights, setter, zeros
from demodel_tpu.ops import grouped
from demodel_tpu.utils.metrics import HUB, labeled

HUB.inc(labeled("gen_moe_assignments_total", held="true"), 0)
HUB.inc(labeled("gen_moe_assignments_total", held="false"), 0)
HUB.inc("gen_moe_experts_hit_total", 0)
HUB.inc("gen_moe_rows_computed_total", 0)
HUB.inc("gen_moe_expert_reads_total", 0)

#: assignment rows one pass of the grouped products holds: a layer with no
#: more than this computes them all at once, a longer prompt the landed
#: ones in slabs of this many
SLAB = 4096


def ep_size(mesh: Mesh | None) -> int:
    return int(mesh.shape.get("ep", 1)) if mesh is not None else 1


def _grouped(rows, stacked, sizes, preferred_element_type=None):
    """``rows`` [M, Kd], sorted by group, each group's through its own
    matrix of ``stacked`` [E, Kd, Nd]. In a program lowered for a TPU the
    kernel that streams each hit expert once
    (:mod:`demodel_tpu.ops.grouped`); everywhere else ``lax.ragged_dot``,
    the portable form and the kernel's oracle. Which one a program holds is
    the platform's it is lowered for, nothing else's."""
    return lax.platform_dependent(
        rows, stacked, sizes,
        tpu=lambda *a: grouped.grouped_dot(*a, preferred_element_type),
        default=lambda *a: lax.ragged_dot(
            *a, preferred_element_type=preferred_element_type))


def _slab(x, held, weights, idx, sizes, gate_up, down, valid=None):
    """Assignments ``idx`` (positions in the flattened ``[N * K]``, sorted
    by expert, ``sizes`` [E] of them to each held expert) through their
    experts and weighted: ``[len(idx), D]`` float32. Rows past the groups'
    end and rows not ``valid`` come out zero."""
    K, F = held.shape[1], down.shape[1]
    rows = x[idx // K]
    with jax.named_scope("moe.experts"):
        h = _grouped(rows, gate_up, sizes)
        h = jax.nn.silu(h[:, :F]) * h[:, F:]
        y = _grouped(h, down, sizes, jnp.float32)
    # rows past the groups' end belong to no held expert: whatever the
    # grouped product left there is dropped, not scaled
    w = jnp.where(held, weights, 0.0).reshape(-1)[idx]
    if valid is not None:
        w = jnp.where(valid, w, 0.0)
    return jnp.where(w[:, None] != 0, y * w[:, None], 0.0)


def held_part(x, live, chosen, weights, gate_up, down, first):
    """The held experts' part of the routed sum. ``x`` [N, D]; ``chosen``
    [N, K] expert ids over the whole router and ``weights`` [N, K] theirs;
    ``gate_up`` [E, D, 2F] and ``down`` [E, F, D] the held experts, which
    are ``first .. first + E - 1``; rows not ``live`` (the pad rows of a
    batch bucket) choose nothing. Returns ``(y [N, D] float32, tokens
    [E])``. Every assignment that falls on a held expert is computed: the
    assignments are sorted by expert, which puts the ``tokens.sum()`` that
    landed first, and each projection is one grouped product over the
    groups' actual sizes.

    Up to :data:`SLAB` assignments (a decode step, a short prompt) that is
    one pass over all ``N * K`` rows and an unsort. A longer prompt works
    on the landed prefix alone, a slab of ``SLAB`` rows at a time under a
    loop whose trip count is the data's (``ceil(landed / SLAB)``: all
    ``N * K`` rows when every assignment lands, none when none does), and
    each slab's rows are added to their tokens' in float32."""
    N, K = chosen.shape
    E = down.shape[0]
    local = chosen - first
    held = (local >= 0) & (local < E) & live[:, None]
    group = jnp.where(held, local, E).reshape(N * K)    # E: not computed
    order = jnp.argsort(group, stable=True)
    tokens = (group[:, None] == jnp.arange(E)[None, :]).sum(
        axis=0, dtype=jnp.int32)
    if N * K <= SLAB:
        y = _slab(x, held, weights, order, tokens, gate_up, down)
        back = jnp.argsort(order)                       # the unsort
        return y[back].reshape(N, K, -1).sum(axis=1), tokens
    ends = jnp.cumsum(tokens)
    landed = ends[-1]
    order = jnp.pad(order, (0, -(N * K) % SLAB))

    def slab(carry):
        i, y = carry
        lo = i * SLAB
        idx = lax.dynamic_slice(order, (lo,), (SLAB,))
        sizes = (jnp.clip(ends, lo, lo + SLAB)
                 - jnp.clip(ends - tokens, lo, lo + SLAB))
        part = _slab(x, held, weights, idx, sizes, gate_up, down,
                     lo + jnp.arange(SLAB) < landed)
        return i + 1, y.at[idx // K].add(part)

    _, y = lax.while_loop(
        lambda carry: carry[0] * SLAB < landed, slab,
        (jnp.int32(0), jnp.zeros((N, x.shape[1]), jnp.float32)))
    return y, tokens


def routed(x, live, chosen, weights, gate_up, down, first: int,
           mesh: Mesh | None):
    """:func:`held_part` on one chip, or split over the mesh's ``ep`` axis
    (when the held experts divide) with the parts summed: ``(y [N, D]
    float32, tokens per held expert [E])``."""
    n, E = ep_size(mesh), down.shape[0]
    if n == 1 or E % n:
        return held_part(x, live, chosen, weights, gate_up, down, first)
    each = E // n

    def part(x, live, chosen, weights, gate_up, down):
        y, tokens = held_part(x, live, chosen, weights, gate_up, down,
                              first + lax.axis_index("ep") * each)
        return lax.psum(y, "ep"), lax.all_gather(tokens, "ep", tiled=True)

    return jax.shard_map(
        part, mesh=mesh, in_specs=(P(),) * 4 + (P("ep"),) * 2,
        out_specs=(P(), P()), axis_names={"ep"}, check_vma=False)(
        x, live, chosen, weights, gate_up, down)


def held_shardings(tree: dict, held: int, mesh: Mesh) -> dict:
    """``tree`` (a params tree of replicated shardings) with the stacked
    expert tensors of every layer split over ``ep`` when they divide."""
    n = ep_size(mesh)
    if n > 1 and held % n == 0:
        for layer in tree["layers"]:
            for name in ("experts_gate_up", "experts_down"):
                if name in layer:
                    layer[name] = NamedSharding(mesh, P("ep"))
    return tree


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def expert_reads(expert_tokens, rows: int) -> int:
    """Whole experts' worth of weights the kernel's tiling fetches for a
    step's ``expert_tokens`` (on the host), a layer's call ``rows``
    assignment rows long: :func:`held_part`'s calls of
    :func:`grouped.grouped_dot`, slab by slab, under the same rule
    (:func:`grouped.row_tile`). A group that crosses a row tile's end
    counts its expert twice."""
    tm = grouped.row_tile(min(rows, SLAB))
    ends = expert_tokens.cumsum(axis=1)
    starts = ends - expert_tokens
    return sum(grouped.reads(ends.clip(lo, lo + SLAB)
                             - starts.clip(lo, lo + SLAB), tm)
               for lo in range(0, int(ends[:, -1].max()), SLAB))


def observe(expert_tokens, assignments: int, free: int = 0,
            platform: str = "cpu", call_rows: int = 0,
            grouped: bool = True) -> dict:
    """A step's ``expert_tokens`` ([expert layers, held experts], on the
    host) and the assignments its tokens made in all → the step span's
    attributes; the counters are counted here. ``free`` of the assignments
    fell on an expert that is nobody's to hold (an identity expert): they
    are neither held nor absent, and the family counts them.
    ``expert_rows`` is what
    :func:`held_part`'s grouped products ran over, layer by layer, as one
    chip runs it (the pad rows of a batch bucket not counted): over
    ``expert_tokens`` it says how much of the work landed.
    ``expert_reads`` is how many experts' weights those products fetched
    where the step's program holds the kernel (one lowered for a TPU,
    ``platform``; a layer's call ``call_rows`` assignment rows long, pad
    rows and all, or as many as a layer's share of ``assignments``): over
    ``experts_hit``, 1.0 is each hit expert once; 0 is the compiler's own
    path, or a program that holds no grouped product at all (``grouped``
    false: its family computes its few rows through every expert)."""
    landed = int(expert_tokens.sum())
    hit = int((expert_tokens > 0).sum())
    rows = assignments                  # up to a slab a layer: all of them
    if assignments > SLAB * len(expert_tokens):
        rows = int((-(-expert_tokens.sum(axis=1) // SLAB)).sum()) * SLAB
    reads = expert_reads(
        expert_tokens, call_rows or assignments // len(expert_tokens)) \
        if platform == "tpu" and grouped else 0
    HUB.inc(labeled("gen_moe_assignments_total", held="true"), landed)
    HUB.inc(labeled("gen_moe_assignments_total", held="false"),
            assignments - free - landed)
    HUB.inc("gen_moe_experts_hit_total", hit)
    HUB.inc("gen_moe_rows_computed_total", rows)
    HUB.inc("gen_moe_expert_reads_total", reads)
    return {"expert_tokens": landed, "experts_hit": hit,
            "expert_rows": rows, "expert_reads": reads}


def stack_experts(w: Weights, pre: str, projs, cfg, sharding):
    """The held experts' ``<pre>mlp.experts.<e>.<p>_proj.weight`` (``[out,
    in]`` each, under their index in the whole layer) for the projections
    ``projs`` → one ``[E, in, len(projs) * out]``, a projection's runs side
    by side. Each matrix is popped, set into the stack in place and freed,
    so boot holds the stack and one matrix, not the experts twice."""
    D, F = cfg.hidden_size, cfg.moe_intermediate_size
    shape = (cfg.num_experts, *((F, D) if projs == ("down",)
                                else (D, len(projs) * F)))
    stack = zeros(shape, cfg.dtype, sharding)()
    put = setter(sharding)
    first = cfg.ep_rank * cfg.num_experts
    for j in range(cfg.num_experts):
        for i, p in enumerate(projs):
            stack = put(stack, w.get(
                f"{pre}mlp.experts.{first + j}.{p}_proj.weight"), j, i * F)
    return stack
