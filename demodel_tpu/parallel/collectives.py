"""ICI-leg collectives for the delivery layer.

The reference has no device communication at all (SURVEY.md §2.3); its
"distributed" capability is HTTP blob exchange. In the rebuild, the DCN leg
is the peer cache (:mod:`demodel_tpu.parallel.peer`) and this module is the
ICI leg: once each host has landed its addressable shards, layout changes
(replicate a tensor, switch tp axis, gather for export) are expressed as
XLA resharding/collectives over the mesh — ``psum``/``all_gather``/
``ppermute`` inserted by the compiler or written explicitly via shard_map,
riding ICI rather than host networking.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def shard_map_nocheck(f, mesh: Mesh, in_specs, out_specs):
    """``shard_map`` with the static replication checker disabled.
    Collective outputs here ARE identical across the mapped axis, but the
    checker can't statically infer that."""
    return shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


def redistribute(arr: jax.Array, sharding: NamedSharding) -> jax.Array:
    """Change an array's layout on-device.

    A jitted identity with an output-sharding constraint: XLA emits the
    minimal collective (all-gather to replicate, all-to-all for an axis
    switch, slice for a split) over ICI — the idiomatic JAX way to move
    shards, rather than staging through host memory.
    """
    return jax.jit(lambda x: x, out_shardings=sharding)(arr)


def replicate(arr: jax.Array, mesh: Mesh) -> jax.Array:
    """All-gather a sharded array so every device holds the full tensor."""
    return redistribute(arr, NamedSharding(mesh, P()))


def allgather_axis(arr: jax.Array, mesh: Mesh, axis: str = "tp") -> jax.Array:
    """Explicit all-gather over one mesh axis via shard_map — the
    hand-written equivalent of :func:`replicate` for a single axis, used
    where the surrounding program is already shard_mapped."""
    ndim = arr.ndim

    def gather(x):
        return jax.lax.all_gather(x, axis, axis=0, tiled=True)

    in_spec = P(axis, *([None] * (ndim - 1)))
    out_spec = P(*([None] * ndim))
    return shard_map_nocheck(gather, mesh, (in_spec,), out_spec)(arr)


def psum_across(arr: jax.Array, mesh: Mesh, axis: str = "dp") -> jax.Array:
    """Sum per-shard blocks across a mesh axis (delivery checksum/
    verification aggregation across hosts).

    ``arr`` is treated as sharded along dim 0 over ``axis`` (shape[0] must
    divide by the axis size); the result is the elementwise sum of the
    per-device blocks, replicated everywhere — shape ``(shape[0]/n, ...)``.
    """
    n = mesh.shape[axis]
    if arr.ndim == 0 or arr.shape[0] % n:
        raise ValueError(
            f"psum_across: leading dim {arr.shape and arr.shape[0]} "
            f"not divisible by mesh axis {axis!r} size {n}"
        )

    def s(x):
        return jax.lax.psum(x, axis)

    in_spec = P(axis, *([None] * (arr.ndim - 1)))
    out_spec = P(*([None] * arr.ndim))
    return shard_map_nocheck(s, mesh, (in_spec,), out_spec)(arr)


@functools.partial(jax.jit, static_argnames=("chunk_elems",))
def _fingerprint(x: jax.Array, chunk_elems: int = 1 << 20) -> jax.Array:
    """Cheap on-device content fingerprint (float sums are layout-invariant
    up to reordering; used to cross-check shard placement across hosts
    without pulling tensors back to host)."""
    f = x.astype(jnp.float32).reshape(-1)
    return jnp.stack([f.sum(), jnp.abs(f).sum(), (f * f).sum()])


def fingerprint(arr: jax.Array) -> jax.Array:
    return _fingerprint(arr)
