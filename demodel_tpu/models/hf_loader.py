"""HF-checkpoint → params-tree layout tools, which every family's
``load_params`` is written with. This module holds no family.

A loader consumes a flat ``{tensor_name: array}`` (a sink
:class:`Placement`'s arrays, or host numpy) holding a ``transformers``-layout
state dict and rebuilds its family's params pytree. torch ``nn.Linear``
stores ``[out, in]`` — those transpose on the way in; GPT-2's Conv1D already
stores ``[in, out]`` and loads verbatim. Optional name prefixes
("model.", "transformer.", "bert.") are stripped automatically.

A placed ``jax.Array`` never leaves the device: it enters the tree as it
is, or transposed where it lives. The loaders CONSUME the mapping — each
tensor is popped as it enters the tree — so a delivered weight is freed
as soon as its transposed copy exists and boot holds about one copy of
the model in HBM, not two. Every layout is one jitted program a target
sharding (``lru_cache``): the layers of a model share it, and the compile
cache finds it again.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PREFIXES = ("", "model.", "transformer.", "bert.")


@functools.lru_cache(maxsize=None)
def placer(transpose: bool, sharding):
    """One jitted (transpose +) reshard per target layout — the layers of
    a model share it, so it compiles once per distinct weight shape."""
    return jax.jit((lambda x: x.T) if transpose else (lambda x: x),
                   out_shardings=sharding)


def lay(arr, transpose: bool = False, sharding=None):
    """``arr`` as a tree leaf: under ``sharding`` (the model's own layout
    for this leaf) when given, else with the placement it arrived with."""
    if sharding is not None:
        return placer(transpose, sharding)(arr)
    if not isinstance(arr, jax.Array):
        arr = jnp.asarray(arr)  # host numpy (tests, tools)
    return arr.T if transpose else arr


class Weights:
    """The mapping a loader consumes: a tensor is popped as it is taken."""

    def __init__(self, weights: dict):
        self.w = weights

    def get(self, name: str, transpose: bool = False, sharding=None):
        for p in PREFIXES:
            if p + name in self.w:
                return lay(self.w.pop(p + name), transpose, sharding)
        raise KeyError(f"checkpoint has no tensor {name!r} "
                       f"(tried prefixes {PREFIXES})")

    def has(self, name: str) -> bool:
        return any(p + name in self.w for p in PREFIXES)


@functools.lru_cache(maxsize=None)
def setter(sharding):
    """Jitted, the stack donated: one expert's ``[out, in]`` matrix of one
    projection, transposed, into its place ``[e, :, at : at + out]`` of
    the stacked tensor."""
    def put(stack, w, e, at):
        return jax.lax.dynamic_update_slice(stack, w.T[None], (e, 0, at))

    return jax.jit(put, donate_argnums=0, out_shardings=sharding)


@functools.lru_cache(maxsize=None)
def zeros(shape, dtype, sharding):
    return jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=sharding)


@functools.lru_cache(maxsize=None)
def head_splitter(heads: int, first: int, by_head: bool, sharding):
    """Jitted: a ``[heads * (first + rest), in]`` matrix whose rows lie head
    by head, each head's ``first`` rows before its ``rest`` → the two
    parts, each ``[heads, in, width]`` (``by_head``) or ``[heads * width,
    in]`` (its rows, as they lay)."""
    def split(x):
        x = x.reshape(heads, -1, x.shape[1])
        parts = x[:, :first], x[:, first:]
        if by_head:
            return tuple(p.transpose(0, 2, 1) for p in parts)
        return tuple(p.reshape(-1, x.shape[2]) for p in parts)

    return jax.jit(split, out_shardings=(sharding, sharding))


def fold(weight, scale: float):
    """A norm's weight with a scale on the norm's output folded in, in
    float32: such a scale (``12 ** 0.5``) is no bfloat16 number, and a
    weight of ones would carry its rounding into every column."""
    return weight.astype(jnp.float32) * scale


@functools.lru_cache(maxsize=None)
def folder(scale: float, sharding):
    """Jitted :func:`fold` of one norm's weight."""
    return jax.jit(lambda w: fold(w, scale), out_shardings=sharding)


@functools.lru_cache(maxsize=None)
def regrouper(groups: int, widths: tuple[int, ...], sharding):
    """Jitted: a ``[out, in]`` matrix whose rows lie in ``groups`` runs,
    each holding ``widths`` rows of the parts side by side, → ``[in, out]``
    with each part's rows of all runs together, the parts in order."""
    def regroup(x):
        x = x.reshape(groups, sum(widths), x.shape[1])
        at, parts = 0, []
        for n in widths:
            parts.append(x[:, at:at + n].reshape(groups * n, -1))
            at += n
        return jnp.concatenate(parts).T

    return jax.jit(regroup, out_shardings=sharding)
