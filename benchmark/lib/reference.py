"""The plain reference: a forward pass in float32, and its comparison.

The layers are the family's (:mod:`families`: ``logits`` of the file named
by the configuration's ``model_type``); here are the pieces every family
builds them from and the comparison ``correct`` makes. Straightforward
``jax.numpy`` following the published equations, one sequence at a time,
no cache, no batching, no kernel, matmuls at ``highest`` precision (on a
TPU a float32 matmul otherwise runs in bfloat16 passes). It imports
nothing of the program under test and takes nothing the program made: the
weights come again from the seed through :class:`checkpoint.Checkpoint`,
one layer at a time, so that a model whose float32 copy would not fit runs
in the memory the program has freed.

``mode`` selects the precision of the linear layers:

``float32``   the reference.
``int8``      the control of "How ``correct`` is decided": the nearest
              precision below the configuration's bfloat16. Weights are
              rounded to int8 per output channel and activations per token
              (symmetric, the usual W8A8 scheme); products of int8 values
              are exact in float32, so this equals an int8 matmul with an
              int32 accumulator.
``bfloat16``  what the engine does (inputs and result rounded to
              bfloat16); the CPU tests use it as the sound program.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import families

HIGHEST = jax.lax.Precision.HIGHEST
PAD = 256          # sequences are padded to a multiple: few shapes compile
ROWS = 64          # and so are the positions whose logits are wanted


def int8(a, axis):
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(a / scale) * scale


def linear(x, w, mode: str):
    """``x [T, in] @ w[out, in].T`` in the precision ``mode`` names."""
    w = w.astype(jnp.float32)
    if mode == "int8":
        x, w = int8(x, 1), int8(w, 1)
    elif mode == "bfloat16":
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif mode != "float32":
        raise ValueError(f"unknown precision {mode!r}")
    y = jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                            precision=HIGHEST,
                            preferred_element_type=jnp.float32)
    if mode == "bfloat16":
        y = y.astype(jnp.bfloat16).astype(jnp.float32)
    return y


def rms_norm(x, w, eps: float):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def rope(x, theta: float):
    """``x [T, H, hd]`` at positions 0..T-1, Hugging Face's rotate-half."""
    T, _H, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


@partial(jax.jit, static_argnames=("eps", "mode"))
def _head(x, norm, head, *, eps: float, mode: str):
    return linear(rms_norm(x, norm, eps), head, mode)


def logits(ckpt, sequences: list[list[int]], wanted: list[range],
           mode: str = "float32") -> list[jax.Array]:
    """Logits of each ``sequences[i]`` at the positions ``wanted[i]``
    (position p's logits predict token p + 1), as ``[n, V]`` arrays whose
    first ``len(wanted[i])`` rows are those positions; ``n`` is rounded up
    to a multiple of ``ROWS`` and the rows past them are padding. The
    forward pass is the one of the checkpoint's family."""
    return families.of(ckpt.config).logits(ckpt, sequences, wanted, mode)


def embed(ckpt, name: str, sequences: list[list[int]]) -> list[jax.Array]:
    """Rows of the embedding tensor ``name`` for each sequence, float32,
    padded with zero rows to a multiple of ``PAD`` positions."""
    table = ckpt.tensor(name)
    xs = []
    for seq in sequences:
        rows = np.zeros((-(-len(seq) // PAD) * PAD, table.shape[1]),
                        np.float32)
        rows[:len(seq)] = table[np.asarray(seq)].astype(np.float32)
        xs.append(jnp.asarray(rows))
    return xs


def layers_ahead(load, n_layers: int):
    """Yield ``load(0)``, ``load(1)``, ... on the device, each made from
    the seed on another thread while the one before it is in use. A
    caller that drops its layer (``del``) before asking for the next has
    one layer on the device at a time."""
    with ThreadPoolExecutor(1) as ahead:
        nxt = ahead.submit(load, 0)
        for i in range(n_layers):
            w = jax.device_put(nxt.result())
            if i + 1 < n_layers:
                nxt = ahead.submit(load, i + 1)
            yield w
            del w


def head_rows(xs: list[jax.Array], wanted: list[range], norm, head, *,
              eps: float, mode: str) -> list[jax.Array]:
    """The final norm and the output head at the ``wanted`` positions of
    each sequence, the rows padded to a multiple of ``ROWS``."""
    out = []
    for x, want in zip(xs, wanted):
        rows = np.asarray(want)
        padded = np.concatenate(
            [rows, np.full(-len(rows) % ROWS, rows[-1], rows.dtype)])
        out.append(_head(x[padded], norm, head, eps=eps, mode=mode))
    return out


def gaps_below_best(ref: jax.Array, tokens) -> np.ndarray:
    """For each of the first ``len(tokens)`` rows of the reference's
    logits, how far the given token's logit lies below the reference's
    best (0 where it is the best)."""
    tokens = np.asarray(tokens, np.int32)
    padded = np.zeros(ref.shape[0], np.int32)
    padded[:len(tokens)] = tokens
    at = jnp.take_along_axis(ref, jnp.asarray(padded)[:, None], axis=1)[:, 0]
    return np.asarray(jnp.max(ref, axis=1) - at, np.float64)[:len(tokens)]
