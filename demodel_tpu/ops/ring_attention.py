"""Ring attention: exact context-parallel attention over an ``sp`` mesh axis.

Long-context delivery-side parallelism (SURVEY.md §5 "Long-context /
sequence parallelism"): the sequence is sharded over ``sp``; K/V chunks
rotate around the ring via ``lax.ppermute`` while each device keeps a
numerically-stable online-softmax accumulator (flash-attention style), so
attention is EXACT — identical to dense up to float error — with activation
memory O(T/n) per device and N-1 ICI hops instead of an all-gather.

Supports causal masking (global positions derived from the ring index),
grouped-query attention (fewer K/V heads than Q heads), and sequences that
do not divide the ring size (internal padding, masked out of the softmax).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from demodel_tpu.utils.env import env_bool

NEG_INF = -1e30  # large-but-finite: -inf rows would NaN through exp/where


def dense_attention(q, k, v, causal: bool = True,
                    scale: float | None = None) -> jax.Array:
    """Reference single-device attention. q: [B,T,H,D], k/v: [B,T,Hkv,D]."""
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    if H != Hkv:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if scale is None:
        scale = D ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _use_flash_ring() -> bool:
    """Compute each ring step with the fused pallas kernel
    (ops/flash_attention.py), combining per-step partials in log space —
    no (B,H,Tq,Tk) score tensor per step, and no GQA head repeat riding
    the ppermute? Only when ``DEMODEL_FLASH_RING`` says so."""
    return env_bool("DEMODEL_FLASH_RING")


def _ring_attention_flash(q, k, v, axis_name, causal, scale, kv_len):
    """Flash-tiled ring: per step, the kernel returns the NORMALIZED
    partial and its per-row logsumexp; partials merge as
    ``O ← O·e^{L−L'} + O_i·e^{L_i−L'}`` with ``L' = logaddexp(L, L_i)``
    — numerically the same online softmax the einsum path runs, held at
    row granularity instead of materialized scores."""
    from demodel_tpu.ops.flash_attention import flash_attention

    B, Tq, H, D = q.shape
    if scale is None:
        scale = D ** -0.5
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    Tk = k.shape[1]

    O = jnp.zeros((B, Tq, H, D), jnp.float32)
    L = jnp.full((B, Tq, H), NEG_INF, jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]
    for step in range(n):
        src = (my - step) % n
        # absolute-position masking folded into the kernel's scalars:
        # query i (global my·Tq+i) sees key j (global src·Tk+j) iff
        # j ≤ i + (my·Tq − src·Tk); ring padding is key-validity
        offset_step = my * Tq - src * Tk
        kv_local = Tk if kv_len is None else jnp.clip(
            kv_len - src * Tk, 0, Tk)
        out_i, lse_i = flash_attention(
            q, k, v, kv_len=kv_local, causal=causal, scale=scale,
            causal_offset=offset_step, return_lse=True)
        L_comb = jnp.logaddexp(L, lse_i)
        O = (O * jnp.exp(L - L_comb)[..., None]
             + out_i.astype(jnp.float32) * jnp.exp(lse_i - L_comb)[..., None])
        L = L_comb
        if step != n - 1:
            k = lax.ppermute(k, axis_name, perm)
            v = lax.ppermute(v, axis_name, perm)
    return O.astype(q.dtype)


def ring_attention(q, k, v, axis_name: str = "sp", causal: bool = True,
                   scale: float | None = None,
                   kv_len: jax.Array | None = None,
                   use_flash: bool | None = None) -> jax.Array:
    """Per-shard ring attention (call inside shard_map over ``axis_name``).

    q: [B, T_loc, H, D]; k/v: [B, T_loc, Hkv, D] (GQA repeats on the fly).
    ``kv_len`` (global) masks ring padding when the true sequence length is
    not a multiple of the ring size.
    """
    if use_flash is None:
        use_flash = _use_flash_ring()
    if use_flash:
        return _ring_attention_flash(q, k, v, axis_name, causal, scale,
                                     kv_len)
    B, Tq, H, D = q.shape
    Hkv = k.shape[2]
    if H != Hkv:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if scale is None:
        scale = D ** -0.5
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    Tk = k.shape[1]

    q32 = q.astype(jnp.float32) * scale
    num = jnp.zeros((B, H, Tq, D), jnp.float32)
    den = jnp.zeros((B, H, Tq), jnp.float32)
    m = jnp.full((B, H, Tq), NEG_INF, jnp.float32)

    q_pos = my * Tq + jnp.arange(Tq)

    perm = [(i, (i + 1) % n) for i in range(n)]
    for step in range(n):
        src = (my - step) % n
        k_pos = src * Tk + jnp.arange(Tk)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q32, k.astype(jnp.float32))
        valid = jnp.ones((Tq, Tk), bool)
        if causal:
            valid &= q_pos[:, None] >= k_pos[None, :]
        if kv_len is not None:
            valid &= (k_pos < kv_len)[None, :]
        scores = jnp.where(valid[None, None], scores, NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        # masked entries are zeroed EXPLICITLY, not via underflow: a row
        # with zero visible keys this step has m_new == NEG_INF, so
        # exp(scores - m_new) would be 1 (not 0) for every masked entry
        # and den would silently accumulate Tk (output = mean of V)
        p = jnp.where(valid[None, None],
                      jnp.exp(scores - m_new[..., None]), 0.0)
        num = num * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v.astype(jnp.float32))
        den = den * alpha + p.sum(axis=-1)
        m = m_new
        if step != n - 1:
            k = lax.ppermute(k, axis_name, perm)
            v = lax.ppermute(v, axis_name, perm)

    out = num / jnp.maximum(den, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh: Mesh, axis: str = "sp",
                           causal: bool = True) -> jax.Array:
    """Global-view wrapper: shards the sequence over ``axis`` (padding to a
    multiple of the ring size, masked), runs the ring, unpads.

    Eager calls (concrete arrays — serving / tests, not under an outer
    ``jit``) run under a ``compute.ring-attention`` span when EXPORT
    tracing is opted in (``DEMODEL_TRACE`` / ``trace.enable()``), so the
    compute plane shows up in the critical-path report and the stage
    histograms alongside pull/serve/restore. The span syncs the result
    (a dispatch-only duration would be a lie), so it deliberately does
    NOT run under the default observe tier — default-config callers keep
    fully async dispatch. ``jit``-traced calls skip the span entirely (a
    span inside ``jit`` would record trace-time once, not run time)."""
    n = int(mesh.shape[axis])
    B, T, H, D = q.shape

    def run() -> jax.Array:
        nonlocal q, k, v
        pad = (-T) % n
        kv_len = None
        if pad:
            kv_len = jnp.int32(T)
            zq = ((0, 0), (0, pad), (0, 0), (0, 0))
            q = jnp.pad(q, zq)
            k = jnp.pad(k, zq)
            v = jnp.pad(v, zq)

        spec = P(None, axis, None, None)
        from demodel_tpu.parallel.collectives import shard_map_nocheck

        fn = shard_map_nocheck(
            functools.partial(ring_attention, axis_name=axis, causal=causal,
                              kv_len=kv_len),
            mesh, (spec, spec, spec), spec,
        )
        out = fn(q, k, v)
        return out[:, :T] if pad else out

    from demodel_tpu.utils import trace

    if isinstance(q, jax.core.Tracer) or not trace.enabled():
        return run()
    with trace.span("compute.ring-attention", batch=B, tokens=T, heads=H,
                    head_dim=D, ring=n, causal=causal):
        out = run()
        # demodel: allow(no-host-sync-in-hot-path) — observability-only
        # sync: the span must time the COMPUTE, not the async dispatch;
        # this branch runs only when the operator opted into export
        # tracing, never on the default (observe-tier) hot path
        jax.block_until_ready(out)
        return out
