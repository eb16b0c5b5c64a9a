"""Paged KV cache: fixed-size blocks in one preallocated host pool.

vLLM's PagedAttention memory discipline grafted onto the repo's tier
accounting: the pool preallocates ``num_blocks`` blocks of
``block_tokens`` KV slots each (all layers of one token position live in
the same block index — a block is ``[L, block_tokens, Hkv, hd]`` ×2 for
K and V), sequences lease whole blocks through a
:class:`~demodel_tpu.tier.TierBudget` so generation KV memory shows up
on statusz next to the RAM tier, and a finished sequence's blocks return
to the free list immediately — no per-sequence ``max_len`` rectangle,
no fragmentation beyond the last partial block.

The model never sees a block table: the scheduler gathers each step's
running sequences into a dense ``[B, S, Hkv, hd]`` view
(:meth:`KVBlockPool.gather`) and writes the step's new K/V back through
:meth:`KVBlockPool.write_token` — placement is entirely the pool's
business, which is what makes admission/eviction a host-side list
operation instead of a device reshape.

Pool arrays are host numpy in the model's dtype (``ml_dtypes.bfloat16``
for a bf16 checkpoint): the pool is the *memory ledger* (alloc/free
exactness, budget-bounded admission), while compute shapes stay static
for jit via the scheduler's bucketing.
"""

from __future__ import annotations

import threading
from typing import Any

import ml_dtypes  # noqa: F401 — registers bfloat16 with numpy's dtype names
import numpy as np

from demodel_tpu.tier import TierBudget
from demodel_tpu.utils import trace
from demodel_tpu.utils.env import gen_block_tokens, gen_kv_mb
from demodel_tpu.utils.logging import get_logger
from demodel_tpu.utils.metrics import HUB

log = get_logger("serve.kvcache")

#: pre-register the generation KV families at import so a scrape types
#: them before the first request (house idiom — see tier.py)
HUB.set_gauge("gen_kv_blocks_in_use", 0)
HUB.inc("gen_kv_blocks_alloc_total", 0)
HUB.inc("gen_kv_blocks_freed_total", 0)
HUB.inc("gen_d2h_bytes_total", 0)


class PoolExhausted(Exception):
    """alloc() asked for more blocks than the pool has free — the
    admission signal: the scheduler keeps the sequence WAITING (or the
    admission queue overflows into 503), it never overcommits."""


class BlockLease:
    """One sequence's blocks. Must reach :meth:`free` exactly once —
    at completion, eviction, or error; idempotent so cleanup paths can
    race shutdown without double-crediting the budget."""

    __slots__ = ("_pool", "blocks", "_freed")

    def __init__(self, pool: "KVBlockPool", blocks: list[int]):
        self._pool = pool
        self.blocks = blocks
        self._freed = False

    def free(self) -> None:
        if self._freed:
            return
        self._freed = True
        self._pool._reclaim(self.blocks)


class KVBlockPool:
    """Preallocated block pool for one model's generation KV.

    ``layers``/``kv_heads``/``head_dim`` fix the block geometry; the
    byte budget (``DEMODEL_GEN_KV_MB`` unless overridden) fixes the
    block count. All block state sits behind one lock; the K/V arrays
    themselves are written lock-free because a block belongs to exactly
    one live lease and only the engine thread touches leased bytes.
    """

    def __init__(self, layers: int, kv_heads: int, head_dim: int, *,
                 block_tokens: int | None = None,
                 budget_mb: int | None = None,
                 dtype: str = "float32"):
        self.block_tokens = int(block_tokens or gen_block_tokens())
        budget_bytes = int(budget_mb if budget_mb is not None
                           else gen_kv_mb()) << 20
        dt = np.dtype(dtype)
        # K + V, every layer, one block of token positions
        self.block_bytes = (2 * layers * self.block_tokens * kv_heads
                            * head_dim * dt.itemsize)
        self.num_blocks = max(1, budget_bytes // self.block_bytes)
        shape = (layers, self.num_blocks, self.block_tokens, kv_heads,
                 head_dim)
        self.k = np.zeros(shape, dt)
        self.v = np.zeros(shape, dt)
        self.budget = TierBudget("gen-kv", budget_bytes)
        self._free_list = list(range(self.num_blocks - 1, -1, -1))
        self._lock = threading.Lock()
        log.info("kv pool: %d blocks x %d tokens (%d KiB/block, %d MiB)",
                 self.num_blocks, self.block_tokens,
                 self.block_bytes >> 10, budget_bytes >> 20)

    # ------------------------------------------------------------ sizing
    def blocks_for(self, tokens: int) -> int:
        """Blocks needed to hold ``tokens`` KV positions (≥1)."""
        return max(1, -(-int(tokens) // self.block_tokens))

    @property
    def free_blocks(self) -> int:
        with self._lock:
            return len(self._free_list)

    @property
    def in_use_blocks(self) -> int:
        with self._lock:
            return self.num_blocks - len(self._free_list)

    # ------------------------------------------------------- alloc/free
    def alloc(self, n: int) -> BlockLease:
        """Lease ``n`` blocks or raise :class:`PoolExhausted` — never a
        partial grant, so admission is all-or-nothing (no overcommit:
        the caller reserves its worst case up front)."""
        with self._lock:
            if n > len(self._free_list):
                raise PoolExhausted(
                    f"need {n} blocks, {len(self._free_list)} free "
                    f"of {self.num_blocks}")
            blocks = [self._free_list.pop() for _ in range(n)]
            in_use = self.num_blocks - len(self._free_list)
        self.budget.charge(n * self.block_bytes)
        HUB.inc("gen_kv_blocks_alloc_total", n)
        HUB.set_gauge("gen_kv_blocks_in_use", in_use)
        return BlockLease(self, blocks)

    def _reclaim(self, blocks: list[int]) -> None:
        with self._lock:
            self._free_list.extend(blocks)
            in_use = self.num_blocks - len(self._free_list)
        self.budget.release(len(blocks) * self.block_bytes)
        HUB.inc("gen_kv_blocks_freed_total", len(blocks))
        HUB.set_gauge("gen_kv_blocks_in_use", in_use)

    # ---------------------------------------------------------- data IO
    def write_prompt(self, lease: BlockLease, kv) -> None:
        """Page a prefill's KV out into the lease: ``kv`` is the
        per-layer ``(k, v)`` list from ``step_prefill``, each
        [1, T, Hkv, hd]. The ``serve.kv-pageout`` span covers the 2L
        pulls to the host and the block copies; its ``bytes`` (what
        crosses device → host, from the shapes) feed
        ``gen_d2h_bytes_total``."""
        T = kv[0][0].shape[1]
        nbytes = sum(lk.nbytes + lv.nbytes for lk, lv in kv)
        with trace.span("serve.kv-pageout", prompt=T, bytes=nbytes):
            k = np.stack([np.asarray(lk[0]) for lk, _lv in kv])
            v = np.stack([np.asarray(lv[0]) for _lk, lv in kv])
            bs = self.block_tokens
            for j in range(0, T, bs):
                blk = lease.blocks[j // bs]
                n = min(bs, T - j)
                self.k[:, blk, :n] = k[:, j:j + n]
                self.v[:, blk, :n] = v[:, j:j + n]
            HUB.inc("gen_d2h_bytes_total", nbytes)

    def write_token(self, lease: BlockLease, pos: int, k, v) -> None:
        """Write one decoded position: ``k``/``v`` are [L, Hkv, hd]."""
        blk = lease.blocks[pos // self.block_tokens]
        off = pos % self.block_tokens
        self.k[:, blk, off] = k
        self.v[:, blk, off] = v

    def gather(self, leases: list[BlockLease], width: int, rows: int):
        """Dense [L, rows, width, Hkv, hd] K and V views of ``leases`` —
        the per-step ragged batch the model consumes. Rows past a
        sequence's filled length are stale pool bytes; the model masks
        them by length (see ``llama.step_decode``), so short sequences
        simply index block 0 for table slots they don't have. ``rows``
        (at least one per lease) pads the batch with zero rows up to the
        scheduler's jit bucket. The ``serve.kv-gather`` span covers the
        gather and the pad; its ``bytes`` are the two rectangles it
        returns, from the pool's geometry."""
        B, width, rows = len(leases), int(width), int(rows)
        L = self.k.shape[0]
        bs = self.block_tokens
        with trace.span("serve.kv-gather", batch=B, width=width,
                        bytes=rows * width * (self.block_bytes // bs)):
            nb = -(-width // bs)
            ids = np.zeros((B, nb), np.int64)
            for i, lease in enumerate(leases):
                got = lease.blocks[:nb]
                ids[i, :len(got)] = got
            k = self.k[:, ids].reshape(L, B, nb * bs,
                                       *self.k.shape[3:])[:, :, :width]
            v = self.v[:, ids].reshape(L, B, nb * bs,
                                       *self.v.shape[3:])[:, :, :width]
            if rows > B:
                pad = ((0, 0), (0, rows - B)) + ((0, 0),) * (k.ndim - 2)
                k = np.pad(k, pad)
                v = np.pad(v, pad)
        return k, v

    # ------------------------------------------------------------ intro
    def describe(self) -> dict[str, Any]:
        with self._lock:
            free = len(self._free_list)
        return {
            "block_tokens": self.block_tokens,
            "block_bytes": self.block_bytes,
            "num_blocks": self.num_blocks,
            "free_blocks": free,
            "in_use_blocks": self.num_blocks - free,
            "budget": self.budget.describe(),
        }
