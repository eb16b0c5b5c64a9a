"""The generation cache: paged K and V (or a latent layer's one vector a
position), and fixed slots of what a sequence keeps whatever its length
(recurrent state, a window layer's ring of its last positions), in one
preallocated pool on the device.

vLLM's PagedAttention memory discipline grafted onto the repo's tier
accounting: the pool preallocates ``num_blocks`` blocks of
``block_tokens`` KV slots each (all layers of one token position live in
the same block index — a block is ``[L, block_tokens, Hkv, hd]`` ×2 for
K and V), sequences lease whole blocks through a
:class:`~demodel_tpu.tier.TierBudget` so generation KV memory shows up
on statusz next to the RAM tier, and a finished sequence's blocks return
to the free list immediately — no per-sequence ``max_len`` rectangle,
no fragmentation beyond the last partial block.

**Two kinds of state, one manager.** A model's module states what it keeps
for a sequence (:class:`CacheSpec`, from its ``cache_spec(cfg)``): which of
its layers page K and V, and which arrays of fixed size it carries from
token to token whatever the length (a linear-attention layer's matrix
state, a state-space layer's, the last columns of a convolution's input,
the **ring** of a window layer: the keys and values of its last ``window``
positions and no others, position ``p`` at place ``p mod window``, laid out
in blocks as the pages are). A pool built from a spec
with such arrays also holds **slots**: a sequence's lease is its blocks and
one slot, taken and returned together, and the device arrays of the slots
live beside ``k`` and ``v``, ``[layers, slots + 1, ...]`` each (the extra
slot is scratch, as the extra block is). Llama and EXAONE-MoE say "every
layer pages, no slot", and their pool is ``k`` and ``v`` alone; Qwen3-Next
pages its attention layers and keeps a slot of recurrent state; Phi-4-flash
pages one layer (which seven more read), and its slot holds eight rings,
nine state-space states and their convolutions' tails.

**A page of one array.** A latent-attention layer keeps one vector a
position, ``[c_kv | k_rope]``, whose first ``values`` columns are also its
values: its module says so in its spec (``CacheSpec.values``), and the pool
then holds **one** array of pages, ``k`` (``[L, num_blocks + 1, kv_heads,
block_tokens, head_dim]`` like any other; ``v`` is ``None``), which it
leases, budgets (``block_bytes`` counts it once), describes and donates as
it does the pair. Everything below that speaks of ``k`` and ``v`` takes
``v=None`` for such a page: :meth:`Paged.read` and :meth:`Paged.past` hand
the blocks over once and ``models/common.attend`` takes the values as the
leading columns of the keys it has gathered; :func:`put_blocks` and
:func:`put_positions` write the one array.

The pool is two halves that never touch each other:

- the **ledger** on the host: free list, :class:`BlockLease`, budget,
  counters, ``describe()``. Admission and eviction are list operations.
- the **arrays** on the device: ``k`` and ``v``, ``[L, num_blocks + 1,
  Hkv, block_tokens, hd]`` in the model's dtype, ``L`` the layers that page
  (positions and head width innermost, the order the TPU's attention
  reads; the extra block is scratch no lease can hold), so the byte budget
  (``budget_mb``) is an HBM budget. Under a ``tp`` mesh they are sharded on
  the KV-head axis by ``llama._head_align``'s rule (replicated when the
  heads do not divide); then the spec's state arrays, replicated.
  The budget pays for the slots first and the blocks with the rest. Every
  program that writes them takes them all donated and returns them all
  (:meth:`KVBlockPool.apply`), so the bytes never move: a decode step
  sends a block table in and gets ids back.

Inside the engine's jitted programs every model reads the pool the same
way: its ``step_decode`` is handed :class:`Paged` (the arrays and the
batch's block table) and each layer gathers the blocks it reads where they
lie (:meth:`Paged.read`; ``models/common.attend`` takes them in that
layout), and a layer with state reads its rows' slots
(:meth:`Paged.read_state`). A layer that reads the whole of its rows'
pages asks for them as a past (:meth:`Paged.filled` once a step,
:meth:`Paged.past` a layer): the rectangle of the table's width where that
is two tiles of :data:`TILE_BLOCKS` slots (the narrowest table there is,
:func:`table_slots`), beyond it the tiles the rows have filled
(:class:`Tiles`), so that a wide step's work follows what its rows hold
and not rows x width. :func:`put_blocks` / :func:`put_positions`
place a prefill's or a step's new K/V, :func:`put_slots` what it leaves in
the slots: the whole of a layer's part of the slot (a list, one array a
layer), one part of it for every layer at once (:class:`Placed`: a
position of a ring, :func:`ring_put`), or the array as the module made it
(:class:`Whole`) — placement is entirely this module's
business, the ring's order (:func:`ring_fill`, :func:`ring_positions`)
with it. A prefill writes the whole of its slot (a ring in ring order,
zeros where the prompt is shorter), so a slot taken again carries nothing
over.

**One signature for life.** ``jax.jit`` keys its executables on an
argument's sharding and on whether it is committed. The arrays are
therefore born as the output of a program that is told to return
``self.sharding``, and every program that takes them must return them
with ``out_shardings=pool.sharding``: the first call on a fresh pool and
every later one then hit the same executable (a second one would compile,
or load from the persistent cache, in the middle of serving).
"""

from __future__ import annotations

import math
import threading
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import (NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from demodel_tpu.tier import TierBudget
from demodel_tpu.utils.logging import get_logger
from demodel_tpu.utils.metrics import HUB

log = get_logger("serve.kvcache")

#: pre-register the generation KV families at import so a scrape types
#: them before the first request (house idiom — see tier.py)
HUB.set_gauge("gen_kv_blocks_in_use", 0)
HUB.inc("gen_kv_blocks_alloc_total", 0)
HUB.inc("gen_kv_blocks_freed_total", 0)
HUB.set_gauge("gen_state_slots_in_use", 0)
HUB.inc("gen_state_slots_alloc_total", 0)
HUB.inc("gen_state_slots_freed_total", 0)
HUB.inc("gen_kv_positions_width_total", 0)
HUB.inc("gen_kv_positions_read_total", 0)
HUB.inc("gen_kv_positions_in_place_total", 0)


class CacheSpec(NamedTuple):
    """What a model keeps for a sequence, as its module states it
    (``cache_spec(cfg)``): ``layers`` of its layers page K and V at
    ``kv_heads`` heads of ``head_dim``; ``state`` names the arrays of fixed
    size a sequence carries beside them, each ``(name, shape, dtype)`` with
    the layers that keep it as the shape's first axis (the pool puts the
    slot axis second, as K and V have the block axis). What an array is
    (a recurrent state, a convolution's tail, a ring ``[layers, window /
    block_tokens, kv_heads, block_tokens, head_dim]`` of a window layer's
    last positions) is the module's to know: the pool holds it, leases it
    with the slot and counts its bytes. ``values`` 0 is a page of keys and
    of values, two arrays of ``head_dim`` columns; otherwise the page is
    one array whose ``head_dim`` columns are a position's keys and whose
    first ``values`` columns are also its values (a latent layer's ``[c_kv
    | k_rope]``, one "head" all query heads share)."""

    layers: int
    kv_heads: int
    head_dim: int
    state: tuple[tuple[str, tuple[int, ...], str], ...] = ()
    values: int = 0


class PoolExhausted(Exception):
    """alloc() asked for more blocks than the pool has free — the
    admission signal: the scheduler keeps the sequence WAITING (or the
    admission queue overflows into 503), it never overcommits."""


class BlockLease:
    """One sequence's blocks and, in a pool that has slots, its slot
    (``None`` otherwise). Must reach :meth:`free` exactly once — at
    completion, eviction, or error; idempotent so cleanup paths can race
    shutdown without double-crediting the budget."""

    __slots__ = ("_pool", "blocks", "slot", "_freed")

    def __init__(self, pool: "KVBlockPool", blocks: list[int],
                 slot: int | None = None):
        self._pool = pool
        self.blocks = blocks
        self.slot = slot
        self._freed = False

    def free(self) -> None:
        if self._freed:
            return
        self._freed = True
        self._pool._reclaim(self.blocks, self.slot)


class KVBlockPool:
    """Preallocated pool for one model's generation cache.

    ``spec`` (the model module's :class:`CacheSpec`) fixes the block
    geometry and the slot's arrays; ``slots`` how many sequences can hold
    a slot at once (the engine's ``max_batch``; ignored when the spec has
    no state); the byte budget (``budget_mb``) pays for the slots and
    fixes the block count with what is left; ``mesh`` (the engine's) fixes
    where the arrays live. All ledger state sits behind one lock and never
    touches the arrays; the arrays belong to the engine thread alone,
    which hands them to its programs through :meth:`apply`.
    """

    def __init__(self, spec: CacheSpec, *,
                 slots: int = 0,
                 block_tokens: int = 16,
                 budget_mb: int = 256,
                 dtype: str = "float32", mesh=None):
        self.spec = spec
        layers, kv_heads, head_dim = spec.layers, spec.kv_heads, spec.head_dim
        self.block_tokens = int(block_tokens)
        budget_bytes = int(budget_mb) << 20
        dt = jnp.dtype(dtype)
        #: arrays a page is made of: K and V, or a latent layer's one
        self.pages = 1 if spec.values else 2
        # every layer that pages, one block of token positions
        self.block_bytes = (self.pages * layers * self.block_tokens
                            * kv_heads * head_dim * dt.itemsize)
        #: one sequence's fixed state, all its arrays
        self.slot_bytes = sum(
            math.prod(shape) * jnp.dtype(sdt).itemsize
            for _name, shape, sdt in spec.state)
        self.num_slots = max(1, int(slots)) if spec.state else 0
        self.num_blocks = max(1, (
            budget_bytes - self.num_slots * self.slot_bytes)
            // self.block_bytes)
        #: one block past the leasable ones, where a row that must write
        #: nothing writes (see :func:`put_positions`), and one such slot
        self.scratch_block = self.num_blocks
        self.scratch_slot = self.num_slots
        shape = (layers, self.num_blocks + 1, kv_heads, self.block_tokens,
                 head_dim)
        if mesh is None:
            self.sharding = self.replicated = SingleDeviceSharding(
                jax.devices()[0])
        else:
            tp = int(mesh.shape.get("tp", 1))
            heads = "tp" if tp > 1 and kv_heads % tp == 0 else None
            self.sharding = NamedSharding(
                mesh, P(None, None, heads, None, None))
            #: where the engine puts a program's small per-call inputs
            self.replicated = NamedSharding(mesh, P())
        #: True where the arrays lie on more than one chip (their heads
        #: across them, or a copy on each), None on one: what a program's
        #: :class:`Paged` carries to :func:`_in_place`
        self.meshed = True if len(self.sharding.device_set) > 1 else None
        #: one tile of one of a page's arrays, as a kernel's buffer holds it
        self.tile_bytes = (TILE_BLOCKS * kv_heads * self.block_tokens
                           * head_dim * dt.itemsize)
        #: what the engine's programs are lowered for: the arrays' devices'
        self.platform = next(iter(self.sharding.device_set)).platform
        #: names of the slot's arrays, in the order :attr:`arrays` holds
        #: them after the pages
        self.state_names = tuple(name for name, _s, _d in spec.state)
        made = [(shape, dt)] * self.pages + [
            ((s[0], self.num_slots + 1, *s[1:]), jnp.dtype(sdt))
            for _name, s, sdt in spec.state]
        #: what every program returns the arrays with, in their order
        self.shardings = (self.sharding,) * self.pages \
            + (self.replicated,) * len(spec.state)
        # a program's output, like every later pool: see the module
        # docstring ("one signature for life")
        self._fresh = jax.jit(
            lambda: tuple(jnp.zeros(s, d) for s, d in made),
            out_shardings=self.shardings)
        #: ``(k, v, *state)``, or ``(k, *state)`` where the page is one
        #: array: engine thread only
        self.arrays = self._fresh()
        self.budget = TierBudget("gen-kv", budget_bytes)
        self._free_list = list(range(self.num_blocks - 1, -1, -1))
        self._free_slots = list(range(self.num_slots - 1, -1, -1))
        self._lock = threading.Lock()
        log.info("kv pool: %d blocks x %d tokens (%d KiB/block), %d slots "
                 "(%d KiB/slot), %d MiB on %s", self.num_blocks,
                 self.block_tokens, self.block_bytes >> 10, self.num_slots,
                 self.slot_bytes >> 10, budget_bytes >> 20, self.sharding)

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

    @property
    def in_use_slots(self) -> int:
        with self._lock:
            return self.num_slots - len(self._free_slots)

    def positions_in_place(self, slots: int, read: int) -> int:
        """On the host, from shapes and the pool's own devices: how many of
        the ``read`` positions a decode step's attention reads at ``slots``
        table slots a row it reads from the pool itself, with no gathered
        copy: all of them where the step's programs hold a kernel that
        follows the filled tiles (:func:`_in_place` pages, a wide table,
        programs lowered for a TPU), none otherwise: the rule of
        :func:`models.common._over_tiles`."""
        in_place = (_in_place(self.pages == 2, self.spec.kv_heads,
                              self.tile_bytes, self.meshed)
                    and _wide(slots) and self.platform == "tpu")
        return read if in_place else 0

    # ------------------------------------------------------- alloc/free
    def alloc(self, n: int) -> BlockLease:
        """Lease ``n`` blocks, and a slot where the pool has them, or raise
        :class:`PoolExhausted` — never a partial grant, so admission is
        all-or-nothing (no overcommit: the caller reserves its worst case
        up front)."""
        with self._lock:
            if n > len(self._free_list):
                raise PoolExhausted(
                    f"need {n} blocks, {len(self._free_list)} free "
                    f"of {self.num_blocks}")
            if self.num_slots and not self._free_slots:
                raise PoolExhausted(
                    f"all {self.num_slots} state slots are held")
            blocks = [self._free_list.pop() for _ in range(n)]
            slot = self._free_slots.pop() if self.num_slots else None
            in_use = self.num_blocks - len(self._free_list)
            slots_in_use = self.num_slots - len(self._free_slots)
        HUB.inc("gen_kv_blocks_alloc_total", n)
        HUB.set_gauge("gen_kv_blocks_in_use", in_use)
        charge = n * self.block_bytes
        if slot is not None:
            charge += self.slot_bytes
            HUB.inc("gen_state_slots_alloc_total")
            HUB.set_gauge("gen_state_slots_in_use", slots_in_use)
        self.budget.charge(charge)
        return BlockLease(self, blocks, slot)

    def _reclaim(self, blocks: list[int], slot: int | None) -> None:
        with self._lock:
            self._free_list.extend(blocks)
            if slot is not None:
                self._free_slots.append(slot)
            in_use = self.num_blocks - len(self._free_list)
            slots_in_use = self.num_slots - len(self._free_slots)
        HUB.inc("gen_kv_blocks_freed_total", len(blocks))
        HUB.set_gauge("gen_kv_blocks_in_use", in_use)
        release = len(blocks) * self.block_bytes
        if slot is not None:
            release += self.slot_bytes
            HUB.inc("gen_state_slots_freed_total")
            HUB.set_gauge("gen_state_slots_in_use", slots_in_use)
        self.budget.release(release)

    # ------------------------------------------------------- the arrays
    @property
    def k(self):
        return self.arrays[0]

    @property
    def v(self):
        """None where the page is one array (its values are columns of
        ``k``)."""
        return self.arrays[1] if self.pages == 2 else None

    @property
    def state(self) -> dict:
        """The slot's arrays by name, ``[layers, slots + 1, ...]`` each."""
        return dict(zip(self.state_names, self.arrays[self.pages:]))

    def apply(self, program, *args):
        """Run one of the engine's programs over the arrays:
        ``program(*args, *arrays) -> (out, *arrays)`` with every
        array donated and returned with :attr:`shardings`. The program's
        outputs become the pool, so nothing keeps a reference to the arrays
        that went in. Engine thread only."""
        out, *arrays = program(*args, *self.arrays)
        self.arrays = tuple(arrays)
        return out

    @property
    def lost(self) -> bool:
        """A program took the arrays and gave none back."""
        return any(a.is_deleted() for a in self.arrays)

    def reset(self) -> None:
        """Fresh, zeroed arrays after a program failed with the old ones
        in hand. The ledger is the caller's to settle: every lease's
        contents are gone."""
        self.arrays = self._fresh()

    # ------------------------------------------------------------ intro
    def describe(self) -> dict[str, Any]:
        with self._lock:
            free = len(self._free_list)
            free_slots = len(self._free_slots)
        return {
            # what a position keeps: keys and values, or a latent layer's
            # one vector (``value_dim`` of its ``head_dim`` columns its
            # values too)
            "page": "latent" if self.spec.values else "kv",
            "value_dim": self.spec.values or self.spec.head_dim,
            # the layers that page: a model with two cached sublayers a
            # layer has twice its own count here
            "layers": self.spec.layers,
            "block_tokens": self.block_tokens,
            "block_bytes": self.block_bytes,
            "num_blocks": self.num_blocks,
            "free_blocks": free,
            "in_use_blocks": self.num_blocks - free,
            "slot_bytes": self.slot_bytes,
            "num_slots": self.num_slots,
            "in_use_slots": self.num_slots - free_slots,
            "budget": self.budget.describe(),
        }


# ------------------------------------------------- inside the programs
# jit-traceable: the engine's prefill and decode programs call these on
# the donated arrays. Indices are int32 arrays built on the host from the
# leases. A row that must write nothing (a pad row of the batch bucket)
# is given ``pool.scratch_block`` and ``pool.scratch_slot``, the one block
# and the one slot no lease can hold.


class Paged(NamedTuple):
    """What a model's ``step_decode`` gets for its cache: the pool's arrays,
    the batch's block table and, where the pool has slots, each row's. A
    layer reads the table slots it needs (all of a row's, or the few that
    cover a window) with :meth:`read`, a layer with state its rows' slots
    with :meth:`read_state`. A row's table slots past its lease, and a pad
    row's, may name any block: the model masks positions at or past a
    row's length.

    The table's width is :func:`table_slots` of the batch's longest row:
    two tiles a row whatever the rows hold up to there (the rectangle),
    and past two tiles it steps so coarsely that it is capacity and not
    work, because a layer that reads the whole of its rows' pages asks for
    them through :meth:`filled` and :meth:`past` and then follows the
    tiles the rows have filled. A ``step_decode`` that read a wide table
    whole (``read(layer, table)``) would pay for the width."""

    k: jax.Array
    v: Any                  # None: a page of one array, values inside k
    table: jax.Array        # [B, n] block ids
    state: dict = {}        # name -> [layers, slots + 1, ...]
    slots: Any = None       # [B] slot ids
    meshed: Any = None      # True: the pool lies on more than one chip
    #                         (``KVBlockPool.meshed``; never False: None
    #                         is no leaf of a program's arguments)

    @property
    def block_tokens(self) -> int:
        return self.k.shape[3]

    def read(self, layer: int, ids):
        """Blocks ``ids`` [B, m] of one layer as the pool holds them:
        ``(k, v)``, each [B, m, Hkv, block_tokens, hd], not transposed
        (``v`` None where the page is one array: gathered once, the values
        are columns of ``k``). The caller masks what a row does not own.
        One gather from the pool itself (layers and blocks as one axis,
        which costs nothing): a slice of one layer first is a copy of it,
        the whole pool a step over all layers."""
        L, nb = self.k.shape[:2]
        at = ids + layer * nb

        def blocks(a):
            return jnp.take(a.reshape(L * nb, *a.shape[2:]), at, axis=0,
                            mode="clip")

        return blocks(self.k), None if self.v is None else blocks(self.v)

    @property
    def wide(self) -> bool:
        """More than two tiles a row: a step's attention over the whole
        of its rows' pages then follows the tiles they have filled."""
        return _wide(self.table.shape[1])

    def filled(self, lengths):
        """What a step computes once for every layer that reads the whole
        of its rows' pages, ``lengths`` [B] positions each: where the table
        is at most two tiles wide (there is at most a tile a row to skip)
        the rectangle's positions and which of them a row owns,
        ``(kpos, live)``; beyond that the flat list of the **tiles** the
        rows have filled, a :class:`Tiles` of the pool itself. A tile is
        :data:`TILE_BLOCKS` table slots of one row."""
        if not self.wide:
            B, n = self.table.shape
            S = n * self.block_tokens
            kpos = jnp.broadcast_to(jnp.arange(S), (B, S))
            return kpos, kpos < lengths[:, None]
        with jax.named_scope("kv.tiles"):
            return _tiles(jnp.clip(self.table, 0, self.k.shape[1] - 1),
                          lengths, self.block_tokens)

    def past(self, layer: int, filled):
        """One layer's pages as :func:`models.common.attend` takes a past:
        ``filled`` is what :meth:`filled` gave for the step; the rectangle
        ``(k, v, kpos, live)`` gathered to the table's width, or the filled
        tiles where they lie in the pool."""
        if not isinstance(filled, Tiles):
            return (*self.read(layer, self.table), *filled)
        L, nb = self.k.shape[:2]
        return filled._replace(
            k=self.k.reshape(L * nb, *self.k.shape[2:]),
            v=None if self.v is None
            else self.v.reshape(L * nb, *self.v.shape[2:]),
            ids=filled.ids + layer * nb, meshed=self.meshed)

    def read_state(self, name: str, layer: int):
        """The rows' slots of one layer of the state array ``name``,
        [B, ...]: one gather, layers and slots as one axis."""
        a = self.state[name]
        L, ns = a.shape[:2]
        return jnp.take(a.reshape(L * ns, *a.shape[2:]),
                        self.slots + layer * ns, axis=0, mode="clip")


#: table slots a tile holds: what a wide past is skipped in
TILE_BLOCKS = 16
#: the ratio of a wide table's widths: 2, 16, 128 tiles a row (512, 4 096,
#: 32 768 positions at 16 a block)
WIDE_STEP = 8
#: tiles a trip of the loops over the filled tiles takes
TILE_CHUNK = 128
#: what a chunk of keys and one of values may take together of a core's
#: fast memory (128 MiB on a v5e) and still both be held there
FAST_BYTES = 96 << 20
#: what the four buffers of the kernel over keys and values apart may take
#: of the 16 MiB a kernel is given of fast memory unasked: 2.5 MiB at
#: Phi-4-mini-flash's 10 pairs of 128, 1 MiB at Qwen3-Next's 2 heads of
#: 256, 8 MiB at 32 heads of 128 in bfloat16 (compiled for the chip:
#: tests/test_tpu_layout.py)
KERNEL_BYTES = 8 << 20


class Tiles(NamedTuple):
    """The tiles a batch's rows have filled, in row order, of a capacity of
    ``C`` = rows x tiles a row of which the first so many are filled: a
    past :func:`models.common.attend` runs over a chunk at a time, never
    over the rectangle. ``ids`` [C, TILE_BLOCKS] names each tile's blocks
    in ``k`` and ``v`` ([N, Hkv, block_tokens, hd]: the pool, layers and
    blocks as one axis; ``v`` None where the page is one array and the
    values are columns of the keys), gathered a chunk a trip from where
    they lie. A
    tile past the filled ones repeats the last of them (its ``row`` too,
    with no position ``live``), so no block wholly past a row's length is
    ever read. A trip of the loop over the chunks costs a handful of device
    operations whatever it moves, so the chunks are few and large and what
    a trip needs of the index is ready to be sliced: a chunk's ids and
    ``live`` (:meth:`chunk`) and whose its tiles are (:meth:`rows`), by
    which the trip combines them into the one running softmax it carries a
    row. Whether a trip gathers keys and values apart follows from a
    chunk's bytes (:attr:`apart`). The chunks, the gathers and ``apart``
    are the portable loop's: a program lowered for a TPU reads the filled
    tiles of either kind of page from the pool itself, a tile at a time
    (:attr:`in_place`; the kernels take ``ids``, ``live`` and ``own``)."""

    ids: jax.Array          # [C, TILE_BLOCKS] uint32
    row: jax.Array          # [C] the row a tile belongs to
    live: jax.Array         # [C, positions a tile] which of its slots hold
    #                         a position of its row: none of a tile past
    #                         the filled ones
    own: jax.Array          # [B, tiles a row] where a row's tiles lie in
    #                         the list, -1 where it has filled none (the
    #                         kernel's: a row's first tile and its count)
    trips: jax.Array        # [] uint32: the chunks that hold a filled tile
    #                         (unsigned: a loop's index then slices without
    #                         a test for a negative start)
    k: Any = None
    v: Any = None
    meshed: Any = None      # ``Paged.meshed``

    @property
    def chunk_tiles(self) -> int:
        return _chunk_tiles(self.row.shape[0])

    @property
    def in_place(self) -> bool:
        """A program lowered for a TPU reads these tiles from the pool
        itself (:func:`_in_place`); where not, every platform's program is
        the loop."""
        return _in_place(
            self.v is not None, self.k.shape[1],
            TILE_BLOCKS * math.prod(self.k.shape[1:]) * self.k.dtype.itemsize,
            self.meshed)

    @property
    def apart(self) -> bool:
        """The loop's alone (a kernel holds a tile of each, not a chunk):
        a chunk of keys and one of values do not fit fast memory together,
        so the compiler would leave one of them in HBM, and a trip gathers
        the values when it is done with the keys."""
        return self.v is not None and 2 * self.chunk_tiles * TILE_BLOCKS \
            * math.prod(self.k.shape[1:]) * self.k.dtype.itemsize \
            > FAST_BYTES

    def chunk(self, i):
        """Chunk ``i``: its tiles' block ids [n * TILE_BLOCKS] and their
        ``live`` [n, positions a tile]."""
        n = self.chunk_tiles
        return (lax.dynamic_slice_in_dim(self.ids, i * n, n).reshape(-1),
                lax.dynamic_slice_in_dim(self.live, i * n, n))

    def rows(self, i):
        """The rows [n] chunk ``i``'s tiles belong to: in row order, so a
        row's tiles of a chunk are adjacent."""
        n = self.chunk_tiles
        return lax.dynamic_slice_in_dim(self.row, i * n, n)

    def blocks(self, a, ids):
        """A chunk's blocks of ``a`` (``k`` or ``v``), gathered from where
        they lie: [n, TILE_BLOCKS, Hkv, block_tokens, hd]."""
        return a.at[ids].get(mode="promise_in_bounds").reshape(
            self.chunk_tiles, TILE_BLOCKS, *a.shape[1:])


def _chunk_tiles(capacity: int) -> int:
    return math.gcd(TILE_CHUNK, capacity)


def _in_place(apart: bool, kv_heads: int, tile_bytes: int, meshed) -> bool:
    """The pages whose filled tiles a kernel reads where they lie, stated
    once for the programs (:attr:`Tiles.in_place`) and for the host's count
    of them (:meth:`KVBlockPool.positions_in_place`).

    On one chip: one array under one cached head
    (:mod:`demodel_tpu.ops.latent_tiles`), and keys and values ``apart``,
    whatever their heads (:mod:`demodel_tpu.ops.paged_tiles`), where the
    kernel's four buffers of a tile (``tile_bytes`` each) fit
    :data:`KERNEL_BYTES`: heads, blocks or a dtype that make a tile larger
    than the largest compiled for the chip keep the loop. So does a pool
    ``meshed`` over several chips, of either kind: the compiler partitions
    the loop by the pool's heads as it did, and has no rule for a Pallas
    call (JAX refuses to lower one in a program of more than one chip
    outside a ``shard_map``: before PR 50 a latent family's wide step
    under a mesh did not lower for a TPU)."""
    if meshed:
        return False
    return 4 * tile_bytes <= KERNEL_BYTES if apart else kv_heads == 1


def _wide(slots: int) -> bool:
    """A table of ``slots`` a row is read by its filled tiles."""
    return slots > 2 * TILE_BLOCKS and slots % TILE_BLOCKS == 0


def table_slots(blocks: int) -> int:
    """The slots a row of a decode step's table whose longest row holds
    ``blocks`` blocks: two tiles (the rectangle, which masks what a row
    does not own) for every longest row up to two tiles, past that two
    tiles times a power of :data:`WIDE_STEP` (the work follows the filled
    tiles, so a width is capacity). Either way a deployment makes ready
    one program a batch bucket and not one for every doubling of its
    contexts."""
    slots = 2 * TILE_BLOCKS
    while slots < blocks:
        slots *= WIDE_STEP
    return slots


def positions_read(lengths, rows: int, slots: int,
                   block_tokens: int) -> tuple[int, int]:
    """On the host, from the lengths it shipped: ``(width, read)``, the
    positions of a step's table (``rows`` of the batch bucket, ``slots``
    each) and those its attention over whole rows reads of them: the
    filled tiles of a wide table, all of a narrow one."""
    width = rows * slots * block_tokens
    if not _wide(slots):
        return width, width
    span = TILE_BLOCKS * block_tokens
    return width, sum(min(-(-n // span), slots // TILE_BLOCKS)
                      for n in lengths) * span


def _tiles(table, lengths, block_tokens: int) -> Tiles:
    """The index of the filled tiles of ``table`` [B, n] at ``lengths``:
    row ``b`` has ``ceil(lengths[b] / tile)`` of them, and their flat list
    in row order comes from a cumulative sum."""
    B, n = table.shape
    per_row = n // TILE_BLOCKS
    span = TILE_BLOCKS * block_tokens
    tiles = jnp.minimum(-(-lengths // span), per_row)
    ends = jnp.cumsum(tiles)
    first, count = ends - tiles, ends[-1]
    # a tile past the filled ones repeats the last filled one
    j = jnp.minimum(jnp.arange(B * per_row), jnp.maximum(count - 1, 0))
    row = jnp.minimum((j[:, None] >= ends[None, :]).sum(axis=1), B - 1)
    t = j - first[row]
    ids = table[row[:, None], t[:, None] * TILE_BLOCKS
                + jnp.arange(TILE_BLOCKS)[None, :]]
    live = (t[:, None] * span + jnp.arange(span)[None, :]
            < lengths[row][:, None]) & (jnp.arange(B * per_row)
                                        < count)[:, None]
    u = jnp.arange(per_row)[None, :]
    own = jnp.where(u < tiles[:, None], first[:, None] + u, -1)
    n = _chunk_tiles(B * per_row)
    # unsigned: a gather then has no negative index to wrap
    return Tiles(ids.astype(jnp.uint32), row, live, own,
                 ((count + n - 1) // n).astype(jnp.uint32))


class Placed(NamedTuple):
    """One part of each row's slot, the same for every layer that keeps the
    array: ``new`` [layers, B, *part] lands in row ``b``'s slot at ``at[b]``
    (where the part starts along the slot's axes, the ones after layers
    and slots; None: at their start)."""

    new: jax.Array
    at: Any = None


class Whole(NamedTuple):
    """A slot array itself, ``[layers, slots + 1, ...]``, already holding
    what the step's rows leave in their slots: for a module that reads all
    its layers' rows' slots of a small array in one gather and can put them
    back in one select by slot, where a slice update a row is the bucket's
    rows in device operations (64 a step for 5.6 MB of convolution tails:
    PERF.md, Findings, PR 49)."""

    array: jax.Array


class Written(NamedTuple):
    """What a step of a model with fixed state hands back for the cache: the
    new keys and values of its paging layers, as every model's step does,
    and ``state``, name → what each row's slot holds from now on: one
    array a layer that keeps it ([B, ...] each, in the layers' order), a
    :class:`Placed` where a step writes a part of the slot only, or the
    array :class:`Whole`."""

    kv: list
    state: dict


def parts(new):
    """``(kv, state)`` of what a step hands back, whichever it is."""
    return new if isinstance(new, Written) else (new, {})


def _stack(kv):
    """Per-layer ``(k, v)`` of [B, T, Hkv, hd] → two [L, B, T, Hkv, hd]."""
    return (jnp.stack([lk for lk, _lv in kv]),
            jnp.stack([lv for _lk, lv in kv]))


def put_blocks(k, v, kv, blocks):
    """A prefill's KV into its lease: ``kv`` is ``step_prefill``'s
    per-layer ``(k, v)``, each [1, T, Hkv, hd]; ``blocks`` the lease's
    first ``ceil(T / block_tokens)`` ids. The tail of the last block is
    written with zeros (it is the lease's own, and past its length).
    ``v`` None is a page of one array: ``kv`` is then a layer's one new
    array each, and what comes back is ``(k,)``."""
    L, _nb, Hkv, bs, hd = k.shape
    n = blocks.shape[0]

    def put(a, new):
        T = new.shape[2]
        new = jnp.pad(new[:, 0], ((0, 0), (0, n * bs - T), (0, 0), (0, 0)))
        new = new.reshape(L, n, bs, Hkv, hd).transpose(0, 1, 3, 2, 4)
        return a.at[:, blocks].set(new, mode="promise_in_bounds",
                                   unique_indices=True)

    if v is None:
        return (put(k, jnp.stack(kv)),)
    nk, nv = _stack(kv)
    return put(k, nk), put(v, nv)


def put_positions(k, v, new_kv, blocks, offsets):
    """A decode step's new K/V: ``new_kv`` is ``step_decode``'s per-layer
    ``(k, v)``, each [B, 1, Hkv, hd]; row ``b`` lands in block
    ``blocks[b]`` at slot ``offsets[b]``. One in-place slice update a
    row: a scatter makes the TPU compiler copy the whole pool into
    another layout and back (PERF.md, Findings, PR 26). ``v`` None is a
    page of one array, as in :func:`put_blocks`."""
    L, _nb, Hkv, _bs, hd = k.shape

    def put(a, new):
        for b in range(blocks.shape[0]):
            a = lax.dynamic_update_slice(
                a, new[:, b].reshape(L, 1, Hkv, 1, hd),
                (0, blocks[b], 0, offsets[b], 0))
        return a

    if v is None:
        return (put(k, jnp.stack(new_kv)),)
    nk, nv = _stack(new_kv)
    return put(k, nk), put(v, nv)


def put_slots(arrays, names, state, slots):
    """What a step leaves in its rows' slots: ``arrays`` the pool's state
    arrays in the order of ``names``. ``state[name]`` is one [B, ...] array
    a layer: row ``b`` of each lands in slot ``slots[b]`` of its layer (a
    prefill is the step of one row, and writes the whole of its slot), one
    in-place slice update a layer a row, from the layer's own result, so
    that no copy of the rows, stacked over the layers, stands between (a
    matrix state of megabytes a row). Or it is a :class:`Placed`: a part
    of the slot (one position of a ring, a convolution's few columns) for
    all the layers at once, one in-place slice update a row, as
    :func:`put_positions` writes the pages. Or a :class:`Whole`: the array
    as the module has already made it."""
    out = []
    for a, name in zip(arrays, names):
        new = state[name]
        if isinstance(new, Whole):
            a = new.array.astype(a.dtype)
        elif isinstance(new, Placed):
            for b in range(slots.shape[0]):
                where = (0,) * (a.ndim - 2) if new.at is None \
                    else tuple(new.at[b])
                a = lax.dynamic_update_slice(
                    a, new.new[:, b][:, None].astype(a.dtype),
                    (0, slots[b], *where))
        else:
            for li, layer in enumerate(new):
                for b in range(slots.shape[0]):
                    a = lax.dynamic_update_slice(
                        a, layer[b][None, None].astype(a.dtype),
                        (li, slots[b]) + (0,) * (a.ndim - 2))
        out.append(a)
    return tuple(out)


# ------------------------------------------------------------- the rings
# A window layer's part of the slot: [window / block_tokens, Hkv,
# block_tokens, hd] a layer, position ``p`` of the sequence at place ``p mod
# window``, in blocks as :meth:`Paged.read` hands the pages to attention.


def ring_fill(new, window: int, block_tokens: int):
    """A prompt's keys (or values) ``new`` [B, T, Hkv, hd] as the ring a
    prefill leaves: its last ``window`` positions in ring order, zeros at
    the places a shorter prompt has not reached; [B, window / block_tokens,
    Hkv, block_tokens, hd]."""
    B, T, Hkv, hd = new.shape
    if T <= window:
        ring = jnp.pad(new, ((0, 0), (0, window - T), (0, 0), (0, 0)))
    else:       # position T - window lies at place T mod window
        ring = jnp.roll(new[:, T - window:], T % window, axis=1)
    return ring.reshape(B, window // block_tokens, block_tokens, Hkv,
                        hd).transpose(0, 1, 3, 2, 4)


def ring_positions(lengths, window: int):
    """The position each place of a row's ring holds before the row, of
    ``lengths`` [B] positions so far, writes its next: [B, window], negative
    where the place is still empty (all of a pad row's, of length 0). The
    place the next position will take holds the one ``window`` behind it,
    which a window layer no longer sees."""
    last = lengths[:, None] - 1
    return last - (last - jnp.arange(window)) % window


def ring_put(new, lengths, window: int, block_tokens: int) -> Placed:
    """A step's new keys (or values) of the window layers, [L, B, 1, Hkv,
    hd], as what :func:`put_slots` writes: position ``lengths[b]`` of row
    ``b`` at its place of the ring, every layer's in one slice update."""
    place = lengths % window
    zero = jnp.zeros_like(place)
    return Placed(new[:, :, :, :, None, :],
                  jnp.stack([place // block_tokens, zero,
                             place % block_tokens, zero], axis=1))
