"""Token-serving plane: paged KV pool exactness, budget-bounded
admission, continuous-batching correctness vs the one-at-a-time
reference decoder, and the ``/generate`` HTTP contract."""

from __future__ import annotations

import json
import random
import threading
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from demodel_tpu import serve
from demodel_tpu.models import llama
from demodel_tpu.serve import (BlockLease, GenEngine, KVBlockPool,
                               PoolExhausted, QueueOverflow, kvcache)
from demodel_tpu.serve.kvcache import CacheSpec
from demodel_tpu.serve.scheduler import _Seq
from demodel_tpu.utils.metrics import HUB, labeled


@pytest.fixture(scope="module")
def tiny_model():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.key(2), cfg)
    return params, cfg


def _tiny(family):
    """``(module, params, cfg)`` of a model module the engine serves, at
    its test size."""
    from demodel_tpu.models import exaone_moe, phi4flash, qwen3_next

    module, cfg = {
        "llama": (llama, llama.LlamaConfig.tiny()),
        "exaone_moe": (exaone_moe, exaone_moe.ExaoneMoeConfig.tiny()),
        "qwen3_next": (qwen3_next, qwen3_next.Qwen3NextConfig.tiny()),
        "phi4flash": (phi4flash, phi4flash.Phi4FlashConfig.tiny()),
    }[family]
    return module, module.init_params(jax.random.key(2), cfg), cfg


def _pool(cfg, **kw):
    kw.setdefault("block_tokens", 16)
    kw.setdefault("budget_mb", 1)
    return KVBlockPool(CacheSpec(cfg.num_hidden_layers,
                                 cfg.num_key_value_heads, cfg.head_dim), **kw)


def _prompt(cfg, n, seed=0):
    rng = random.Random(seed)
    return [rng.randrange(cfg.vocab_size) for _ in range(n)]


# ---------------------------------------------------------------- KV pool


class TestKVBlockPool:
    def test_blocks_for_rounds_up(self, tiny_model):
        _, cfg = tiny_model
        pool = _pool(cfg, block_tokens=16)
        assert pool.blocks_for(1) == 1
        assert pool.blocks_for(16) == 1
        assert pool.blocks_for(17) == 2
        assert pool.blocks_for(0) == 1  # floor: a sequence owns a block

    def test_alloc_free_exact_under_churn(self, tiny_model):
        """Every alloc/free cycle must account exactly: blocks AND the
        byte budget return to their pre-cycle values, no drift."""
        _, cfg = tiny_model
        pool = _pool(cfg)
        rng = random.Random(7)
        live: list[BlockLease] = []
        for _ in range(400):
            if live and (rng.random() < 0.5 or pool.free_blocks < 4):
                live.pop(rng.randrange(len(live))).free()
            else:
                live.append(pool.alloc(rng.randrange(1, 4)))
            used = sum(len(ls.blocks) for ls in live)
            assert pool.in_use_blocks == used
            assert pool.free_blocks == pool.num_blocks - used
            assert pool.budget.describe()["in_use_bytes"] == \
                used * pool.block_bytes
        for ls in live:
            ls.free()
        assert pool.in_use_blocks == 0
        assert pool.budget.describe()["in_use_bytes"] == 0
        # every block id came home exactly once
        assert sorted(pool._free_list) == list(range(pool.num_blocks))

    def test_alloc_is_all_or_nothing(self, tiny_model):
        _, cfg = tiny_model
        pool = _pool(cfg)
        free = pool.free_blocks
        with pytest.raises(PoolExhausted):
            pool.alloc(free + 1)
        assert pool.free_blocks == free  # no partial grant leaked

    def test_double_free_is_idempotent(self, tiny_model):
        _, cfg = tiny_model
        pool = _pool(cfg)
        lease = pool.alloc(3)
        lease.free()
        lease.free()
        assert pool.in_use_blocks == 0
        assert pool.budget.describe()["in_use_bytes"] == 0

    def test_device_roundtrip_through_a_block_table(self, tiny_model):
        """Prompt blocks written by ``put_blocks``, one position appended
        by ``put_positions``, read back through a block table at ragged
        widths and across block boundaries; a pad row writes nothing a
        lease can hold and reads only what the length mask hides."""
        _, cfg = tiny_model
        L, Hkv, hd = (cfg.num_hidden_layers, cfg.num_key_value_heads,
                      cfg.head_dim)
        pool = _pool(cfg, block_tokens=4)
        rng = np.random.default_rng(3)
        t_a, t_b = 6, 3  # sequence lengths: spans blocks / partial block
        lease_a = pool.alloc(pool.blocks_for(t_a + 2))
        lease_b = pool.alloc(pool.blocks_for(t_b + 2))
        ka = rng.normal(size=(L, 1, t_a, Hkv, hd)).astype(np.float32)
        kb = rng.normal(size=(L, 1, t_b, Hkv, hd)).astype(np.float32)
        put = jax.jit(kvcache.put_blocks, donate_argnums=(0, 1))
        for lease, new, t in ((lease_a, ka, t_a), (lease_b, kb, t_b)):
            ids = np.asarray(lease.blocks[:pool.blocks_for(t)], np.int32)
            pool.arrays = put(pool.k, pool.v,
                              [(new[li], new[li] + 1) for li in range(L)],
                              ids)
        # one position appended to a; row 1 is a pad row: scratch block
        tok = rng.normal(size=(L, 2, 1, Hkv, hd)).astype(np.float32)
        before = np.asarray(pool.k)
        pool.arrays = jax.jit(kvcache.put_positions,
                              donate_argnums=(0, 1))(
            pool.k, pool.v, [(tok[li], tok[li] - 1) for li in range(L)],
            np.asarray([lease_a.blocks[t_a // 4], pool.scratch_block],
                       np.int32),
            np.asarray([t_a % 4, 0], np.int32))
        after = np.asarray(pool.k)
        changed = set(np.flatnonzero(
            (before != after).any(axis=(0, 2, 3, 4))).tolist())
        assert changed == {lease_a.blocks[t_a // 4], pool.scratch_block}
        # rows: a, b, and a pad row (block 0 in every slot, as the
        # scheduler builds it) at a width of two blocks
        table = np.zeros((3, 2), np.int32)
        table[0] = lease_a.blocks[:2]
        table[1, :1] = lease_b.blocks[:1]   # b's missing slot reads block 0

        @jax.jit
        def read(k, v, table):
            cache = kvcache.Paged(k, v, table)
            assert cache.block_tokens == 4
            return [cache.read(li, cache.table) for li in range(L)]

        got = read(pool.k, pool.v, table)
        assert got[0][0].shape == (3, 2, Hkv, 4, hd)  # as the pool holds it

        def positions(a):
            # [B, n, Hkv, bs, hd] -> [B, n * bs, Hkv, hd]
            return np.asarray(a).transpose(0, 1, 3, 2, 4).reshape(
                3, 8, Hkv, hd)

        k = np.stack([positions(lk) for lk, _lv in got])
        v = np.stack([positions(lv) for _lk, lv in got])
        np.testing.assert_array_equal(k[:, 0, :t_a], ka[:, 0])
        np.testing.assert_array_equal(k[:, 0, t_a], tok[:, 0, 0])
        np.testing.assert_array_equal(v[:, 0, t_a], tok[:, 0, 0] - 1)
        np.testing.assert_array_equal(k[:, 1, :t_b], kb[:, 0])
        np.testing.assert_array_equal(v[:, 1, :t_b], kb[:, 0] + 1)
        np.testing.assert_array_equal(k[:, 1, t_b], 0)  # the block's tail
        lease_a.free()
        lease_b.free()

    def test_arrays_live_on_the_device_and_nowhere_else(self, tiny_model):
        """``pool.k`` is a ``jax.Array`` with one scratch block past the
        leasable ones; neither the pool nor an engine over it holds a
        numpy array the size of a block."""
        params, cfg = tiny_model
        pool = _pool(cfg, block_tokens=4)
        engine = GenEngine(params, cfg, pool=pool, max_batch=2)
        try:
            for arr in (pool.k, pool.v):
                assert isinstance(arr, jax.Array)
                assert arr.shape == (cfg.num_hidden_layers,
                                     pool.num_blocks + 1,
                                     cfg.num_key_value_heads, 4,
                                     cfg.head_dim)
                assert arr.dtype == jnp.dtype(cfg.dtype)
                assert arr.sharding == pool.sharding and arr.committed
            assert pool.scratch_block == pool.num_blocks
            assert pool.scratch_block not in pool._free_list
            held = [(type(o).__name__, name) for o in (pool, engine)
                    for name, val in vars(o).items()
                    if isinstance(val, np.ndarray)
                    and val.nbytes >= pool.block_bytes]
            assert held == []
        finally:
            engine.stop()


# ----------------------------------------------------------- scheduler


class TestGenEngine:
    @pytest.mark.parametrize("lengths,news,block,join", [
        ([9, 5, 12, 9], [6] * 4, 16, "third-after-first-is-done"),
        # lengths pass 8 and 16 = the width exactly: the fed position is
        # the one concatenated past the rectangle, in a block the table
        # does not reach yet
        ([7, 3, 14, 7], [12] * 4, 4, "third-after-first-is-done"),
        # who is in the batch changes every step or two, so a row's token
        # comes from another row of the previous step's ids (``src``); the
        # one-token request never decodes; two wait for a row
        ([9, 5, 12, 7, 4, 6], [2, 3, 7, 1, 5, 9], 4, "at-once"),
        # each joins once the one before it streams: an admission between
        # two steps of a full pipe, its first token fed from the host
        ([6, 11, 4, 9], [8, 3, 6, 5], 4, "each-after-a-token"),
        ([5, 8], [9, 4], 16, "each-after-a-token")],
        ids=["staggered", "bucket-edge", "unequal", "join-mid-pipe",
             "pair-parts"])
    def test_matches_one_at_a_time_reference(self, tiny_model, lengths,
                                             news, block, join):
        """Continuous batching, one step ahead of the host, with staggered
        admission must produce the same greedy tokens as the sequential
        reference decoder."""
        params, cfg = tiny_model
        prompts = [_prompt(cfg, n, seed=i) for i, n in enumerate(lengths)]
        refs = [np.asarray(llama.generate(params, cfg, p, n))[0]
                for p, n in zip(prompts, news)]
        before = HUB.snapshot()
        engine = GenEngine(params, cfg, max_batch=3, queue_limit=16,
                           max_new_tokens=max(news), kv_mb=4,
                           block_tokens=block).start()
        try:
            reqs = []
            for i, (p, n) in enumerate(zip(prompts, news)):
                if join == "third-after-first-is-done" and i == 2:
                    reqs[0].result(timeout=120)     # join mid-decode
                if join == "each-after-a-token" and reqs:
                    next(reqs[-1].iter_tokens(timeout=120))
                reqs.append(engine.submit(p, n))
            outs = [r.result(timeout=120) for r in reqs]
        finally:
            engine.stop()
        ahead = labeled("gen_decode_steps_total", ahead="1")
        assert HUB.snapshot()[ahead] > before.get(ahead, 0)
        for out, ref in zip(outs, refs):
            assert out == [int(t) for t in ref]
        assert engine.pool.describe()["in_use_blocks"] == 0

    def test_budget_bounded_admission_no_overcommit(self, tiny_model):
        """A pool sized for two sequences serves four correct requests —
        the extras WAIT for frees rather than overcommitting blocks."""
        params, cfg = tiny_model
        # block_tokens=2048 -> 512 KiB/block for tiny cfg -> 2 blocks/MiB
        pool = _pool(cfg, block_tokens=2048, budget_mb=1)
        assert pool.num_blocks == 2
        max_new = 4
        prompts = [_prompt(cfg, 7, seed=40 + i) for i in range(4)]
        refs = [np.asarray(llama.generate(params, cfg, p, max_new))[0]
                for p in prompts]
        engine = GenEngine(params, cfg, pool=pool, max_batch=4,
                           queue_limit=16, max_new_tokens=max_new).start()
        peak = []
        stop = threading.Event()

        def watch():
            while not stop.is_set():
                peak.append(pool.in_use_blocks)

        t = threading.Thread(target=watch, daemon=True)
        t.start()
        try:
            reqs = [engine.submit(p, max_new) for p in prompts]
            outs = [r.result(timeout=240) for r in reqs]
        finally:
            stop.set()
            t.join(timeout=10)
            engine.stop()
        assert max(peak) <= pool.num_blocks
        for out, ref in zip(outs, refs):
            assert out == [int(t) for t in ref]
        assert pool.in_use_blocks == 0
        assert pool.budget.describe()["in_use_bytes"] == 0

    def test_cancel_evicts_and_frees_blocks(self, tiny_model):
        params, cfg = tiny_model
        engine = GenEngine(params, cfg, max_batch=2, queue_limit=16,
                           max_new_tokens=64, kv_mb=4).start()
        try:
            req = engine.submit(_prompt(cfg, 8), 64)
            for _ in iter(req.iter_tokens(timeout=120)):
                req.cancel()  # first token seen -> evict mid-decode
                break
            with pytest.raises(RuntimeError, match="evicted"):
                req.result(timeout=120)
            assert engine.pool.describe()["in_use_blocks"] == 0
            # a request cancelled while still waiting also settles
            waiting = engine.submit(_prompt(cfg, 8), 4)
            waiting.cancel()
            with pytest.raises(RuntimeError):
                waiting.result(timeout=120)
            assert engine.admission.describe()["outstanding"] == 0
        finally:
            engine.stop()

    def test_cancel_with_a_step_in_flight(self, tiny_model):
        """The evicted sequence's blocks come home at the step boundary,
        before the step it rides has been pulled; its row is computed and
        dropped, and the one beside it decodes the reference's tokens."""
        params, cfg = tiny_model
        engine = GenEngine(params, cfg, max_batch=2, queue_limit=8,
                           max_new_tokens=7, kv_mb=1, block_tokens=4)
        prompts = [_prompt(cfg, 6, seed=1), _prompt(cfg, 9, seed=2)]
        ref = [int(t) for t in
               np.asarray(llama.generate(params, cfg, prompts[1], 7))[0]]
        try:
            gone, kept = _drive(engine, prompts, 7)
            engine._decode_step()
            assert engine._flight is not None
            held = engine.pool.in_use_blocks
            gone.cancel()
            engine._evict_cancelled()
            assert engine.pool.in_use_blocks == held - engine.pool.blocks_for(
                len(prompts[0]) + 7 - 1)
            with pytest.raises(RuntimeError, match="evicted"):
                gone.result(timeout=10)
            assert len(gone.tokens) == 2
            while engine._flight is not None or engine._snapshot_running():
                engine._decode_step()
            assert kept.result(timeout=10) == ref
            assert len(gone.tokens) == 2
            assert engine.pool.in_use_blocks == 0
            assert engine.admission.describe()["outstanding"] == 0
        finally:
            engine.stop()

    @pytest.mark.parametrize("driven", ["by-hand", "by-its-thread"])
    def test_stop_with_a_step_in_flight(self, tiny_model, driven):
        """``stop()`` leaves no block leased and no admission outstanding
        though a step was dispatched and never pulled."""
        params, cfg = tiny_model
        engine = GenEngine(params, cfg, max_batch=2, queue_limit=8,
                           max_new_tokens=64, kv_mb=4)
        prompts = [_prompt(cfg, 8, seed=i) for i in range(3)]
        try:
            if driven == "by-hand":
                reqs = _drive(engine, prompts, 64)
                engine._decode_step()
                assert engine._flight is not None
            else:
                engine.start()
                reqs = [engine.submit(p, 64) for p in prompts]
                for r in reqs[:2]:      # both decode; the third waits
                    for _ in zip(range(3), r.iter_tokens(timeout=120)):
                        pass
        finally:
            engine.stop()
        for r in reqs:
            with pytest.raises(RuntimeError, match="shutdown"):
                r.result(timeout=10)
        assert engine.pool.describe()["in_use_blocks"] == 0
        assert engine.admission.describe()["outstanding"] == 0

    def test_queue_overflow_raises_with_retry_after(self, tiny_model):
        params, cfg = tiny_model
        engine = GenEngine(params, cfg, max_batch=1, queue_limit=2,
                           max_new_tokens=4, kv_mb=4)  # NOT started
        try:
            for _ in range(2):
                engine.submit(_prompt(cfg, 4), 2)
            with pytest.raises(QueueOverflow) as exc:
                engine.submit(_prompt(cfg, 4), 2)
            assert exc.value.retry_after >= 1
        finally:
            engine.stop()

    def test_submit_validates_before_reserving(self, tiny_model):
        params, cfg = tiny_model
        engine = GenEngine(params, cfg, max_batch=1, queue_limit=2,
                           max_new_tokens=4, kv_mb=4)
        try:
            with pytest.raises(ValueError):
                engine.submit([], 2)
            with pytest.raises(ValueError):
                engine.submit([cfg.vocab_size], 2)
            assert engine.admission.describe()["outstanding"] == 0
        finally:
            engine.stop()

    def test_submit_rejects_request_larger_than_pool(self, tiny_model):
        """A worst-case reservation larger than the whole pool can never
        be admitted — reject at submit() (→ HTTP 400) instead of wedging
        the FIFO head forever while the engine spins."""
        params, cfg = tiny_model
        pool = _pool(cfg, block_tokens=2048, budget_mb=1)
        assert pool.num_blocks == 2
        capacity = pool.num_blocks * pool.block_tokens
        engine = GenEngine(params, cfg, pool=pool, max_batch=2,
                           queue_limit=8,
                           max_new_tokens=capacity + 64).start()
        try:
            with pytest.raises(ValueError, match="KV blocks"):
                engine.submit(_prompt(cfg, 8), capacity + 8)
            assert engine.admission.describe()["outstanding"] == 0
            # the plane still serves: a sane request right behind it
            out = engine.generate(_prompt(cfg, 7, seed=3), 3, timeout=240)
            assert len(out) == 3
        finally:
            engine.stop()
        assert pool.in_use_blocks == 0

    def test_cancel_between_alloc_and_start_frees_lease(self, tiny_model):
        """The narrowest cancel race: cancel() lands while _admit_one
        holds a freshly allocated lease — the lease must be freed, not
        dropped (a silent, permanent capacity leak otherwise)."""
        params, cfg = tiny_model
        engine = GenEngine(params, cfg, max_batch=2, queue_limit=8,
                           max_new_tokens=8, kv_mb=4)  # never started:
        req = engine.submit(_prompt(cfg, 6), 4)  # we drive _admit_one
        real_alloc = engine.pool.alloc

        def alloc_then_cancel(need):
            lease = real_alloc(need)
            req.cancel()  # lands after the alloc, before the start
            return lease

        engine.pool.alloc = alloc_then_cancel
        try:
            assert engine._admit_one() is True
            with pytest.raises(RuntimeError, match="cancelled"):
                req.result(timeout=10)
            assert engine.pool.in_use_blocks == 0
            assert engine.pool.budget.describe()["in_use_bytes"] == 0
            assert engine.admission.describe()["outstanding"] == 0
        finally:
            engine.pool.alloc = real_alloc
            engine.stop()

    def test_stop_settles_pending_requests(self, tiny_model):
        params, cfg = tiny_model
        engine = GenEngine(params, cfg, max_batch=1, queue_limit=8,
                           max_new_tokens=4, kv_mb=4)  # never started
        req = engine.submit(_prompt(cfg, 4), 2)
        engine.stop()
        with pytest.raises(RuntimeError, match="shutdown"):
            req.result(timeout=10)
        assert engine.admission.describe()["outstanding"] == 0
        with pytest.raises(RuntimeError, match="stopped"):
            engine.submit(_prompt(cfg, 4), 2)


# ------------------------------------------------- the pool on the device


def _drive(engine, prompts, max_new):
    """Admit ``prompts`` on a never-started engine, by hand."""
    reqs = [engine.submit(p, max_new) for p in prompts]
    while engine._admit_one():
        pass
    return reqs


def _pool_bytes(pool):
    return np.asarray(pool.k), np.asarray(pool.v)


class TestDevicePool:
    """The arrays never leave the device: what a step may and may not
    write, what crosses the link, and what a failed program leaves."""

    def test_pad_row_and_missing_slot_change_no_leased_block(self,
                                                             tiny_model):
        """B = 3 in a bucket of 4, ragged lengths at a width the short
        rows' leases do not fill: the step, and the step dispatched ahead
        of its pull, write each row's one new position each and, for the
        pad row, the scratch block. Block 0 (what a missing table slot
        reads) belongs to a bystander."""
        params, cfg = tiny_model
        pool = _pool(cfg, block_tokens=4)
        engine = GenEngine(params, cfg, pool=pool, max_batch=4,
                           queue_limit=8, max_new_tokens=4)
        bystander = pool.alloc(2)
        assert bystander.blocks == [0, 1]
        fill = jnp.full((cfg.num_hidden_layers, 1, 8,
                         cfg.num_key_value_heads, cfg.head_dim), 7.0)
        pool.arrays = jax.jit(kvcache.put_blocks,
                              donate_argnums=(0, 1))(
            pool.k, pool.v, [(a, a) for a in fill],
            np.asarray(bystander.blocks, np.int32))
        try:
            _drive(engine, [_prompt(cfg, n, seed=n) for n in (3, 9, 18)], 3)
            seqs = engine._snapshot_running()
            assert len(seqs) == 3
            k0, v0 = _pool_bytes(pool)
            engine._decode_step()
            k1, v1 = _pool_bytes(pool)
            assert engine._flight is not None and engine._flight.ahead
            wrote = {(s.lease.blocks[at // 4], at % 4) for s in seqs
                     for at in (s.length - 2, s.length - 1)}
            for before, after in ((k0, k1), (v0, v1)):
                diff = (before != after).any(axis=(0, 2, 4))  # [blk, slot]
                got = {(int(b), int(o)) for b, o in np.argwhere(diff)}
                assert wrote <= got <= wrote | {(pool.scratch_block, 0)}
                for blk in bystander.blocks:
                    np.testing.assert_array_equal(after[:, blk], 7.0)
        finally:
            engine.stop()
            bystander.free()
        assert pool.in_use_blocks == 0

    def test_released_blocks_leak_nothing_into_the_next_lease(self,
                                                              tiny_model):
        """A pool one long sequence fills: the next sequence gets those
        blocks back, stale bytes and all, and decodes the reference's
        tokens (the length mask hides every position it did not write)."""
        params, cfg = tiny_model
        pool = _pool(cfg, block_tokens=4, budget_mb=1)
        pool._free_list = pool._free_list[-8:]     # eight blocks to share
        pool.num_blocks = 8
        long_, short = _prompt(cfg, 22, seed=1), _prompt(cfg, 5, seed=2)
        refs = [np.asarray(llama.generate(params, cfg, p, n))[0]
                for p, n in ((long_, 6), (short, 9))]
        engine = GenEngine(params, cfg, pool=pool, max_batch=2,
                           queue_limit=8, max_new_tokens=9).start()
        try:
            first = engine.generate(long_, 6, timeout=240)
            assert np.asarray(pool.k)[:, :8].any(axis=(0, 2, 3, 4)).sum() >= 6
            second = engine.generate(short, 9, timeout=240)
        finally:
            engine.stop()
        assert first == [int(t) for t in refs[0]]
        assert second == [int(t) for t in refs[1]]
        assert pool.in_use_blocks == 0

    def test_programs_follow_the_shape_not_the_reservation(self, tiny_model):
        """One prompt length at three ``max_new_tokens`` (so three lease
        sizes): one prefill executable, and as many decode executables as
        (batch bucket, width) pairs — what ``gen_new_shapes_total``
        counts, so that a warm-up by shape reaches every program. Up to
        two tiles a row there is one width, so one program a bucket."""
        params, cfg = tiny_model
        before = HUB.snapshot()
        engine = GenEngine(params, cfg, max_batch=1, queue_limit=8,
                           max_new_tokens=12, kv_mb=1,
                           block_tokens=4).start()
        try:
            for new in (2, 5, 11):      # leases of 2, 3 and 5 blocks
                assert len(engine.generate(_prompt(cfg, 7), new,
                                           timeout=240)) == new
        finally:
            engine.stop()
        after = HUB.snapshot()

        def shapes(stage):
            name = labeled("gen_new_shapes_total", stage=stage)
            return after[name] - before.get(name, 0)

        assert engine._jprefill._cache_size() == shapes("prefill") == 1
        # lengths 7..17 at batch 1 cross two doublings of the row's blocks
        assert engine._jdecode._cache_size() == shapes("decode") == 1

    def test_a_step_ships_a_table_and_pulls_back_ids(self, tiny_model):
        """Three tokens a request, so two decode steps: the first cycle
        ships both tables (the step, and the one it dispatches ahead) and
        pulls one step's ids, the second ships nothing and pulls the
        other's. The logits never cross."""
        params, cfg = tiny_model
        engine = GenEngine(params, cfg, max_batch=3, queue_limit=8,
                           max_new_tokens=4, kv_mb=1, block_tokens=4)
        names = ("gen_h2d_bytes_total", "gen_d2h_bytes_total",
                 labeled("gen_decode_steps_total", ahead="0"),
                 labeled("gen_decode_steps_total", ahead="1"))
        try:
            _drive(engine, [_prompt(cfg, n, seed=n) for n in (3, 9, 18)], 3)
            seen = [HUB.snapshot()]
            for _ in range(2):
                engine._decode_step()
                seen.append(HUB.snapshot())
            assert engine._flight is None and not engine._snapshot_running()
        finally:
            engine.stop()
        cycles = [[b[n] - a[n] for n in names] for a, b in zip(seen, seen[1:])]
        # a bucket of 4 rows (= _pow2(max_batch)); length 18: 5 blocks in a
        # table of two tiles; a row: token, length, write block and offset,
        # src, its 32 slots
        table, ids = 4 * (5 + 32) * 4, 4 * 4
        assert cycles == [[2 * table, ids, 1, 0], [0, ids, 0, 1]]
        assert engine.pool.in_use_blocks == 0

    def test_a_wide_step_books_the_tiles_its_rows_have_filled(self,
                                                              tiny_model):
        """Blocks of 2 positions, so a tile holds 32: rows of 70, 40 and 5
        positions at a width of 256 slots (sixteen tiles a row) read 3 + 2 +
        1 tiles where the bucket of four rows is 2 048 positions wide, and
        the step dispatched ahead of it the same. Every cycle's span names both
        numbers, the counters are their sums, and ``/statusz`` has them
        beside the other ``gen_`` counters. The tokens are the reference's
        (the tiles, read where they lie, through a llama's 2 layers)."""
        from demodel_tpu.utils import statusz, trace

        params, cfg = tiny_model
        engine = GenEngine(params, cfg, max_batch=3, queue_limit=8,
                           max_new_tokens=4, kv_mb=1, block_tokens=2)
        names = ("gen_kv_positions_width_total",
                 "gen_kv_positions_read_total")
        prompts = [_prompt(cfg, n, seed=n) for n in (70, 40, 5)]
        trace.reset()
        trace.enable()
        try:
            before = HUB.snapshot()
            reqs = _drive(engine, prompts, 3)
            for _ in range(2):
                engine._decode_step()
            after = HUB.snapshot()
            steps = [r["attrs"] for r in trace.buffer().snapshot()
                     if r["name"] == "serve.decode-step"]
            counters = statusz.snapshot()["counters"]
        finally:
            trace.reset()
            engine.stop()
        assert [(a["width"], a["kv_positions_width"], a["kv_positions_read"])
                for a in steps] == [(512, 2048, 6 * 32)] * 2
        assert [after[n] - before[n] for n in names] == [4096, 2 * 6 * 32]
        assert all(counters[n] >= after[n] - before[n] for n in names)
        for req, prompt in zip(reqs, prompts):
            assert req.result(5) == np.asarray(llama.generate(
                params, cfg, jnp.asarray([prompt]), 3))[0].tolist()

    @pytest.mark.parametrize("family,platform,longest,pool,in_place", [
        ("axk1", "tpu", 70, {}, True), ("axk1", "cpu", 70, {}, False),
        ("axk1", "tpu", 18, {}, False), ("llama", "tpu", 70, {}, True),
        ("llama", "cpu", 70, {}, False), ("llama", "tpu", 18, {}, False),
        ("llama", "tpu", 70, {"meshed": True}, False),
        ("llama", "tpu", 70,
         {"tile_bytes": kvcache.KERNEL_BYTES // 4 + 1}, False)],
        ids=["a-latent-page-on-a-tpu", "on-the-cpu", "narrow",
             "keys-and-values-apart-on-a-tpu",
             "keys-and-values-apart-on-the-cpu",
             "keys-and-values-apart-narrow",
             "keys-and-values-apart-on-several-chips",
             "keys-and-values-apart-a-tile-past-the-buffers"])
    def test_a_step_books_the_positions_it_reads_in_place(
            self, family, platform, longest, pool, in_place):
        """``kv_positions_in_place`` on ``serve.decode-step``: the
        positions of ``kv_positions_read`` the step's attention reads from
        the pool itself, with no gathered copy. All of them where the
        kernel that follows the filled tiles is the step's path: a page of
        one array under one cached head (A.X-K1's absorbed step), a wide
        table, programs lowered for a TPU; none on
        the CPU, where the loop gathers a chunk a trip, for a table of two
        tiles, or for a page of keys and values apart. The platform is the
        pool's devices'; the test says ``tpu`` in its place (the programs
        it runs are the CPU's: what is held here is the host's count).
        Counted in ``gen_kv_positions_in_place_total``, which ``/statusz``
        has."""
        from demodel_tpu.models import axk1
        from demodel_tpu.utils import statusz, trace

        if family == "axk1":
            cfg = axk1.AxK1Config.tiny(num_attention_heads=32)
            params = axk1.init_params(jax.random.key(2), cfg)
        else:
            _module, params, cfg = _tiny(family)
        engine = GenEngine(params, cfg, max_batch=3, queue_limit=8,
                           max_new_tokens=4, kv_mb=1, block_tokens=2)
        assert engine.pool.platform == "cpu" and engine.pool.meshed is None
        engine.pool.platform = platform
        for name, value in pool.items():
            setattr(engine.pool, name, value)
        trace.reset()
        trace.enable()
        try:
            before = HUB.snapshot()
            _drive(engine, [_prompt(cfg, n, seed=n)
                            for n in (longest, 9, 5)], 3)
            for _ in range(2):
                engine._decode_step()
            after = HUB.snapshot()
            steps = [r["attrs"] for r in trace.buffer().snapshot()
                     if r["name"] == "serve.decode-step"]
            counters = statusz.snapshot()["counters"]
        finally:
            trace.reset()
            engine.stop()
        # 3 + 1 + 1 tiles of 32 positions of a wide table, all of a narrow
        read = (3 + 1 + 1) * 32 if longest == 70 else 4 * 64
        want = read if in_place else 0
        assert [(a["kv_positions_read"], a["kv_positions_in_place"])
                for a in steps] == [(read, want)] * 2
        name = "gen_kv_positions_in_place_total"
        assert after[name] - before[name] == 2 * want
        assert name in counters and counters[name] >= 2 * want

    @pytest.mark.parametrize("family,platform", [
        ("exaone_moe", "tpu"), ("exaone_moe", "cpu"), ("qwen3_next", "tpu"),
        ("llama", "tpu")],
        ids=["held-experts-on-a-tpu", "on-the-cpu", "a-second-family",
             "no-expert-layer"])
    def test_a_step_books_the_experts_its_products_read(self, family,
                                                        platform):
        """``expert_reads`` on ``serve.decode-step`` and
        ``serve.prefill-device``: the experts' weights the step's grouped
        products fetch where its program holds the kernel that streams
        each hit expert once (``ops/grouped.py``: programs lowered for a
        TPU): the kernel's visits for the step's ``expert_tokens``, from
        the same tiling rule, so ``expert_reads`` over ``experts_hit`` is
        1.0 while a row tile holds every landed row (these tiny steps); 0
        on the CPU, where the products are ``lax.ragged_dot``. The
        platform is the pool's devices'; the test says ``tpu`` in its
        place (the programs it runs are the CPU's: what is held here is
        the host's count). Counted in ``gen_moe_expert_reads_total``,
        which ``/statusz`` has. A family with no expert layer names
        neither."""
        from demodel_tpu.utils import statusz, trace

        _module, params, cfg = _tiny(family)
        engine = GenEngine(params, cfg, max_batch=3, queue_limit=8,
                           max_new_tokens=4, kv_mb=1, block_tokens=2)
        assert engine.pool.platform == "cpu"
        engine.pool.platform = platform
        name = "gen_moe_expert_reads_total"
        trace.reset()
        trace.enable()
        try:
            before = HUB.snapshot()[name]
            _drive(engine, [_prompt(cfg, n, seed=n) for n in (18, 9, 5)], 3)
            for _ in range(2):
                engine._decode_step()
            after = HUB.snapshot()[name]
            spans = [r["attrs"] for r in trace.buffer().snapshot()
                     if r["name"] in ("serve.decode-step",
                                      "serve.prefill-device")]
            counters = statusz.snapshot()["counters"]
        finally:
            trace.reset()
            engine.stop()
        assert len(spans) == 3 + 2
        if family == "llama":
            assert not any("expert_reads" in a for a in spans)
            assert after == before
            return
        assert all(a["experts_hit"] > 0 for a in spans)
        want = [a["experts_hit"] if platform == "tpu" else 0 for a in spans]
        assert [a["expert_reads"] for a in spans] == want
        assert after - before == sum(want)
        assert name in counters and counters[name] >= sum(want)

    @pytest.mark.parametrize("longest,slots", [
        (1, 32), (2, 32), (3, 32), (9, 32), (31, 32), (32, 32), (33, 32),
        (64, 32), (65, 256), (300, 256), (512, 256), (513, 2048),
        (3000, 2048), (4096, 2048), (4097, 16384)])
    def test_the_width_follows_the_longest_row(self, tiny_model, longest,
                                               slots):
        """Blocks of 2 positions, a tile of 32: two tiles a row for every
        longest row up to two tiles, whatever it holds, past that two tiles
        times a power of eight, on both sides of each boundary. The table
        starts at column 5 of the rows; a slot past the lease names block
        0."""
        params, cfg = tiny_model
        engine = GenEngine(params, cfg, max_batch=2, queue_limit=8,
                           max_new_tokens=4, kv_mb=3, block_tokens=2)
        lease = engine.pool.alloc(longest // 2 + 1)
        short = engine.pool.alloc(1)
        try:
            width, rows = engine._decode_inputs(
                [_Seq(None, short, 1, 1), _Seq(None, lease, longest, 1)])
        finally:
            lease.free()
            short.free()
            engine.stop()
        assert kvcache.table_slots(-(-longest // 2)) == slots
        assert (width, rows.shape) == (2 * slots, (2, 5 + slots))
        held = min(len(lease.blocks), slots)
        assert rows[1, 5:5 + held].tolist() == lease.blocks[:held]
        assert not rows[1, 5 + held:].any() and not rows[0, 6:].any()

    @staticmethod
    def _decode_programs(params, cfg, max_batch, waves):
        """Serve ``waves`` of prompt lengths one after another at blocks of
        2 positions, every request 3 tokens, held to the reference's:
        the decode shapes the engine ran, and the decode programs it made
        ready by its counter and by its cache."""
        engine = GenEngine(params, cfg, max_batch=max_batch, queue_limit=8,
                           max_new_tokens=4, kv_mb=1, block_tokens=2)
        name = labeled("gen_new_shapes_total", stage="decode")
        before = HUB.snapshot()
        try:
            for wave in waves:
                prompts = [_prompt(cfg, n, seed=n) for n in wave]
                reqs = _drive(engine, prompts, 3)
                while engine._flight is not None \
                        or engine._snapshot_running():
                    engine._decode_step()
                for req, prompt in zip(reqs, prompts):
                    assert req.result(5) == np.asarray(llama.generate(
                        params, cfg, jnp.asarray([prompt]), 3))[0].tolist()
            shapes = sorted(s for s in engine._shapes_run
                            if s[0] == "decode")
            made = HUB.snapshot()[name] - before.get(name, 0)
        finally:
            engine.stop()
        assert engine.pool.in_use_blocks == 0
        return shapes, made, engine._jdecode._cache_size()

    def test_past_two_tiles_a_bucket_runs_one_program(self, tiny_model):
        """Blocks of 2 positions (a tile holds 32, the widths past two
        tiles are 512 and 4 096 positions): pairs whose longer row holds
        75, 190 and 375 positions, then one row of 120 alone, all run at a
        width of 512, so the engine makes ready one decode program a batch
        bucket where a width by powers of two made ready 256 and 512 for
        the pairs alone. The tokens are the reference's: rows of unequal
        length through the tiles."""
        shapes, made, cached = self._decode_programs(
            *tiny_model, 2, ((75, 40), (190, 9), (375, 130), (120,)))
        assert shapes == [("decode", 1, 512), ("decode", 2, 512)]
        assert made == cached == 2

    def test_up_to_two_tiles_a_bucket_runs_one_program(self, tiny_model):
        """Blocks of 2 positions (two tiles hold 64): three rows whose
        longest holds 3, 9, 20 and 60 positions, then one row of 13 alone,
        all run at a width of 64, so the engine makes ready one decode
        program a batch bucket where a width by powers of two made ready
        one for every doubling of the longest row. The tokens are the
        reference's: rows of unequal length in the rectangle, which masks
        what a row does not own, and a pad row beside every three."""
        shapes, made, cached = self._decode_programs(
            *tiny_model, 3, ((3, 2, 1), (9, 4, 2), (20, 11, 5),
                             (60, 33, 17), (13,)))
        assert shapes == [("decode", 1, 64), ("decode", 4, 64)]
        assert made == cached == 2

    @pytest.mark.parametrize("name", ["exaone_moe", "qwen3_next",
                                      "phi4flash"])
    def test_every_family_reads_two_tiles_whatever_its_rows_hold(self, name):
        """The families whose step over a narrow table is not the llama's
        (``exaone_moe``'s window layers take their 5 slots as a slice of
        the table's 32, ``qwen3_next`` and ``phi4flash`` keep slots beside
        the pages): rows of 20, 9 and 3 positions and a pad row through
        one program of 32 slots of 2 positions. Every token served is the
        first choice of the module's own prefill over the sequence so far,
        which reads no table."""
        from demodel_tpu.models import exaone_moe

        module, params, cfg = _tiny(name)
        assert exaone_moe.window_slots(8, 2) == 5
        engine = GenEngine(params, cfg, max_batch=3, queue_limit=8,
                           max_new_tokens=4, kv_mb=4, block_tokens=2)
        prompts = [_prompt(cfg, n, seed=n) for n in (20, 9, 3)]
        try:
            reqs = _drive(engine, prompts, 4)
            while engine._flight is not None or engine._snapshot_running():
                engine._decode_step()
            outs = [req.result(5) for req in reqs]
            shapes = {s for s in engine._shapes_run if s[0] == "decode"}
        finally:
            engine.stop()
        assert shapes == {("decode", 4, 64)}
        first = jax.jit(
            lambda tokens: module.step_prefill(params, tokens, cfg)[0])
        for prompt, out in zip(prompts, outs):
            for i, token in enumerate(out):
                logits = first(jnp.asarray([prompt + out[:i]]))
                assert int(np.asarray(logits)[0].argmax()) == token
        kv = engine.pool.describe()
        assert kv["in_use_blocks"] == 0 and not kv.get("in_use_slots")

    def test_every_family_is_handed_the_pool_and_its_table(self,
                                                           monkeypatch):
        """A family made here of two step functions: its ``step_decode``
        gets a ``kvcache.Paged`` (the pool's arrays, the batch's block
        table, and whether the pool lies on several chips, which a module
        never reads: ``kvcache._in_place``) and nothing else, with no word from the module about how it
        wants its cache. The "model" keeps a token's id as its key and
        chooses the sum of the row's live cached keys and the fed token:
        right only if the table names the row's own blocks, in order, and
        the lengths mask what lies past them."""
        import sys
        import types
        from dataclasses import dataclass

        V = 64

        @dataclass(frozen=True)
        class StubConfig:
            vocab_size: int = V
            num_hidden_layers: int = 1
            num_key_value_heads: int = 1
            head_dim: int = 2
            dtype: str = "float32"

        def kv_of(tokens):      # [B, T] -> one layer's (k, v) [B, T, 1, 2]
            k = jnp.broadcast_to(
                tokens[..., None, None].astype(jnp.float32),
                (*tokens.shape, 1, 2))
            return [(k, -k)]

        def step_prefill(params, tokens, cfg, mesh=None):
            return jax.nn.one_hot(tokens.sum(axis=1) % V, V), kv_of(tokens)

        handed = []

        def step_decode(params, tokens, cfg, cache, lengths, mesh=None):
            handed.append((type(cache), cache.k.shape, cache.table.shape))
            B, n = cache.table.shape
            pk, _pv = cache.read(0, cache.table)    # [B, n, 1, bs, 2]
            live = jnp.arange(n * cache.block_tokens)[None] \
                < lengths[:, None]
            past = jnp.where(live, pk[:, :, 0, :, 0].reshape(B, -1), 0)
            chosen = (past.sum(axis=1).astype(jnp.int32) + tokens) % V
            return jax.nn.one_hot(chosen, V), kv_of(tokens[:, None])

        family = types.ModuleType("stub_family")
        family.step_prefill, family.step_decode = step_prefill, step_decode
        family.cache_spec = lambda cfg: CacheSpec(
            cfg.num_hidden_layers, cfg.num_key_value_heads, cfg.head_dim)
        monkeypatch.setitem(sys.modules, "stub_family", family)
        StubConfig.__module__ = "stub_family"
        engine = GenEngine({"embed": jnp.zeros((V, 2))}, StubConfig(),
                           max_batch=3, queue_limit=8, max_new_tokens=8,
                           kv_mb=1, block_tokens=4).start()
        try:
            prompts = [_prompt(StubConfig(), n, seed=n) for n in (3, 9, 6)]
            reqs = [engine.submit(p, 7) for p in prompts]
            outs = [r.result(timeout=240) for r in reqs]
        finally:
            engine.stop()
        for prompt, out in zip(prompts, outs):
            total, want = sum(prompt), []
            for _ in range(7):
                want.append(total % V)
                total += want[-1]
            assert out == want
        pool = engine.pool
        assert pool.in_use_blocks == 0
        assert kvcache.Paged._fields == ("k", "v", "table", "state", "slots",
                                         "meshed")
        assert handed and all(
            kind is kvcache.Paged and shape == pool.k.shape
            and table[0] in (1, 2, 4)
            for kind, shape, table in handed)

    def test_pool_is_sharded_on_the_kv_heads_under_tp(self, tiny_model):
        """Two CPU devices, ``tp`` = 2 = the toy's KV heads: the arrays
        are split on that axis and the engine serves what the unsharded
        engine serves (float32, so no near-tie flips)."""
        from demodel_tpu.parallel.mesh import make_mesh

        params, cfg = tiny_model
        mesh = make_mesh(2)
        placed = jax.device_put(params, llama.param_shardings(cfg, mesh))
        prompts = [_prompt(cfg, n, seed=30 + n) for n in (9, 5, 12)]
        outs = []
        for p, m in ((params, None), (placed, mesh)):
            engine = GenEngine(p, cfg, mesh=m, max_batch=2, queue_limit=8,
                               max_new_tokens=6, kv_mb=1).start()
            try:
                reqs = [engine.submit(q, 6) for q in prompts]
                outs.append([r.result(timeout=240) for r in reqs])
                pool = engine.pool
                if m is not None:
                    assert pool.k.sharding.spec == P(None, None, "tp",
                                                     None, None)
                    assert len(pool.k.sharding.device_set) == 2
                    heads = {s.data.shape[2]
                             for s in pool.k.addressable_shards}
                    assert heads == {cfg.num_key_value_heads // 2}
                # on several chips a wide step keeps its loop
                # (kvcache._in_place)
                assert pool.meshed is (None if m is None else True)
                assert pool.k.sharding == pool.sharding
            finally:
                engine.stop()
            assert pool.in_use_blocks == 0
        assert outs[0] == outs[1]
        # KV heads that do not divide tp: replicated, as _head_align says
        odd = KVBlockPool(CacheSpec(2, 3, 8), block_tokens=4, budget_mb=1,
                          mesh=mesh)
        # a copy a chip is a program of two chips still
        assert odd.k.sharding.is_fully_replicated and odd.meshed is True
        assert odd.tile_bytes == kvcache.TILE_BLOCKS * 3 * 4 * 8 * 4

    @pytest.mark.parametrize("stage", ["prefill", "decode", "decode-ahead",
                                       "decode-pull", "prefill-ahead",
                                       "prefill-pull"])
    def test_a_program_that_fails_with_the_pool_in_hand(self, tiny_model,
                                                        monkeypatch, stage):
        """The program deletes what it was given (as donation does) and
        raises: every running sequence is retired with the error, every
        lease comes home, and the next request is served from fresh
        arrays. With a step in flight the failure is the dispatch behind
        it, or (as a device reports one) the wait for its ids, its
        successor already queued: both steps' sequences are retired and
        the pipe is dropped. So with a prefill that rides the pipe: it
        fails where it is dispatched, in the cycle that is open, or at the
        pull of its id, with the step that carries its row queued behind
        it."""
        params, cfg = tiny_model
        engine = GenEngine(params, cfg, max_batch=3, queue_limit=8,
                           max_new_tokens=6, kv_mb=1, block_tokens=4)
        stage, _, flight = stage.partition("-")
        real = getattr(engine, f"_j{stage}")

        def boom(*args):
            args[-2].delete()
            args[-1].delete()
            raise RuntimeError("device fell over")

        prompt = _prompt(cfg, 9, seed=4)
        ref = [int(t) for t in
               np.asarray(llama.generate(params, cfg, prompt, 5))[0]]
        try:
            reqs = _drive(engine, [_prompt(cfg, 6), _prompt(cfg, 11)], 6)
            assert len(engine._snapshot_running()) == 2
            if flight:
                engine._decode_step()
                assert engine._flight.ahead
                assert [len(r.tokens) for r in reqs] == [2, 2]
            if stage == "prefill" and flight == "pull":
                real_get = jax.device_get

                def fell_over(outs):    # the step's ids come, the id not
                    if outs[0].shape == (1,):
                        raise RuntimeError("device fell over")
                    return real_get(outs)

                monkeypatch.setattr(jax, "device_get", fell_over)
            elif flight == "pull":
                def fell_over(_outs):
                    monkeypatch.undo()
                    raise RuntimeError("device fell over")

                monkeypatch.setattr(jax, "block_until_ready", fell_over)
            else:
                setattr(engine, f"_j{stage}", boom)
            if stage == "prefill" and flight:
                reqs.append(engine.submit(_prompt(cfg, 4), 6))
                assert engine._turn()
                assert len(reqs[-1].tokens) == 0
                monkeypatch.undo()
            elif stage == "prefill":
                reqs += _drive(engine, [_prompt(cfg, 4)], 6)
            else:
                engine._decode_step()
            setattr(engine, f"_j{stage}", real)
            assert engine._flight is None
            assert engine._prev_ids is engine._ids0
            for r in reqs:
                with pytest.raises(RuntimeError, match="device fell over"):
                    r.result(timeout=10)
            assert engine._snapshot_running() == []
            assert engine.pool.in_use_blocks == 0
            assert engine.pool.budget.describe()["in_use_bytes"] == 0
            assert engine.admission.describe()["outstanding"] == 0
            assert not engine.pool.lost
            assert not np.asarray(engine.pool.k).any()
            engine.start()
            assert engine.generate(prompt, 5, timeout=240) == ref
        finally:
            engine.stop()
        assert engine.pool.in_use_blocks == 0

    def test_a_failure_before_the_pool_was_taken_spares_the_others(
            self, tiny_model):
        """A prefill that fails while the arrays are still the pool's
        (tracing, compilation) costs its own request alone."""
        params, cfg = tiny_model
        engine = GenEngine(params, cfg, max_batch=3, queue_limit=8,
                           max_new_tokens=6, kv_mb=1, block_tokens=4)
        prompt = _prompt(cfg, 6)
        ref = [int(t) for t in
               np.asarray(llama.generate(params, cfg, prompt, 6))[0]]
        real = engine._jprefill

        def refuse(*_args):
            raise RuntimeError("no such shape")

        try:
            kept = _drive(engine, [prompt], 6)[0]
            engine._jprefill = refuse
            lost = _drive(engine, [_prompt(cfg, 4)], 6)[0]
            engine._jprefill = real
            with pytest.raises(RuntimeError, match="no such shape"):
                lost.result(timeout=10)
            assert len(engine._snapshot_running()) == 1
            engine.start()
            assert kept.result(timeout=240) == ref
        finally:
            engine.stop()
        assert engine.pool.in_use_blocks == 0


class _Compiles:
    """Every XLA compilation and every persistent-cache hit, as
    ``benchmark/run.py``'s ``Compiles`` hears them."""

    events: list[str] = []
    registered = False

    @classmethod
    def listen(cls) -> list[str]:
        if not cls.registered:
            jax.monitoring.register_event_duration_secs_listener(
                lambda event, _secs, **_kw: cls.events.append(event)
                if event == "/jax/core/compile/backend_compile_duration"
                else None)
            jax.monitoring.register_event_listener(
                lambda event, **_kw: cls.events.append(event)
                if event == "/jax/compilation_cache/cache_hits" else None)
            cls.registered = True
        return cls.events


class TestOneSignatureForLife:
    """The benchmark's warm-up-then-window protocol on the CPU: a fresh
    engine under a 1-device ``tp`` mesh with mesh-placed weights (as
    ``load_model`` gives them) runs every shape once, then again; the
    second round must find every executable it needs. A pool with two
    signatures (fresh against returned-by-a-program) compiles there.
    Run for both model modules the engine serves: the pool, its donation
    and the programs' signatures are the engine's, whatever the steps."""

    @pytest.fixture(params=["llama", "exaone_moe", "qwen3_next"])
    def placed(self, request):
        from demodel_tpu.parallel.mesh import make_mesh

        module, params, cfg = _tiny(request.param)
        mesh = make_mesh(1)
        return (jax.device_put(params, module.param_shardings(cfg, mesh)),
                cfg, mesh)

    @pytest.fixture(params=["observe", "export"])
    def tier(self, request, monkeypatch):
        from demodel_tpu.utils import trace

        for var in ("DEMODEL_TRACE", "DEMODEL_TRACE_SAMPLE", "DEMODEL_OBS"):
            monkeypatch.delenv(var, raising=False)
        trace.reset()
        if request.param == "export":
            trace.enable()
        assert trace.mode() == request.param
        yield request.param
        trace.reset()

    def test_score_path_prefills_compile_once(self, placed, tier):
        """One-token requests of three prompt lengths once each (the
        harness's warm-up), then the same lengths in another order (the
        window): no compilation, no cache hit, three executables, the
        same tokens."""
        params, cfg, mesh = placed
        events = _Compiles.listen()
        lengths = [24, 40, 72]
        engine = GenEngine(params, cfg, mesh=mesh, max_batch=2,
                           queue_limit=8, max_new_tokens=4, kv_mb=1).start()
        try:
            first = {n: engine.generate(_prompt(cfg, n, seed=n), 1,
                                        timeout=240) for n in lengths}
            mark = len(events)
            again = {n: engine.generate(_prompt(cfg, n, seed=n), 1,
                                        timeout=240)
                     for n in (lengths[1], lengths[2], lengths[0],
                               lengths[1])}
        finally:
            engine.stop()
        assert events[mark:] == []
        assert engine._jprefill._cache_size() == 3
        assert engine._jdecode._cache_size() == 0
        assert again == first
        assert engine.pool.describe()["in_use_blocks"] == 0

    def test_decode_shapes_compile_once(self, placed, tier):
        """Each (batch bucket, width) from a fresh pool and again later:
        one executable each, none made in the second round. A step
        dispatched on an empty pipe and one dispatched ahead run the same
        one, and so does the step that takes the ids the engine was born
        with for the previous step's."""
        params, cfg, mesh = placed
        events = _Compiles.listen()
        before = HUB.snapshot()
        engine = GenEngine(params, cfg, mesh=mesh, max_batch=2,
                           queue_limit=8, max_new_tokens=12, kv_mb=1,
                           block_tokens=4)
        launched = []
        real = engine._launch

        def watched(step):
            launched.append(((len(step.rows), step.width), step.ahead,
                             engine._prev_ids is engine._ids0))
            real(step)

        engine._launch = watched
        engine.start()

        def wave():
            alone = engine.generate(_prompt(cfg, 6, seed=1), 12, timeout=240)
            pair = [engine.submit(_prompt(cfg, n, seed=n), 6)
                    for n in (5, 9)]
            return alone, [r.result(timeout=240) for r in pair]

        try:
            first = wave()
            sizes = (engine._jprefill._cache_size(),
                     engine._jdecode._cache_size())
            mark = len(events)
            # the pair may or may not have decoded side by side the first
            # time (admission races the first step); only what the first
            # round ran is owed to the second
            shapes = set(engine._shapes_run)
            again = wave()
            fresh = set(engine._shapes_run) - shapes
        finally:
            engine.stop()
        after = HUB.snapshot()
        assert again == first
        assert len(events[mark:]) == len(fresh), (events[mark:], fresh)
        name = labeled("gen_new_shapes_total", stage="decode")
        assert engine._jdecode._cache_size() \
            == after[name] - before.get(name, 0) \
            == sum(1 for s in engine._shapes_run if s[0] == "decode")
        assert sizes[0] == engine._jprefill._cache_size() == 3
        born, *later = launched
        assert born[2] and not any(first for _s, _a, first in later)
        both = {shape for shape, ahead, _f in launched if ahead} \
            & {shape for shape, ahead, _f in launched if not ahead}
        assert born[0] in both
        assert {shape for shape, _a, _f in launched} \
            == {s[1:] for s in engine._shapes_run if s[0] == "decode"}

    def test_an_admission_behind_a_step_makes_no_program(self, placed):
        """The harness's protocol when a run had to compile: warm,
        ``jax.clear_caches()``, warm again, window. No wave admits behind
        a step in flight, the window does at once: the program that sets
        the prefill's id on the device must still be there, so nothing is
        compiled or loaded beyond the shapes run again since the clear."""
        params, cfg, mesh = placed
        events = _Compiles.listen()
        engine = GenEngine(params, cfg, mesh=mesh, max_batch=2,
                           queue_limit=8, max_new_tokens=12, kv_mb=1,
                           block_tokens=16)
        assert isinstance(engine._set_id, jax.stages.Compiled)
        long, short = _prompt(cfg, 20, seed=1), _prompt(cfg, 18, seed=2)
        ahead = labeled("gen_prefills_total", ahead="1")

        def side_by_side():     # a wave: both admitted on an empty pipe
            reqs = [engine.submit(long, 12), engine.submit(short, 4)]
            while engine._turn():
                pass
            return [r.result(timeout=10) for r in reqs]

        try:
            first = side_by_side()
            jax.clear_caches()
            mark = len(events)
            assert side_by_side() == first
            shapes = set(engine._shapes_run)
            # each made again, compiled or loaded, since the clear
            assert len(events[mark:]) >= len(shapes) == 4
            mark, before = len(events), HUB.snapshot().get(ahead, 0)
            reqs = [engine.submit(long, 12)]
            assert engine._turn() and engine._flight is not None
            reqs.append(engine.submit(short, 4))
            while engine._turn():
                pass
            assert [r.result(timeout=10) for r in reqs] == first
        finally:
            engine.stop()
        assert HUB.snapshot()[ahead] == before + 1
        assert set(engine._shapes_run) == shapes
        assert events[mark:] == []
        assert engine._jprefill._cache_size() == 2
        assert engine._jdecode._cache_size() == 2
        assert engine.pool.describe()["in_use_blocks"] == 0

    def test_donation_is_real(self, placed):
        """After every call the arrays that went in are gone: nothing was
        copied, and nothing could have read them afterwards."""
        params, cfg, mesh = placed
        engine = GenEngine(params, cfg, mesh=mesh, max_batch=2,
                           queue_limit=8, max_new_tokens=6, kv_mb=1)
        pool = engine.pool
        went_in = []
        real = pool.apply

        def watched(program, *args):
            went_in.extend((pool.k, pool.v))
            return real(program, *args)

        pool.apply = watched
        engine.start()
        try:
            reqs = [engine.submit(_prompt(cfg, n, seed=n), 6)
                    for n in (7, 19)]
            for r in reqs:
                r.result(timeout=240)
        finally:
            engine.stop()
        assert len(went_in) >= 2 * (2 + 5)   # two prefills, five steps
        assert all(a.is_deleted() for a in went_in)
        assert not pool.lost
        assert pool.k.sharding == pool.sharding and pool.k.committed

    def test_ledger_and_shutdown_never_touch_the_arrays(self, placed):
        """``describe()`` of engine and pool, and ``stop()`` with
        sequences running, work on the ledger alone: they succeed with
        the arrays deleted under them."""
        params, cfg, mesh = placed
        engine = GenEngine(params, cfg, mesh=mesh, max_batch=2,
                           queue_limit=8, max_new_tokens=6, kv_mb=1)
        reqs = _drive(engine, [_prompt(cfg, 7), _prompt(cfg, 12)], 6)
        engine.pool.k.delete()
        engine.pool.v.delete()
        doc = engine.describe()
        assert doc["running"] == 2 and doc["kv"]["in_use_blocks"] > 0
        assert engine.pool.describe()["num_blocks"] == engine.pool.num_blocks
        engine.stop()
        for r in reqs:
            with pytest.raises(RuntimeError, match="shutdown"):
                r.result(timeout=10)
        assert engine.pool.describe()["in_use_blocks"] == 0
        assert engine.admission.describe()["outstanding"] == 0


# ------------------------------------------ an admission rides the pipe


@pytest.fixture(scope="module", params=["llama", "exaone_moe", "qwen3_next"])
def family(request):
    return _tiny(request.param)[1:]


class TestAdmissionRidesThePipe:
    """An engine driven by hand, a turn of its loop at a time, so that
    who arrives over which step in flight is the test's to say."""

    KW = dict(max_batch=3, queue_limit=16, kv_mb=4, block_tokens=4)

    @staticmethod
    def _alone(params, cfg, work, **kw):
        """The oracle of ``test_matches_one_at_a_time_reference`` for any
        family: each request alone on an idle engine, so admitted on an
        empty pipe and decoded in a batch of one."""
        engine = GenEngine(params, cfg, **kw).start()
        try:
            return [engine.generate(p, n, timeout=240) for p, n in work]
        finally:
            engine.stop()

    @staticmethod
    def _play(engine, work, arrivals):
        """Submit ``work[i]`` before turn ``arrivals[i]`` and turn the
        loop until nothing is left."""
        reqs = []
        for turn in range(200):
            while len(reqs) < len(work) and arrivals[len(reqs)] <= turn:
                reqs.append(engine.submit(*work[len(reqs)]))
            if not engine._turn() and len(reqs) == len(work):
                return reqs
        raise AssertionError("the engine never came to rest")

    @pytest.mark.parametrize("lengths,news,arrivals", [
        # one behind each of the first steps, the one-token request among
        # them; the last two wait for a row
        ([9, 5, 12, 7, 4, 6], [14, 3, 1, 6, 2, 5], [0, 1, 2, 3, 3, 4]),
        # two and three ride one cycle; the batch fills, empties to one
        # row and fills again
        ([6, 11, 4, 9, 3, 8], [16, 2, 3, 1, 4, 2], [0, 2, 2, 5, 5, 5]),
        # the pipe runs empty (the first is done before the second comes)
        # and an admission finds it empty again
        ([5, 8, 7], [2, 6, 3], [0, 4, 6])],
        ids=["one-a-step", "several-a-cycle", "pipe-runs-empty"])
    def test_arrivals_over_steps_in_flight(self, family, lengths, news,
                                           arrivals):
        """Requests that arrive while steps are in flight get the tokens
        they get alone, the first token first."""
        params, cfg = family
        work = [(_prompt(cfg, n, seed=i), new)
                for i, (n, new) in enumerate(zip(lengths, news))]
        kw = dict(self.KW, max_new_tokens=max(news))
        refs = self._alone(params, cfg, work, **kw)
        before = HUB.snapshot()
        engine = GenEngine(params, cfg, **kw)
        try:
            reqs = self._play(engine, work, arrivals)
            outs = [r.result(timeout=10) for r in reqs]
        finally:
            engine.stop()
        assert outs == refs
        after = HUB.snapshot()

        def delta(name, **labels):
            name = labeled(name, **labels)
            return after[name] - before.get(name, 0)

        on_empty = delta("gen_prefills_total", ahead="0")
        assert on_empty + delta("gen_prefills_total", ahead="1") == len(work)
        # only an idle engine admits on an empty pipe, and only the cycle
        # after it starts on one
        assert on_empty == delta("gen_decode_steps_total", ahead="0") \
            == (2 if arrivals[1] > news[0] else 1)
        assert engine.pool.describe()["in_use_blocks"] == 0
        assert engine.admission.describe()["outstanding"] == 0

    def test_cancel_between_dispatch_and_pull(self, family):
        """Cancelled with its prefill queued and its row shipped: the
        first token still comes, the eviction at the next boundary frees
        the lease once, and whoever leases those blocks next is behind the
        programs that write them."""
        params, cfg = family
        work = [(_prompt(cfg, 9, seed=1), 10), (_prompt(cfg, 6, seed=2), 8),
                (_prompt(cfg, 7, seed=3), 5)]
        kw = dict(self.KW, max_new_tokens=10)
        refs = self._alone(params, cfg, work, **kw)
        engine = GenEngine(params, cfg, **kw)
        real = engine._launch
        try:
            kept = engine.submit(*work[0])
            assert engine._turn() and engine._flight is not None
            gone = engine.submit(*work[1])

            def launch_then_cancel(step):
                real(step)
                gone.cancel()       # dispatched, shipped, not yet pulled

            engine._launch = launch_then_cancel
            held = engine.pool.in_use_blocks
            assert engine._turn()
            engine._launch = real
            assert gone.tokens == refs[1][:1] and not gone.done.is_set()
            assert engine.pool.in_use_blocks > held
            late = engine.submit(*work[2])
            while engine._turn():
                pass
            with pytest.raises(RuntimeError, match="evicted"):
                gone.result(timeout=10)
            assert gone.tokens == refs[1][:1]
            assert kept.result(timeout=10) == refs[0]
            assert late.result(timeout=10) == refs[2]
        finally:
            engine.stop()
        assert engine.pool.describe()["in_use_blocks"] == 0
        assert engine.admission.describe()["outstanding"] == 0

    def test_counter_and_span_say_which_way(self, family, monkeypatch):
        """``gen_prefills_total{ahead}`` and ``ahead`` on ``serve.prefill``:
        0 on an idle engine, 1 behind a step; either way one
        ``serve.prefill-device`` inside it, and the span of one that rode
        lies between the cycle that dispatched it and the next."""
        from demodel_tpu.utils import trace

        params, cfg = family
        for var in ("DEMODEL_TRACE", "DEMODEL_TRACE_SAMPLE", "DEMODEL_OBS"):
            monkeypatch.delenv(var, raising=False)
        trace.reset()
        trace.enable()
        before = HUB.snapshot()
        engine = GenEngine(params, cfg, max_new_tokens=6, **self.KW)
        try:
            reqs = self._play(engine, [(_prompt(cfg, 9, seed=1), 6),
                                       (_prompt(cfg, 5, seed=2), 3)], [0, 2])
            for r in reqs:
                r.result(timeout=10)
        finally:
            engine.stop()
            spans = sorted((r for r in trace.buffer().snapshot()
                            if r["name"].startswith("serve.")),
                           key=lambda r: r["ts"])
            trace.reset()
        after = HUB.snapshot()
        for flag in ("0", "1"):
            name = labeled("gen_prefills_total", ahead=flag)
            assert after[name] - before.get(name, 0) == 1
        roots = [r for r in spans
                 if r["name"] in ("serve.prefill", "serve.decode-step")]
        assert [(r["name"], r["attrs"]["ahead"]) for r in roots[:6]] == [
            ("serve.prefill", False), ("serve.decode-step", False),
            ("serve.decode-step", True), ("serve.decode-step", True),
            ("serve.prefill", True), ("serve.decode-step", True)]
        assert all(r["name"] == "serve.decode-step" and r["attrs"]["ahead"]
                   for r in roots[6:])
        for a, b in zip(roots, roots[1:]):      # one after the other
            assert a["ts"] + a["dur"] <= b["ts"] + 1e-6
        rode = roots[4]
        assert rode["attrs"]["request"] == reqs[1].id
        inside = [r for r in spans if r["name"] == "serve.prefill-device"]
        assert [r["parent"] for r in inside] == [roots[0]["span"],
                                                 rode["span"]]
        assert all(r["attrs"]["new_shape"] for r in inside)


# --------------------------------------------------------- HTTP surface


def _post(url, doc, timeout=120):
    body = json.dumps(doc).encode()
    req = urllib.request.Request(url, data=body, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


@pytest.fixture()
def gen_server(tmp_path):
    from demodel_tpu.restore.server import RestoreRegistry, RestoreServer
    from demodel_tpu.store import Store

    store = Store(tmp_path / "store")
    server = RestoreServer(RestoreRegistry(store), host="127.0.0.1").start()
    yield f"http://127.0.0.1:{server.port}"
    server.stop()
    serve.install(None)
    store.close()


class TestGenerateHTTP:
    def test_disabled_without_engine(self, gen_server):
        serve.install(None)
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(f"{gen_server}/generate", {"prompt": [1, 2, 3]})
        assert exc.value.code == 503
        assert b"serving disabled" in exc.value.read()

    def test_backlog_holds_a_batch_of_sessions(self, gen_server):
        """Sessions of a gateway connect together; socketserver's default
        backlog of 5 had one of 32 reset by the peer on the chip. All of
        them are answered here (503: no engine), none reset."""
        from demodel_tpu.restore.server import _Listener

        assert _Listener.request_queue_size >= 4 * 32
        codes: list = []

        def post():
            try:
                _post(gen_server + "/generate", {"prompt": [1]})
            except urllib.error.HTTPError as exc:
                codes.append(exc.code)

        threads = [threading.Thread(target=post) for _ in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert codes == [503] * 32

    def test_roundtrip_matches_engine(self, gen_server, tiny_model):
        params, cfg = tiny_model
        prompt = _prompt(cfg, 9, seed=5)
        ref = [int(t) for t in
               np.asarray(llama.generate(params, cfg, prompt, 5))[0]]
        serve.boot(params, cfg, max_batch=2, queue_limit=8,
                   max_new_tokens=8, kv_mb=4)
        try:
            status, doc = _post(f"{gen_server}/generate",
                                {"prompt": prompt, "max_new_tokens": 5})
            assert status == 200
            assert doc["tokens"] == ref
            assert doc["prompt_tokens"] == len(prompt)
            bad = urllib.request.Request(
                f"{gen_server}/generate",
                data=json.dumps({"prompt": []}).encode(), method="POST")
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(bad, timeout=30)
            assert exc.value.code == 400
        finally:
            serve.current().stop()

    def test_streaming_ndjson(self, gen_server, tiny_model):
        params, cfg = tiny_model
        prompt = _prompt(cfg, 7, seed=6)
        ref = [int(t) for t in
               np.asarray(llama.generate(params, cfg, prompt, 4))[0]]
        serve.boot(params, cfg, max_batch=2, queue_limit=8,
                   max_new_tokens=8, kv_mb=4)
        try:
            body = json.dumps({"prompt": prompt, "max_new_tokens": 4,
                               "stream": True}).encode()
            req = urllib.request.Request(f"{gen_server}/generate",
                                         data=body, method="POST")
            with urllib.request.urlopen(req, timeout=120) as resp:
                assert resp.status == 200
                assert "x-ndjson" in resp.headers.get("Content-Type", "")
                lines = [json.loads(ln) for ln in
                         resp.read().decode().splitlines() if ln.strip()]
            toks = [ln["token"] for ln in lines if "token" in ln]
            assert toks == ref
            assert lines[-1]["done"] is True
            assert lines[-1]["tokens"] == ref
        finally:
            serve.current().stop()

    def test_oversized_body_answers_413(self, gen_server, tiny_model):
        """A /generate body over the 8 MiB cap is 413 Payload Too Large
        (not a mislabeled 411), and the outcome is counted."""
        import socket

        from demodel_tpu.utils.metrics import HUB, labeled

        params, cfg = tiny_model
        engine = GenEngine(params, cfg, max_batch=1, queue_limit=1,
                           max_new_tokens=4, kv_mb=4)  # not started
        serve.install(engine)
        before = HUB.get(labeled("gen_http_total", code="413"))
        try:
            host, port = gen_server.rsplit("/", 1)[1].split(":")
            with socket.create_connection((host, int(port)),
                                          timeout=30) as s:
                # the server answers from the header alone — no need to
                # actually ship 9 MiB
                s.sendall(b"POST /generate HTTP/1.1\r\nHost: t\r\n"
                          b"Content-Length: 9437184\r\n\r\n")
                status = s.recv(4096).split(b"\r\n", 1)[0]
            assert b"413" in status
            assert HUB.get(labeled("gen_http_total",
                                   code="413")) == before + 1
        finally:
            serve.install(None)
            engine.stop()

    def test_overflow_503_sets_retry_after(self, gen_server, tiny_model):
        params, cfg = tiny_model
        engine = GenEngine(params, cfg, max_batch=1, queue_limit=1,
                           max_new_tokens=4, kv_mb=4)  # not started: the
        serve.install(engine)  # waiting room fills deterministically
        try:
            slow = json.dumps({"prompt": _prompt(cfg, 4),
                               "max_new_tokens": 4}).encode()
            hang = urllib.request.Request(f"{gen_server}/generate",
                                          data=slow, method="POST")
            t = threading.Thread(
                target=lambda: urllib.request.urlopen(hang, timeout=120),
                daemon=True)
            t.start()
            deadline_hit = False
            for _ in range(200):
                if engine.describe()["waiting"] >= 1:
                    deadline_hit = True
                    break
                threading.Event().wait(0.02)
            assert deadline_hit
            with pytest.raises(urllib.error.HTTPError) as exc:
                _post(f"{gen_server}/generate",
                      {"prompt": _prompt(cfg, 4), "max_new_tokens": 4})
            assert exc.value.code == 503
            assert int(exc.value.headers["Retry-After"]) >= 1
            doc = json.loads(exc.value.read())
            assert doc["retry_after"] >= 1
            engine.start()  # drain the parked request before teardown
            t.join(timeout=120)
        finally:
            engine.stop()

    def test_a_herd_is_answered_200_or_503_and_never_dropped(
            self, gen_server, tiny_model):
        """Twelve requests at once against an engine one row wide with
        room for two to wait: every one is answered, with all its tokens
        or with 503 and ``Retry-After``, some of each, none reset or left
        hanging; and the pool's blocks and bytes are back at zero."""
        params, cfg = tiny_model
        engine = serve.boot(params, cfg, max_batch=1, queue_limit=2,
                            max_new_tokens=4, kv_mb=4)
        answers: list = [None] * 12

        def post(i):
            try:
                status, doc = _post(f"{gen_server}/generate", {
                    "prompt": _prompt(cfg, 4, seed=i), "max_new_tokens": 4})
                answers[i] = (status, len(doc["tokens"]))
            except urllib.error.HTTPError as exc:
                answers[i] = (exc.code, exc.headers.get("Retry-After"))
            except Exception as exc:  # noqa: BLE001 - a drop must show
                answers[i] = (-1, repr(exc))

        try:
            threads = [threading.Thread(target=post, args=(i,))
                       for i in range(len(answers))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
        finally:
            serve.install(None)
            engine.stop()
        served = [a for a in answers if a and a[0] == 200]
        refused = [a for a in answers if a and a[0] == 503]
        assert len(served) + len(refused) == len(answers), answers
        assert served and all(n == 4 for _c, n in served)
        assert refused and all(int(after) >= 1 for _c, after in refused)
        assert engine.pool.in_use_blocks == 0
        assert engine.pool.budget.describe()["in_use_bytes"] == 0

    def test_statusz_generation_section(self, gen_server, tiny_model):
        params, cfg = tiny_model
        serve.boot(params, cfg, max_batch=1, queue_limit=4,
                   max_new_tokens=4, kv_mb=4)
        try:
            serve.current().generate(_prompt(cfg, 5), 2)
            with urllib.request.urlopen(f"{gen_server}/debug/statusz",
                                        timeout=30) as resp:
                doc = json.loads(resp.read())
            gen = doc["generation"]
            assert gen["model"] == "inline"
            assert gen["kv"]["in_use_blocks"] == 0
            assert gen["tokens"]["prefill"] >= 5
            assert gen["admission"]["outstanding"] == 0
        finally:
            serve.current().stop()


# ------------------------------------------------- the engine cycle's spans

#: one decode cycle is one serve.decode-step root on the engine thread;
#: these are its children, in order
CYCLE = ["serve.decode-h2d", "serve.decode-device", "serve.decode-fetch",
         "serve.decode-post"]
CHILDREN = {"serve.http-parse": "serve.restore",
            "serve.admit": "serve.restore",
            "serve.prefill": "serve.admit",
            "serve.prefill-device": "serve.prefill",
            **{name: "serve.decode-step" for name in CYCLE}}


def _pow2(n):
    return 1 << max(0, n - 1).bit_length()


class TestServeSpans:
    """Counts only (CPU, toy model): which spans a served request leaves,
    under which parent, with which byte counts and counters."""

    PROMPTS = [9, 5, 12, 9]
    MAX_NEW = 6
    MAX_BATCH = 3
    BLOCK = 16

    @pytest.fixture(scope="class")
    def run(self, tiny_model, tmp_path_factory):
        from demodel_tpu.restore.server import (RestoreRegistry,
                                                RestoreServer)
        from demodel_tpu.store import Store
        from demodel_tpu.utils import trace
        from demodel_tpu.utils.metrics import HUB

        params, cfg = tiny_model
        mp = pytest.MonkeyPatch()
        for var in ("DEMODEL_TRACE", "DEMODEL_TRACE_SAMPLE", "DEMODEL_OBS"):
            mp.delenv(var, raising=False)
        trace.reset()
        trace.enable()
        store = Store(tmp_path_factory.mktemp("spans") / "store")
        server = RestoreServer(RestoreRegistry(store),
                               host="127.0.0.1").start()
        before = HUB.snapshot()
        engine = serve.boot(params, cfg, max_batch=self.MAX_BATCH,
                            queue_limit=16,
                            max_new_tokens=self.MAX_NEW, kv_mb=4,
                            block_tokens=self.BLOCK)
        bodies = [{"prompt": _prompt(cfg, n, seed=i),
                   "max_new_tokens": self.MAX_NEW}
                  for i, n in enumerate(self.PROMPTS)]
        docs: list = [None] * len(bodies)

        def post(i):
            docs[i] = _post(f"http://127.0.0.1:{server.port}/generate",
                            bodies[i])[1]

        try:
            threads = [threading.Thread(target=post, args=(i,))
                       for i in range(len(bodies))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=240)
            assert all(d is not None for d in docs)
        finally:
            engine.stop()
            server.stop()
            serve.install(None)
            store.close()
        after = HUB.snapshot()
        spans = [r for r in trace.buffer().snapshot()
                 if r["name"].startswith("serve.")]
        trace.reset()
        mp.undo()

        def delta(name):
            return after.get(name, 0) - before.get(name, 0)

        def named(name):
            return sorted((r for r in spans if r["name"] == name),
                          key=lambda r: r["ts"])

        return {"cfg": cfg, "docs": docs, "bodies": bodies, "spans": spans,
                "delta": delta, "named": named}

    @pytest.mark.parametrize("name", sorted(CHILDREN))
    def test_span_sits_under_its_parent(self, run, name):
        by_id = {r["span"]: r for r in run["spans"]}
        found = run["named"](name)
        assert found
        for r in found:
            parent = by_id[r["parent"]]
            assert parent["name"] == CHILDREN[name]
            assert parent["trace"] == r["trace"]

    def test_one_of_each_phase_a_cycle(self, run):
        steps = run["named"]("serve.decode-step")
        assert steps and all(r["parent"] is None for r in steps)
        # the four phases, in order, inside each cycle's one root
        for step in steps:
            inside = sorted((r for r in run["spans"]
                             if r["parent"] == step["span"]),
                            key=lambda r: r["ts"])
            assert [r["name"] for r in inside] == CYCLE
            assert step["ts"] <= inside[0]["ts"]
            assert inside[-1]["ts"] + inside[-1]["dur"] \
                <= step["ts"] + step["dur"] + 1e-6
        for name in CYCLE:
            assert len(run["named"](name)) == len(steps), name
        # a cycle carries the step it pulls: dispatched behind another
        # (ahead) or on an empty pipe, which only an idle engine has: the
        # cycle after an admission that found the pipe empty
        prefills = run["named"]("serve.prefill")
        roots = sorted(steps + prefills, key=lambda r: r["ts"])
        ahead = [r["attrs"]["ahead"] for r in steps]
        assert ahead[0] is False and True in ahead
        for last, step in zip(roots, roots[1:]):
            if step["name"] == "serve.decode-step":
                assert step["attrs"]["ahead"] is not (
                    last["name"] == "serve.prefill"
                    and not last["attrs"]["ahead"])
        for name, found in (("gen_decode_steps_total", ahead),
                            ("gen_prefills_total",
                             [r["attrs"]["ahead"] for r in prefills])):
            for flag in (True, False):
                assert run["delta"](labeled(name, ahead=str(int(flag)))) \
                    == found.count(flag)
        # a prefill's span overlaps no cycle: on an empty pipe it runs
        # alone, behind a step it is the wait between two cycles
        for pre in prefills:
            for step in steps:
                assert pre["ts"] + pre["dur"] <= step["ts"] + 1e-6 \
                    or step["ts"] + step["dur"] <= pre["ts"] + 1e-6
        n_req = len(self.PROMPTS)
        assert len(run["named"]("serve.http-parse")) == n_req
        assert len(run["named"]("serve.prefill-device")) == n_req
        # the phases that moved the cache over the link are gone
        for gone in ("serve.kv-gather", "serve.kv-pageout",
                     "serve.decode-release"):
            assert run["named"](gone) == []
        decoded = sum(r["attrs"]["batch"] for r in steps)
        assert decoded == n_req * (self.MAX_NEW - 1)
        assert sum(r["attrs"]["retired"]
                   for r in run["named"]("serve.decode-post")) == n_req

    def test_bytes_follow_the_pools_geometry(self, run):
        tables = 0
        for step, fetch in zip(run["named"]("serve.decode-step"),
                               run["named"]("serve.decode-fetch")):
            width = step["attrs"]["width"]
            assert width % self.BLOCK == 0
            # a row: token, length, write block, write offset, src, and
            # its slots of the block table, all int32
            tables += _pow2(step["attrs"]["batch"]) * (
                5 + width // self.BLOCK) * 4
            # the ids of a full bucket whatever the step's, nothing else
            assert fetch["attrs"]["bytes"] == _pow2(self.MAX_BATCH) * 4
        # every step pulled was shipped once, in its own cycle or the one
        # before it
        assert sum(r["attrs"]["bytes"]
                   for r in run["named"]("serve.decode-h2d")) == tables
        assert sorted(r["attrs"]["prompt"]
                      for r in run["named"]("serve.prefill-device")) == \
            sorted(self.PROMPTS)
        assert sorted(r["attrs"]["bytes"]
                      for r in run["named"]("serve.http-parse")) == \
            sorted(len(json.dumps(b).encode()) for b in run["bodies"])

    def test_a_narrow_step_reads_every_position_of_its_table(self, run):
        """At most two tiles a row (here 32 positions of 16 a block): the
        rectangle, so a step books as many positions read as its bucket's
        table is wide, on its span and in the counters."""
        steps = [r["attrs"] for r in run["named"]("serve.decode-step")]
        assert steps
        for a in steps:
            assert a["kv_positions_read"] == a["kv_positions_width"] \
                == _pow2(a["batch"]) * a["width"]
        total = sum(a["kv_positions_width"] for a in steps)
        assert run["delta"]("gen_kv_positions_width_total") == total
        assert run["delta"]("gen_kv_positions_read_total") == total

    def test_byte_counters_are_what_crosses_the_link(self, run):
        """Decode: the spans' ``bytes``. A prefill ships its prompt and
        its lease's block ids and pulls the id it chose back."""

        def total(name):
            return sum(r["attrs"]["bytes"] for r in run["named"](name))

        assert run["delta"]("gen_h2d_bytes_total") == \
            total("serve.decode-h2d") + sum(
                4 * (n + -(-n // self.BLOCK)) for n in self.PROMPTS)
        assert run["delta"]("gen_d2h_bytes_total") == \
            total("serve.decode-fetch") + len(self.PROMPTS) * 4
        # README's operator reading: under a kilobyte a decoded token
        decoded = sum(r["attrs"]["batch"]
                      for r in run["named"]("serve.decode-step"))
        assert 0 < total("serve.decode-h2d") / decoded < 1024

    @pytest.mark.parametrize("stage,span,shape", [
        ("prefill", "serve.prefill-device",
         lambda a: a["prompt"]),
        ("decode", "serve.decode-device",
         lambda a: (_pow2(a["batch"]), a["width"]))])
    def test_new_shapes_counted_once_each(self, run, stage, span, shape):
        seen, first = set(), []
        for r in run["named"](span):
            first.append(shape(r["attrs"]) not in seen)
            seen.add(shape(r["attrs"]))
        flagged = [r["attrs"]["new_shape"] for r in run["named"](span)]
        assert run["delta"](labeled("gen_new_shapes_total",
                                    stage=stage)) == len(seen)
        if stage == "prefill":
            assert flagged == first
            assert len(seen) == len(set(self.PROMPTS))
        else:
            # the span names the step it pulls and flags a first run among
            # the steps it dispatches (the one ahead, as a rule): a shape
            # is flagged in the cycle that pulls it or the one before
            assert flagged[0] and 1 <= sum(flagged) <= len(seen)
            for i, new in enumerate(first):
                assert not new or flagged[i] or flagged[i - 1]

    def test_a_request_is_one_trace(self, run):
        by_id = {r["span"]: r for r in run["spans"]}
        admits = {r["attrs"]["request"]: r
                  for r in run["named"]("serve.admit")}
        prefills = run["named"]("serve.prefill")
        assert len(prefills) == len(admits) == len(self.PROMPTS)
        for p in prefills:
            admit = admits[p["attrs"]["request"]]
            assert p["trace"] == admit["trace"]
            assert p["parent"] == admit["span"]
            assert by_id[admit["parent"]]["name"] == "serve.restore"
        assert len({p["trace"] for p in prefills}) == len(prefills)

    def test_observability_off_same_tokens_no_spans(self, run, tiny_model,
                                                    monkeypatch):
        from demodel_tpu.utils import trace

        params, cfg = tiny_model
        monkeypatch.setenv("DEMODEL_OBS", "0")
        monkeypatch.delenv("DEMODEL_TRACE", raising=False)
        trace.reset()
        engine = GenEngine(params, cfg, max_batch=3, queue_limit=16,
                           max_new_tokens=self.MAX_NEW, kv_mb=4,
                           block_tokens=self.BLOCK).start()
        try:
            assert trace.mode() == "off"
            reqs = [engine.submit(b["prompt"], self.MAX_NEW)
                    for b in run["bodies"]]
            outs = [r.result(timeout=240) for r in reqs]
        finally:
            engine.stop()
            recorded = (trace.buffer().snapshot()
                        + trace.recorder().snapshot())
            monkeypatch.undo()
            trace.reset()
        assert outs == [d["tokens"] for d in run["docs"]]
        assert recorded == []
        assert all(r.traceparent is None for r in reqs)

    @pytest.mark.parametrize("tier,waits", [("observe", 0), ("export", 1)])
    def test_prefill_waits_for_the_device_only_when_exporting(
            self, tiny_model, monkeypatch, tier, waits):
        """``serve.prefill-device`` syncs only in the export tier; by
        default it ends at dispatch and the pull of the logits takes the
        wait (one token out, so no decode step runs and syncs)."""
        from demodel_tpu.utils import trace

        params, cfg = tiny_model
        for var in ("DEMODEL_TRACE", "DEMODEL_OBS"):
            monkeypatch.delenv(var, raising=False)
        trace.reset()
        if tier == "export":
            trace.enable()
        synced = []
        real = jax.block_until_ready
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda x: synced.append(1) or real(x))
        engine = GenEngine(params, cfg, max_batch=1, kv_mb=4,
                           block_tokens=self.BLOCK).start()
        try:
            assert trace.mode() == tier
            out = engine.submit(_prompt(cfg, 7), 1).result(timeout=240)
        finally:
            engine.stop()
            monkeypatch.undo()
            trace.reset()
        assert len(out) == 1 and len(synced) == waits

    def test_annotator_is_installed_by_building_an_engine(self):
        """Dep-light: the restore server and the tracing module leave the
        hook empty and the serving plane unimported; importing the plane
        installs nothing; building an engine installs the profiler's
        annotation."""
        import subprocess
        import sys
        from pathlib import Path

        code = """
import sys
import demodel_tpu.restore.server
from demodel_tpu.utils import trace
assert trace._annotator is None
assert "demodel_tpu.serve" not in sys.modules
import jax
from demodel_tpu import serve
from demodel_tpu.models import llama
assert trace._annotator is None
cfg = llama.LlamaConfig.tiny()
engine = serve.GenEngine(llama.init_params(jax.random.key(0), cfg), cfg,
                         max_batch=1, kv_mb=1)
assert trace._annotator is jax.profiler.TraceAnnotation
engine.stop()
"""
        out = subprocess.run([sys.executable, "-c", code],
                             cwd=Path(__file__).resolve().parent.parent,
                             capture_output=True, text=True, timeout=240)
        assert out.returncode == 0, out.stderr
