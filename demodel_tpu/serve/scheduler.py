"""Continuous-batching scheduler: admit → prefill → interleaved decode.

The Orca/vLLM serving loop over the paged pool
(:mod:`demodel_tpu.serve.kvcache`): one engine thread advances ALL
running sequences one token per decode step, new sequences join the
running batch *between* steps (a prefill slots in as soon as blocks are
free — no waiting for the batch to drain), and a finished, evicted, or
failed sequence frees its blocks immediately. Admission reserves the
worst case (prompt + ``max_new_tokens``) up front, so a running
sequence can never hit an out-of-blocks wall mid-decode — the
no-overcommit discipline the KV budget exists to enforce.

Backpressure rides the proxy plane's admission contract: a full waiting
queue answers :class:`QueueOverflow`, which the HTTP surface maps to
503 + ``Retry-After`` (:data:`RETRY_AFTER_S`) — loudly rejected,
never silently dropped; every admitted request carries an
:class:`AdmissionTicket` that must settle exactly once.

Compute stays jit-friendly: decode batches are padded to power-of-two
batch/width buckets (padded rows decode with ``length 0`` and are
dropped on the host side), so the number of distinct compiled shapes is
logarithmic in batch size and sequence length.

The engine keeps one decode step in flight. Both programs choose the
token themselves (argmax in float32, first maximum) and a step takes the
ids it feeds from the previous step's ids on the device, so what the next
step needs from the host (lengths, write coordinates, block table, who is
in the batch: the engine retires by count alone) is known before this one
has run: step j+1 is shipped and queued, and only then are step j's ids
pulled, emitted and retired. An admission rides the same pipe: with a step
in flight the prefill is queued behind it, the id it chooses is set on the
device into the ids the next step reads, that step is shipped with the new
row, and only then does the host wait for the first token. On an empty pipe
the prefill runs alone and its id is pulled at once.
"""

from __future__ import annotations

import functools
import itertools
import queue as queue_mod
import sys
import threading
import time
from collections import deque
from typing import Any, Iterator

from demodel_tpu.serve import kvcache
from demodel_tpu.serve.kvcache import KVBlockPool, PoolExhausted
from demodel_tpu.utils import compile_cache, trace
from demodel_tpu.utils.logging import get_logger
from demodel_tpu.utils.metrics import HUB, labeled

log = get_logger("serve.scheduler")

#: the Retry-After hint (seconds) a queue-overflow 503 carries
RETRY_AFTER_S = 1

#: pre-register the generation families at import (house idiom)
HUB.inc(labeled("gen_tokens_total", stage="prefill"), 0)
HUB.inc(labeled("gen_tokens_total", stage="decode"), 0)
HUB.inc("gen_requests_total", 0)
HUB.inc("gen_rejected_total", 0)
HUB.inc("gen_evicted_total", 0)
HUB.inc("gen_h2d_bytes_total", 0)
HUB.inc("gen_d2h_bytes_total", 0)
HUB.inc(labeled("gen_new_shapes_total", stage="prefill"), 0)
HUB.inc(labeled("gen_new_shapes_total", stage="decode"), 0)
HUB.inc(labeled("gen_decode_steps_total", ahead="0"), 0)
HUB.inc(labeled("gen_decode_steps_total", ahead="1"), 0)
HUB.inc(labeled("gen_prefills_total", ahead="0"), 0)
HUB.inc(labeled("gen_prefills_total", ahead="1"), 0)
HUB.set_gauge("gen_queue_depth", 0)
HUB.set_gauge("gen_running", 0)

_END = object()  # stream sentinel: the request is finished


class QueueOverflow(Exception):
    """Waiting queue is full — the HTTP surface answers 503 with
    ``Retry-After: retry_after`` (the proxy admission contract)."""

    def __init__(self, depth: int, limit: int, retry_after: int):
        super().__init__(
            f"generation queue full ({depth}/{limit} waiting)")
        self.retry_after = retry_after


class Request:
    """One generation request, observable from any thread: a bounded
    stream of generated token ids plus a done event. Tokens-in,
    tokens-out — the plane serves models, not tokenizers."""

    def __init__(self, rid: int, prompt: list[int], max_new_tokens: int):
        self.id = rid
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.tokens: list[int] = []
        self.error: str | None = None
        self.ticket: "AdmissionTicket | None" = None
        #: the submitting thread's ``serve.admit`` span as a W3C header:
        #: the engine thread parents ``serve.prefill`` on it, so a
        #: request's handler and engine spans share one trace
        self.traceparent: str | None = None
        self.submitted_s = time.time()
        self.started_s: float | None = None
        self.finished_s: float | None = None
        self.done = threading.Event()
        self.cancelled = threading.Event()
        self._stream: queue_mod.Queue = queue_mod.Queue()

    # -- engine side ----------------------------------------------------
    def _emit(self, tok: int) -> None:
        self.tokens.append(tok)
        self._stream.put(tok)

    def _close(self) -> None:
        self.finished_s = time.time()
        self._stream.put(_END)
        self.done.set()

    # -- consumer side --------------------------------------------------
    def cancel(self) -> None:
        """Ask the engine to evict this sequence at the next step
        boundary (its blocks free immediately there)."""
        self.cancelled.set()

    def result(self, timeout: float | None = None) -> list[int]:
        """Block until finished; the generated token ids (raises on a
        failed/evicted request)."""
        if not self.done.wait(timeout):
            raise TimeoutError(f"request {self.id} still running")
        if self.error is not None:
            raise RuntimeError(self.error)
        return list(self.tokens)

    def iter_tokens(self, timeout: float = 60.0) -> Iterator[int]:
        """Stream token ids as they are generated; raises on error."""
        while True:
            item = self._stream.get(timeout=timeout)
            if item is _END:
                if self.error is not None:
                    raise RuntimeError(self.error)
                return
            yield item


class AdmissionTicket:
    """One admitted request's slot in the engine's accounting — must
    reach :meth:`finish` exactly once (completion, eviction, or error):
    tickets are how "zero silent drops" is checkable, the outstanding
    count is exactly admitted-minus-settled."""

    __slots__ = ("_queue", "request", "_done")

    def __init__(self, queue: "AdmissionQueue", request: Request):
        self._queue = queue
        self.request = request
        self._done = False

    def finish(self) -> None:
        if self._done:
            return
        self._done = True
        self._queue._settle()


class AdmissionQueue:
    """Bounded waiting room with the proxy's overflow contract."""

    def __init__(self, limit: int, retry_after: int):
        self.limit = int(limit)
        self.retry_after = int(retry_after)
        self._outstanding = 0
        self._settled = 0
        self._lock = threading.Lock()

    def admit(self, request: Request, waiting: int) -> AdmissionTicket:
        """Issue a ticket, or answer the overflow contract when
        ``waiting`` (the scheduler's pending depth) is at the limit."""
        with self._lock:
            if waiting >= self.limit:
                HUB.inc("gen_rejected_total")
                raise QueueOverflow(waiting, self.limit, self.retry_after)
            self._outstanding += 1
        return AdmissionTicket(self, request)

    def _settle(self) -> None:
        with self._lock:
            self._outstanding -= 1
            self._settled += 1

    def describe(self) -> dict[str, Any]:
        with self._lock:
            return {"limit": self.limit, "retry_after_s": self.retry_after,
                    "outstanding": self._outstanding,
                    "settled": self._settled}


class _Seq:
    """Engine-internal running-sequence state. ``length``, ``planned`` and
    ``row`` run ahead of ``generated``: they count the programs dispatched,
    ``generated`` the tokens pulled and emitted."""

    __slots__ = ("req", "lease", "length", "last_tok", "generated",
                 "planned", "row", "retired", "first")

    def __init__(self, req: Request, lease, length: int, last_tok: int):
        self.req = req
        self.lease = lease
        self.length = length      # KV positions the dispatched steps write
        self.last_tok = last_tok  # the newest token the host has seen
        self.generated = 0        # emitted; the first comes from the prefill
        self.planned = 1          # tokens the dispatched programs yield
        #: the slot of the newest ids on the device where the next step
        #: finds its token: its row of the newest decode step, or where its
        #: prefill's id was set; -1: ``last_tok`` is fed from the host
        self.row = -1
        self.retired = False
        #: its prefill's ``(new_shape, ids, stats)``, on the device, until
        #: the first token is pulled
        self.first = None


class _Step:
    """One dispatched decode step: who rode it, and its outputs, still on
    the device and possibly still being computed."""

    __slots__ = ("batch", "width", "kv_positions", "rows", "ahead",
                 "new_shape", "ids", "stats")

    def __init__(self, batch: list[_Seq], width: int, kv_positions, rows,
                 ahead: bool, new_shape: bool):
        self.batch = batch
        self.width = width
        #: ``(width, read)``: the table's positions, and those the step's
        #: attention over whole rows reads of them (``kvcache``)
        self.kv_positions = kv_positions
        self.rows = rows            # shipped; dropped once launched
        self.ahead = ahead          # dispatched behind a step in flight
        self.new_shape = new_shape  # first run of its (bucket, width)
        self.ids = None             # [_pow2(max_batch)] int32, by _launch
        self.stats: list = []


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


class GenEngine:
    """The serving loop: one thread, one model, one paged pool.

    All cross-thread state (`_pending`, `_running`, `_stop`, token
    counters) is guarded by ``_work``'s lock; the pool's arrays are
    engine-thread-only: they live on the device, both programs take them
    donated (``pool.apply``) and what crosses the link a step is a block
    table one way and the chosen ids (4 B a row) the other. ``_flight``
    (the decode step dispatched and not yet pulled) and ``_prev_ids`` (the
    newest step's ids, which the next step reads on the device) are the
    engine thread's too.
    """

    def __init__(self, params, cfg, mesh=None, *,
                 pool: KVBlockPool | None = None,
                 max_batch: int = 8,
                 queue_limit: int = 64,
                 max_new_tokens: int = 256,
                 block_tokens: int = 16,
                 kv_mb: int = 256,
                 model: str = "inline"):
        import jax
        import jax.numpy as jnp
        import numpy as np

        # the loaded config names its model module, which states its cache
        # (``cache_spec(cfg)``, a ``kvcache.CacheSpec``: the pool is built
        # from it) and whose two step functions the programs below run:
        # ``step_prefill(params, tokens, cfg, mesh=) -> (last_logits, new,
        # *stats)`` and ``step_decode(params, tokens, cfg, cache, lengths,
        # mesh=) -> (logits, new, *stats)``, ``cache`` a ``kvcache.Paged``
        # (the pool's arrays, the batch's block table and slots) for every
        # module, ``new`` the layers' new keys and values (a layer's one
        # new array where the spec's page is one array, and ``cache.v`` is
        # then None), inside a ``kvcache.Written`` with what goes into the
        # slots where the module keeps such state. ``stats`` (small arrays,
        # or none) come back with the chosen ids and go to the module's
        # ``observe``, which counts them and names the step span's
        # attributes.
        module = sys.modules[type(cfg).__module__]
        if not hasattr(module, "step_decode"):
            raise ValueError(
                f"serving needs step_prefill and step_decode, which "
                f"{module.__name__} ({type(cfg).__name__}) does not have")
        self._module = module

        compile_cache.place()
        # every span of the process on the profiler's clock: with no
        # profiler session the annotation does nothing
        trace.set_annotator(jax.profiler.TraceAnnotation)
        if params["embed"].dtype != jax.numpy.dtype(cfg.dtype):
            # config.json and the safetensors disagree: say so at boot,
            # not as a dtype error inside the first request's prefill
            raise ValueError(
                f"weights are {params['embed'].dtype} but the model "
                f"config says {cfg.dtype}")
        self.params = params
        self.cfg = cfg
        self.mesh = mesh
        self.model = model
        self.max_batch = int(max_batch)
        # a slot a running sequence: one freed by a row still in flight is
        # written by that row before the prefill that takes it over (the
        # device runs them in the order they were queued), as its blocks are
        self.pool = pool if pool is not None else KVBlockPool(
            module.cache_spec(cfg), slots=self.max_batch,
            block_tokens=block_tokens, budget_mb=kv_mb, dtype=cfg.dtype,
            mesh=mesh)
        self.max_new_cap = int(max_new_tokens)
        self.admission = AdmissionQueue(queue_limit, RETRY_AFTER_S)

        #: every decode step returns this many ids, whatever its bucket,
        #: so the ids one step hands the next have one shape for life
        n_ids = _pow2(self.max_batch)

        def choose(logits, n):
            # greedy, the first maximum, in float32 (which holds every
            # value of the model's dtype); rows past the bucket read 0
            ids = jnp.argmax(logits.astype(jnp.float32), axis=-1)
            return jnp.pad(ids.astype(jnp.int32), (0, n - ids.shape[0]))

        pool = self.pool
        names = pool.state_names
        #: a row's (a prompt's) slot rides with its block ids, one more
        #: int32, where the pool has slots; nothing is shipped where not
        self._slotted = int(bool(names))

        def held(arrays):
            """``(k, v, state)`` of the pool's arrays, ``v`` None where
            the page is one array (a latent layer's)."""
            if pool.pages == 1:
                return arrays[0], None, arrays[1:]
            return arrays[0], arrays[1], arrays[2:]

        def prefill(p, tokens, blocks, *arrays):
            k, v, state = held(arrays)
            logits, new, *stats = module.step_prefill(p, tokens, cfg,
                                                     mesh=mesh)
            kv, fresh = kvcache.parts(new)
            if names:       # the lease's slot comes behind its block ids
                blocks, slot = blocks[:-1], blocks[-1:]
                state = kvcache.put_slots(state, names, fresh, slot)
            return ((choose(logits, 1), (logits, *stats)),
                    *kvcache.put_blocks(k, v, kv, blocks), *state)

        def decode(p, rows, prev_ids, *arrays):
            k, v, state = held(arrays)
            # one int32 row a sequence (see _decode_inputs)
            lit, lens, wblocks, woffsets, src = (rows[:, i]
                                                 for i in range(5))
            toks = jnp.where(src >= 0, prev_ids[jnp.maximum(src, 0)], lit)
            if names:
                slots, table = rows[:, 5], rows[:, 6:]
                cache = kvcache.Paged(k, v, table, dict(zip(names, state)),
                                      slots, pool.meshed)
            else:
                cache = kvcache.Paged(k, v, rows[:, 5:], meshed=pool.meshed)
            logits, new, *stats = module.step_decode(p, toks, cfg, cache,
                                                    lens, mesh=mesh)
            new_kv, fresh = kvcache.parts(new)
            return ((choose(logits, n_ids), (logits, *stats)),
                    *kvcache.put_positions(k, v, new_kv, wblocks, woffsets),
                    *kvcache.put_slots(state, names, fresh, cache.slots))

        # the pool goes in donated and comes back as it was born
        # (kvcache: "one signature for life"), the ids come back
        # replicated, as the next step takes them; a program's shapes
        # follow the prompt length, or (batch bucket, width), and nothing
        # else. The logits stay on the device: nothing the engine does
        # pulls them (chip_smoke and the tests of a model module do)
        back = ((pool.replicated, None), *pool.shardings)
        donated = tuple(range(3, 3 + len(pool.arrays)))
        self._jprefill = jax.jit(prefill, donate_argnums=donated,
                                 out_shardings=back)
        self._jdecode = jax.jit(decode, donate_argnums=donated,
                                out_shardings=back)
        # what the first step takes for the previous step's ids: born as a
        # program's output with the sharding every later one has, so the
        # first step runs the executable the others run (PR 25's trap)
        self._ids0 = jax.jit(lambda: jnp.zeros((n_ids,), jnp.int32),
                             out_shardings=pool.replicated)()
        self._prev_ids = self._ids0
        # a prefill queued behind a step in flight leaves its id on the
        # device: this sets it at a free slot of the ids the next step
        # reads. One shape for life, held as the compiled object: a jitted
        # function would be compiled again after jax.clear_caches(), the
        # first time an admission found a step in flight. The slots'
        # numbers are on the device from now on
        int32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                                  sharding=pool.replicated)
        self._set_id = jax.jit(
            lambda ids, slot, tok: jax.lax.dynamic_update_slice(
                ids, tok, (slot,)),
            out_shardings=pool.replicated).lower(
                int32((n_ids,)), int32(()), int32((1,))).compile()
        self._slots = jax.device_put(
            list(np.arange(n_ids, dtype=np.int32)), pool.replicated)
        self._flight: _Step | None = None
        self._pending: deque[Request] = deque()
        self._running: list[_Seq] = []
        self._stop = False
        self._work = threading.Condition(threading.Lock())
        self._ids = itertools.count(1)
        self._tokens = {"prefill": 0, "decode": 0}
        #: prompt lengths and (batch bucket, width) pairs already run
        #: (engine thread only): the first run of each compiles or loads
        #: a program where it is dispatched
        self._shapes_run: set[tuple] = set()
        self.started_s = time.time()
        self._thread = threading.Thread(target=self._run, name="gen-engine",
                                        daemon=True)

    # ------------------------------------------------------------ public
    def start(self) -> "GenEngine":
        self._thread.start()
        return self

    def stop(self) -> None:
        """Stop the loop and settle every in-flight request (error =
        shutdown) — blocks are freed, tickets finished, streams closed."""
        with self._work:
            self._stop = True
            self._work.notify_all()
        if self._thread.ident is not None:  # tolerate never-started engines
            self._thread.join(timeout=30)
            if self._thread.is_alive():
                # the engine thread is still inside a step (e.g. a long
                # jit compile) and will still write into leased blocks —
                # reclaiming them now would hand corruptible memory to
                # the next lease. Leave all state for the thread to
                # settle when it reaches the stop check.
                with self._work:
                    n_run, n_pend = len(self._running), len(self._pending)
                log.error("engine thread still running after 30s; "
                          "leaving %d leases and %d pending requests "
                          "unreclaimed", n_run, n_pend)
                return
        with self._work:
            leftovers = list(self._pending) + [s.req for s in self._running]
            seqs = list(self._running)
            self._pending.clear()
            self._running.clear()
        for seq in seqs:
            seq.lease.free()
        for req in leftovers:
            self._finish_req(req, error="engine shutdown")
        HUB.set_gauge("gen_queue_depth", 0)
        HUB.set_gauge("gen_running", 0)

    def submit(self, prompt, max_new_tokens: int | None = None) -> Request:
        """Admit one request (greedy decode). Raises
        :class:`QueueOverflow` when the waiting room is full and
        ``ValueError`` on malformed input — both before any KV is
        reserved."""
        toks = [int(t) for t in prompt]
        if not toks:
            raise ValueError("empty prompt")
        if any(t < 0 or t >= self.cfg.vocab_size for t in toks):
            raise ValueError("prompt token out of vocab range")
        want = int(max_new_tokens or self.max_new_cap)
        want = max(1, min(want, self.max_new_cap))
        # a request whose worst-case reservation exceeds the whole pool
        # can NEVER be admitted — and FIFO admission means it would wedge
        # every request behind it. Reject it here (HTTP 400), not in the
        # engine loop.
        need = self.pool.blocks_for(len(toks) + want - 1)
        if need > self.pool.num_blocks:
            raise ValueError(
                f"request needs {need} KV blocks (prompt {len(toks)} + "
                f"{want} new tokens) but the pool only has "
                f"{self.pool.num_blocks}; shorten the prompt or lower "
                f"max_new_tokens")
        req = Request(next(self._ids), toks, want)
        rejected: QueueOverflow | None = None
        with trace.span("serve.admit", request=req.id, prompt=len(toks)):
            # a head-sampled-out request keeps none: its prefill then
            # rolls for itself, as every prefill did before
            if not trace.subtree_suppressed():
                req.traceparent = trace.traceparent()
            with self._work:
                if self._stop:
                    raise RuntimeError("engine stopped")
                try:
                    ticket = self.admission.admit(req, len(self._pending))
                except QueueOverflow as exc:
                    # a full waiting room is an OUTCOME, not an error —
                    # the span records it without tripping the flight
                    # recorder's error-root dump
                    trace.event("rejected", retry_after=exc.retry_after)
                    rejected = exc
                else:
                    req.ticket = ticket
                    self._pending.append(req)
                    # publish while still holding _work so concurrent
                    # submitters can't regress the gauge with a stale depth
                    HUB.inc("gen_requests_total")
                    HUB.set_gauge("gen_queue_depth", len(self._pending))
                    self._work.notify_all()
        if rejected is not None:
            raise rejected
        return req

    def generate(self, prompt, max_new_tokens: int | None = None,
                 timeout: float = 300.0) -> list[int]:
        """Synchronous convenience: submit + wait."""
        return self.submit(prompt, max_new_tokens).result(timeout)

    def describe(self) -> dict[str, Any]:
        with self._work:
            waiting = len(self._pending)
            running = len(self._running)
            tokens = dict(self._tokens)
        return {
            "model": self.model,
            "running": running,
            "waiting": waiting,
            "max_batch": self.max_batch,
            "tokens": tokens,
            "uptime_s": round(time.time() - self.started_s, 3),
            "admission": self.admission.describe(),
            "kv": self.pool.describe(),
            "programs": compile_cache.programs(),
        }

    # ------------------------------------------------------ engine loop
    def _run(self) -> None:
        while True:
            with self._work:
                while not self._stop and not self._pending \
                        and not self._running and self._flight is None:
                    self._work.wait()
                if self._stop:
                    # a step in flight is left to the device; stop()
                    # settles its sequences
                    self._flight = None
                    return
            if not self._turn():
                # pending work exists but nothing could be admitted and
                # nothing is running (shouldn't happen now that submit()
                # rejects over-pool requests, but e.g. a leaked lease
                # could still get here): sleep instead of busy-spinning.
                # submit()/stop() notify; the timeout bounds recovery if
                # a free lands without a notify.
                with self._work:
                    if not self._stop and self._pending \
                            and not self._running:
                        self._work.wait(timeout=0.05)

    def _turn(self) -> bool:
        """One turn of the loop: admissions on an empty pipe, evictions,
        one decode cycle. False when there was nothing it could do."""
        progressed = False
        # with no step in flight there is nothing to ride: the prefill
        # runs alone and its id is pulled at once. Over a step in flight
        # the cycle admits (_decode_step, _ride)
        while self._flight is None and self._admit_one():
            progressed = True
        self._evict_cancelled()
        if self._flight is not None or self._snapshot_running():
            self._decode_step()
            return True
        return progressed

    def _snapshot_running(self) -> list[_Seq]:
        with self._work:
            return list(self._running)

    def _take_head(self):
        """Take the head of the queue if it can be admitted now, with its
        worst-case blocks reserved: ``(request, lease)``; the lease is
        None for one cancelled while it waited, which is settled here.
        None when the batch is full, the queue is empty, or blocks are
        short (head-of-line waits for frees: admission order is FIFO, no
        starvation)."""
        with self._work:
            if self._stop or not self._pending \
                    or len(self._running) >= self.max_batch:
                return None
            req = self._pending[0]
            lease = None
            if not req.cancelled.is_set():
                need = self.pool.blocks_for(
                    len(req.prompt) + req.max_new_tokens - 1)
                try:
                    lease = self.pool.alloc(need)
                except PoolExhausted:
                    return None
                cancelled = True
                try:
                    cancelled = req.cancelled.is_set()
                finally:
                    if cancelled:
                        # cancel landed between the head check and the
                        # alloc — free right here or the blocks/budget
                        # bytes leak forever
                        lease.free()
                        lease = None
            self._pending.popleft()
            depth = len(self._pending)
        HUB.set_gauge("gen_queue_depth", depth)
        if lease is None:
            HUB.inc("gen_evicted_total")
            self._finish_req(req, error="cancelled before start")
        return req, lease

    def _admit_one(self) -> bool:
        """Move one waiting request into the running batch on an empty
        pipe: reserve its blocks, prefill, emit its first token. False
        when nobody can be admitted now."""
        got = self._take_head()
        if got is None:
            return False
        req, lease = got
        if lease is not None:
            self._start_seq(req, lease)
        return True

    def _first_run(self, stage: str, *shape: int) -> bool:
        """True the first time the engine runs this shape, counted in
        ``gen_new_shapes_total``."""
        key = (stage, *shape)
        if key in self._shapes_run:
            return False
        self._shapes_run.add(key)
        HUB.inc(labeled("gen_new_shapes_total", stage=stage))
        return True

    def _prefill(self, prompt: list[int], lease, new_shape: bool = False):
        """Ship a prompt and its lease's block ids, run the prefill
        program over the pool (it writes those blocks itself), and
        return ``(ids, (logits, *stats))``: the token it chose ``[1]``,
        the last position's logits ``[1, V]`` and the model's stats, if
        it has any, still on the device and possibly still being
        computed. The first run of a prompt length (``new_shape``) makes
        a program ready where it is dispatched: that dispatch alone lies
        in a ``serve.program-ready`` span, which ``compile_cache``'s
        listener gives the seconds of each phase and ``how``."""
        import jax
        import numpy as np

        pool = self.pool
        tokens = np.asarray([prompt], np.int32)
        blocks = np.asarray(lease.blocks[:pool.blocks_for(len(prompt))]
                            + [lease.slot] * self._slotted, np.int32)
        sent = jax.device_put((tokens, blocks), pool.replicated)
        HUB.inc("gen_h2d_bytes_total", tokens.nbytes + blocks.nbytes)
        compile_cache.dispatching.shape = ("prefill", len(prompt))
        with (trace.span(compile_cache.READY_SPAN, stage="prefill",
                         prompt=len(prompt)) if new_shape else trace.NOOP):
            return pool.apply(self._jprefill, self.params, *sent)

    def _decode_inputs(self, batch: list[_Seq]):
        """What one decode step ships, built from the leases: ``(width,
        rows)``. ``rows`` is one int32 array, a row a sequence of the
        batch bucket — token id, length, the block and the offset its new
        position is written at, the row of the previous step's ids its
        token is taken from on the device (-1: the id in column 0 is fed),
        its state slot where the pool has slots, then its slots of the
        block table — so its shape follows (bucket, width) alone and it
        crosses the link in one transfer. The width follows the longest
        row (``kvcache.table_slots``): two tiles up to two tiles, coarse
        steps past that, where it is capacity and not work."""
        import numpy as np

        pool = self.pool
        bs = pool.block_tokens
        nb = kvcache.table_slots(-(-max(s.length for s in batch) // bs))
        at = 5 + self._slotted      # where the block table starts
        # a slot a sequence does not have reads block 0 (masked by its
        # length); a pad row rides along with length 0, is dropped on the
        # host, and writes into the block and the slot no lease can hold
        rows = np.zeros((_pow2(len(batch)), at + nb), np.int32)
        rows[:, 2] = pool.scratch_block
        rows[:, 4] = -1
        rows[:, 5:at] = pool.scratch_slot
        for row, s in zip(rows, batch):
            got = s.lease.blocks[:nb]
            row[:5] = (s.last_tok, s.length, s.lease.blocks[s.length // bs],
                       s.length % bs, s.row)
            if self._slotted:
                row[5] = s.lease.slot
            row[at:at + len(got)] = got
        return bs * nb, rows

    def _begin(self, req: Request, lease) -> _Seq | None:
        """Ship a request's prompt and queue its prefill: the sequence,
        running from now on, its first token still on the device. None
        when that failed with the arrays still the pool's (tracing,
        compilation), which costs this request alone; a failure that took
        them is raised on, for the caller to settle the pool."""
        T = len(req.prompt)
        new_shape = self._first_run("prefill", T)
        try:
            ids, (_logits, *stats) = self._prefill(req.prompt, lease,
                                                   new_shape)
        except Exception as exc:  # noqa: BLE001 - engine must survive
            lease.free()
            log.error("prefill failed for request %d: %s", req.id, exc)
            self._finish_req(req, error=f"prefill failed: {exc}")
            if self.pool.lost:
                raise
            return None
        seq = _Seq(req, lease, T, 0)
        seq.first = (new_shape, ids, stats)
        with self._work:
            self._running.append(seq)
            running = len(self._running)
        HUB.set_gauge("gen_running", running)
        return seq

    def _ride(self, batch: list[_Seq]) -> list[_Seq]:
        """Admit everybody who can be admitted now behind the step in
        flight: each prefill is queued on the pool that step returns, and
        the id it will choose is set, on the device, into the ids the next
        step reads, at a slot no row of ``batch`` (who rides that step
        already) is read from. ``_pow2(max_batch)`` slots and a row free
        for each admission, so there is one. A one-token request needs
        none."""
        taken = {s.row for s in batch}
        free = (i for i in range(len(self._slots)) if i not in taken)
        joined = []
        while (got := self._take_head()) is not None:
            req, lease = got
            seq = self._begin(req, lease) if lease is not None else None
            if seq is None:
                continue
            if req.max_new_tokens > 1:
                seq.row = next(free)
                self._prev_ids = self._set_id(
                    self._prev_ids, self._slots[seq.row], seq.first[1])
            joined.append(seq)
        return joined

    def _start_seq(self, req: Request, lease, seq: _Seq | None = None
                   ) -> None:
        """A request's first token, under its ``serve.prefill`` span. On
        an empty pipe (``seq`` None) the span holds the whole admission:
        the prompt's ship and the dispatch inside ``serve.prefill-device``,
        then the pull of the id. Behind a step in flight ``_ride`` has
        shipped and dispatched in the cycle that was open, and the span is
        the wait for the id, between that cycle and the next, while the
        device runs the prefill with the next step queued behind it. A
        program that failed is found here, at the pull: whoever rode the
        pool after it is retired with it."""
        import jax

        ahead = seq is not None
        if ahead and seq.retired:   # went with a prefill that failed
            return
        req.started_s = time.time()
        HUB.observe("gen_queue_wait_seconds",
                    req.started_s - req.submitted_s)
        HUB.inc(labeled("gen_prefills_total", ahead=str(int(ahead))))
        T = len(req.prompt)
        try:
            with trace.span("serve.prefill", remote_parent=req.traceparent,
                            request=req.id, prompt=T, ahead=ahead):
                with trace.span("serve.prefill-device", prompt=T) as dev:
                    seq = seq or self._begin(req, lease)
                    if seq is None:
                        return
                    new_shape, ids, stats = seq.first
                    dev.set_attr("new_shape", new_shape)
                    if self._slotted:
                        # the whole slot, written (a module whose steps
                        # move a part of it names its own: _observe)
                        dev.set_attr("state_bytes", self.pool.slot_bytes)
                    pulled = ids.nbytes + sum(a.nbytes for a in stats)
                    if trace.enabled():
                        # export tier only, like the compute spans: off
                        # it the span ends at dispatch and the pull of
                        # the id takes the wait
                        jax.block_until_ready((ids, *stats))
                        self._observe(dev, jax.device_get(stats), T, T)
                        stats = []
                ids, *stats = jax.device_get([ids, *stats])
                seq.first = None
                HUB.inc("gen_d2h_bytes_total", pulled)
                self._observe(None, stats, T, T)
        except Exception as exc:  # noqa: BLE001 - engine must survive
            if seq is not None:
                log.error("prefill failed for request %d: %s", req.id, exc)
                self._retire(seq, error=f"prefill failed: {exc}")
            self._flight = None
            self._prev_ids = self._ids0
            self._settle_pool(True, f"prefill failed: {exc}")
            return
        with self._work:
            self._tokens["prefill"] += T
        HUB.inc(labeled("gen_tokens_total", stage="prefill"), T)
        seq.last_tok = int(ids[0])
        seq.generated = 1
        req._emit(seq.last_tok)
        HUB.inc(labeled("gen_tokens_total", stage="decode"))
        if seq.generated >= req.max_new_tokens:
            self._retire(seq)

    def _observe(self, span, stats: list, tokens: int, rows: int) -> None:
        """A step's stats, pulled to the host, to the model module that
        made them: it counts them and names ``span``'s attributes.
        ``tokens`` of the program's ``rows`` were live (a bucket's pad
        rows are not); the platform the program was lowered for is the
        pool's devices'."""
        if not stats:
            return
        attrs = self._module.observe(*stats, tokens=tokens, cfg=self.cfg,
                                     platform=self.pool.platform, rows=rows)
        if span is not None:
            for key, value in attrs.items():
                span.set_attr(key, value)

    def _settle_pool(self, applied: bool, error: str) -> None:
        """After a program failed: if it had the pool's arrays in hand
        (donated and gone, or returned by a run that then failed), what
        every running sequence cached is lost with them — retire them
        all with the error and go on with a fresh, empty pool."""
        if not (applied or self.pool.lost):
            return
        for seq in self._snapshot_running():
            self._retire(seq, error=error)
        compile_cache.dispatching.shape = ("other",)
        self.pool.reset()

    def _evict_cancelled(self) -> None:
        for seq in self._snapshot_running():
            if seq.req.cancelled.is_set():
                HUB.inc("gen_evicted_total")
                self._retire(seq, error="evicted")

    def _ship(self, batch: list[_Seq], ahead: bool) -> _Step:
        """Build and send one step's rows from what the host knows of
        ``batch`` and advance each sequence past the step, so that the
        next one can be built before this one has run."""
        import jax

        width, rows = self._decode_inputs(batch)
        bs = self.pool.block_tokens
        positions = kvcache.positions_read(
            [s.length for s in batch], len(rows), width // bs, bs)
        for i, seq in enumerate(batch):
            seq.length += 1
            seq.planned += 1
            seq.row = i
        step = _Step(batch, width, positions,
                     jax.device_put(rows, self.pool.replicated),
                     ahead, self._first_run("decode", len(rows), width))
        HUB.inc("gen_h2d_bytes_total", rows.nbytes)
        return step

    def _launch(self, step: _Step) -> None:
        """Queue the program of a shipped step on the pool the newest
        program returns, fed by the newest step's ids; its own ids start
        for the host the moment it ends. What it dispatches it names
        first, for the listener of ``compile_cache``, and the first run of
        a (bucket, width) lies in a ``serve.program-ready`` span, as a
        prefill's does; no other run opens a span."""
        bucket = len(step.rows)
        compile_cache.dispatching.shape = ("decode", bucket, step.width)
        with (trace.span(compile_cache.READY_SPAN, stage="decode",
                         batch=bucket, width=step.width)
              if step.new_shape else trace.NOOP):
            ids, (_logits, *stats) = self.pool.apply(
                self._jdecode, self.params, step.rows, self._prev_ids)
        for out in (ids, *stats):
            out.copy_to_host_async()
        step.rows, step.ids, step.stats = None, ids, stats
        self._prev_ids = ids

    def _decode_step(self) -> None:
        """Advance every running sequence one token, ragged lengths and
        all — the continuous-batching inner loop, one step ahead of the
        host. One cycle is one ``serve.decode-step`` span around four
        children: ``serve.decode-h2d`` (the rows of the steps it
        dispatches: the step to pull if the pipe is empty, and the next
        one; behind a step in flight also the prompt and the dispatch of
        whoever is admitted, who rides the next step: ``_ride``),
        ``serve.decode-device`` (those steps' dispatches and the wait for
        the ids of the step in flight, which the device runs meanwhile),
        ``serve.decode-fetch`` (ids and stats, 4 B a row) and
        ``serve.decode-post`` (emit, retire). The span carries the
        attributes of the step it pulls. Once it has closed, those
        admitted get their first tokens, each under its ``serve.prefill``
        span. A program that failed is found here, at the pull, with its
        successor queued on a pool that is lost: both steps' sequences
        are retired."""
        import jax

        flight, self._flight = self._flight, None
        applied = flight is not None
        running = [] if applied else self._snapshot_running()
        if not (applied or running):
            return
        todo: list[_Step] = []
        joined: list[_Seq] = []
        nxt = None
        try:
            with trace.span("serve.decode-step") as cycle:
                with trace.span("serve.decode-h2d") as ship:
                    if flight is None:
                        flight = self._ship(running, ahead=False)
                        todo.append(flight)
                    batch = [s for s in flight.batch if not s.retired
                             and s.planned < s.req.max_new_tokens]
                    if applied:
                        joined = self._ride(batch)
                        batch += [s for s in joined if s.row >= 0]
                    if batch:
                        nxt = self._ship(batch, ahead=True)
                        todo.append(nxt)
                    ship.set_attr("bytes", sum(t.rows.nbytes for t in todo))
                B = len(flight.batch)
                table, read = flight.kv_positions
                in_place = self.pool.positions_in_place(
                    flight.width // self.pool.block_tokens, read)
                for key, value in (("batch", B), ("width", flight.width),
                                   ("ahead", flight.ahead),
                                   ("kv_positions_width", table),
                                   ("kv_positions_read", read),
                                   ("kv_positions_in_place", in_place)):
                    cycle.set_attr(key, value)
                HUB.inc("gen_kv_positions_width_total", table)
                HUB.inc("gen_kv_positions_read_total", read)
                HUB.inc("gen_kv_positions_in_place_total", in_place)
                if self._slotted:
                    # each row's slot, read and written, unless the module
                    # names what its step moved of it (_observe, below)
                    cycle.set_attr("state_bytes",
                                   2 * B * self.pool.slot_bytes)
                with trace.span("serve.decode-device", batch=B,
                                width=flight.width,
                                new_shape=any(t.new_shape for t in todo)):
                    for step in todo:
                        applied = True
                        self._launch(step)
                    self._flight = nxt
                    # the fetch's pull would wait here anyway
                    jax.block_until_ready((flight.ids, *flight.stats))
                pulled = flight.ids.nbytes + sum(a.nbytes
                                                 for a in flight.stats)
                with trace.span("serve.decode-fetch", bytes=pulled):
                    ids, *stats = jax.device_get([flight.ids, *flight.stats])
                    # the device's copies go now, not as the frame ends:
                    # a buffer's release lets go of the interpreter lock,
                    # and once the emit has woken the handlers that costs
                    # a turn behind each of them, after the span closed
                    # (1.7 ms a cycle at 32 rows)
                    flight.ids = flight.stats = None
                    HUB.inc("gen_d2h_bytes_total", pulled)
                # the bucket's rows: the table's positions over its width
                self._observe(cycle, stats, B, table // flight.width)
                with trace.span("serve.decode-post", batch=B) as post:
                    retired = emitted = 0
                    for seq, tok in zip(flight.batch, ids.tolist()):
                        if seq.retired:     # evicted with its row in flight
                            continue
                        seq.last_tok = tok
                        seq.generated += 1
                        seq.req._emit(tok)
                        emitted += 1
                        if seq.generated >= seq.req.max_new_tokens:
                            self._retire(seq)
                            retired += 1
                    with self._work:
                        self._tokens["decode"] += emitted
                    HUB.inc(labeled("gen_tokens_total", stage="decode"),
                            emitted)
                    HUB.inc(labeled("gen_decode_steps_total",
                                    ahead=str(int(flight.ahead))))
                    post.set_attr("retired", retired)
        except Exception as exc:  # noqa: BLE001 - engine must survive
            # a step dispatched ahead carries none but riders of ``flight``
            riders = [s for s in (flight.batch if flight else running)
                      if not s.retired]
            log.error("decode step failed (batch=%d): %s", len(riders), exc)
            for seq in riders:
                self._retire(seq, error=f"decode failed: {exc}")
            self._flight = None
            self._prev_ids = self._ids0
            self._settle_pool(applied, f"decode failed: {exc}")
            return
        for seq in joined:
            self._start_seq(seq.req, seq.lease, seq)

    def _retire(self, seq: _Seq, error: str | None = None) -> None:
        """Finished/evicted/failed: blocks free IMMEDIATELY (the next
        _admit_one can use them this very iteration)."""
        seq.lease.free()
        seq.retired = True
        with self._work:
            if seq in self._running:
                self._running.remove(seq)
            running = len(self._running)
        HUB.set_gauge("gen_running", running)
        self._finish_req(seq.req, error=error)

    def _finish_req(self, req: Request, error: str | None = None) -> None:
        req.error = error
        if req.ticket is not None:
            req.ticket.finish()
        req._close()
