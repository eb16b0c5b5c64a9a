"""Unified tiered store: host-RAM mmap hot tier ← disk ← peers ← origin.

One tier API over the content-addressed :class:`~demodel_tpu.store.Store`
(ROADMAP item 2): byte-budgeted LRU per tier, digest-verified promotion,
and **single-flight admission** at every miss edge — a cold key requested
by N concurrent callers costs exactly one upstream fetch, with every
waiter served *off the landing stream* via progress-watermark reads
against the store's resumable partials (the Python twin of the native
proxy's ``FillState`` attach), not fetch-completion barriers. A leader
that dies mid-stream elects the next waiter — which resumes the partial
with a ranged fetch — instead of failing the cohort; a digest mismatch
fails the cohort WITHOUT poisoning the key (the next request starts a
fresh flight).

Tiers and their budgets:

- **ram** — committed store objects mmap'd into host RAM, LRU under
  ``DEMODEL_TIER_RAM_MB``. The swarm plane's chunk boards charge the
  SAME budget (a host mid-swarm-pull holds chunk bytes in RAM that the
  hot tier must make room for — swarm-aware eviction).
- **disk** — the store itself under ``DEMODEL_CACHE_MAX_GB``, evicted
  through :meth:`Store.gc` (pin shield and ``store_evictions_total``
  semantics unchanged).

Dep-light by design (stdlib + the native store wrapper; no jax): the
restore server, the proxy launcher, and statusz all touch this module on
nodes that must never pay a jax import. statusz reads
:func:`tiers_snapshot` via its usual ``sys.modules`` peek.
"""

from __future__ import annotations

import errno
import hashlib
import mmap
import os
import threading
import time
import weakref
from typing import Any, Callable, Iterable

from demodel_tpu.store import Store
from demodel_tpu.utils import trace
from demodel_tpu.utils.env import (cache_max_gb, default_tier_ram_mb,
                                   store_reprobe_secs)
from demodel_tpu.utils.faults import DigestMismatch
from demodel_tpu.utils.logging import get_logger
from demodel_tpu.utils.metrics import HUB, labeled

log = get_logger("tier")

#: pre-register the tier/single-flight counter families at import so a
#: scrape types them (``# TYPE … counter``) before the first event
HUB.inc(labeled("store_tier_hits_total", tier="ram"), 0)
HUB.inc(labeled("store_tier_hits_total", tier="disk"), 0)
HUB.inc(labeled("store_tier_misses_total", tier="ram"), 0)
HUB.inc(labeled("store_tier_misses_total", tier="disk"), 0)
HUB.inc(labeled("store_tier_promotions_total", tier="ram"), 0)
HUB.inc(labeled("store_tier_evicted_bytes_total", tier="ram"), 0)
HUB.inc("singleflight_leaders_total", 0)
HUB.inc("singleflight_waiters_total", 0)
HUB.inc("singleflight_handoffs_total", 0)
#: storage-fault plane families (ISSUE 19): quarantines are counted by
#: Store.quarantine; degraded transitions and the 0/1 mode gauge live here
HUB.inc("store_quarantined_total", 0)
HUB.inc("store_degraded_entries_total", 0)
HUB.set_gauge("store_degraded", 0)

#: leader checkpoint cadence: every this-many landed bytes the partial is
#: fsync'd and the .progress watermark sidecar rewritten, bounding what a
#: kill -9 can force the next incarnation to refetch
_CHECKPOINT_BYTES = 8 << 20


def _tick(name: str, tier: str | None = None, n: int = 1) -> None:
    # demodel: allow(metric-hygiene) — forwarding helper: every caller
    # passes a literal family name, all pre-registered above
    HUB.inc(labeled(name, tier=tier) if tier else name, n)


class TierBudget:
    """Byte accounting for one tier (NOT a blocking semaphore — the
    :class:`~demodel_tpu.sink.streaming.ByteBudget` blocks producers; a
    tier budget instead drives eviction: charge unconditionally, then the
    owner evicts LRU entries until :meth:`over` is zero)."""

    def __init__(self, name: str, max_bytes: int):
        self.name = name
        self.max_bytes = int(max_bytes)
        self._in_use = 0
        self.high_water = 0
        self._lock = threading.Lock()

    @property
    def in_use(self) -> int:
        with self._lock:
            return self._in_use

    def charge(self, nbytes: int) -> None:
        with self._lock:
            self._in_use += int(nbytes)
            if self._in_use > self.high_water:
                self.high_water = self._in_use

    def release(self, nbytes: int) -> None:
        with self._lock:
            self._in_use -= int(nbytes)

    def over(self) -> int:
        """Bytes past the budget (0 when inside it, or unbounded)."""
        with self._lock:
            if self.max_bytes <= 0:
                return 0
            return max(0, self._in_use - self.max_bytes)

    def describe(self) -> dict[str, Any]:
        with self._lock:
            return {"name": self.name, "max_bytes": self.max_bytes,
                    "in_use_bytes": self._in_use,
                    "high_water_bytes": self.high_water}


#: process-wide host-RAM tier budget — the hot tier AND the swarm chunk
#: boards charge here, so a mid-pull host sheds mmap'd hot objects to
#: make room for landing chunks instead of overshooting host RAM
_ram_budget: TierBudget | None = None
_ram_budget_lock = threading.Lock()


def ram_budget() -> TierBudget:
    global _ram_budget
    with _ram_budget_lock:
        if _ram_budget is None:
            _ram_budget = TierBudget("tier-ram",
                                     default_tier_ram_mb() << 20)
        return _ram_budget


class _HotObj:
    __slots__ = ("mm", "size", "digest", "last_use")

    def __init__(self, mm: mmap.mmap, size: int, digest: str):
        self.mm = mm
        self.size = size
        self.digest = digest
        self.last_use = time.monotonic()


class HotTier:
    """mmap-backed host-RAM tier over COMMITTED store objects.

    Promotion maps ``objects/<key>`` read-only, hashes the mapped bytes,
    and verifies them against the store's content-address record (the
    ``digests/<sha256>`` hardlink must point at the same inode) — bytes
    that no longer match their digest are refused, never served.
    Demotion is a drop: the disk copy is canonical (verified at commit),
    so eviction releases the mapping and the budget charge.

    Reads return ``bytes`` copies taken under the lock — no exported
    memoryview can outlive an eviction's ``mmap.close()``.
    """

    def __init__(self, store: Store, budget: TierBudget | None = None):
        self.store = store
        self.budget = budget if budget is not None else ram_budget()
        self._objs: dict[str, _HotObj] = {}
        self._lock = threading.Lock()

    # -- reads -----------------------------------------------------------
    def read(self, key: str, offset: int = 0,
             length: int | None = None) -> bytes | None:
        with self._lock:
            obj = self._objs.get(key)
            if obj is None:
                return None
            obj.last_use = time.monotonic()
            end = obj.size if length is None else min(obj.size,
                                                      offset + length)
            _tick("store_tier_hits_total", "ram")
            return bytes(obj.mm[offset:end])

    def contains(self, key: str) -> bool:
        with self._lock:
            return key in self._objs

    # -- promotion / demotion -------------------------------------------
    def promote(self, key: str) -> bool:
        """disk → RAM, digest-verified. False when the object is absent,
        larger than the whole budget, or fails verification."""
        with self._lock:
            if key in self._objs:
                return True
        size = self.store.size(key)
        if size < 0:
            return False
        if self.budget.max_bytes > 0 and size > self.budget.max_bytes:
            return False  # would evict the entire tier for one object
        path = os.path.join(str(self.store.root), "objects", key)
        try:
            fd = os.open(path, os.O_RDONLY)
        except OSError:
            return False
        mm = None
        try:
            try:
                if size == 0:
                    return False  # nothing to map; zero-byte hits stay
                    # on disk
                mm = mmap.mmap(fd, size, prot=mmap.PROT_READ)
            except (OSError, ValueError):
                return False
            finally:
                os.close(fd)
            digest = hashlib.sha256(mm).hexdigest()
            if not self._digest_matches(key, path, digest):
                mm.close()
                log.warning("hot-tier promotion refused: %s fails digest "
                            "verification — quarantining", key)
                # bit-rot caught on the read path: move the object out of
                # the addressable namespace so the next request re-fetches
                # instead of re-verifying the same corrupt bytes forever
                self.store.quarantine(key)
                return False
            with self._lock:
                if key in self._objs:  # lost a promote race; keep the
                    mm.close()         # first mapping
                    return True
                self._objs[key] = _HotObj(mm, size, digest)
        except BaseException:
            # the mapping is this frame's obligation until it is stored:
            # a raise in close/hashing/verification must not strand a
            # PROT_READ mapping of the whole object
            if mm is not None:
                mm.close()
            raise
        self.budget.charge(size)
        _tick("store_tier_promotions_total", "ram")
        self.trim()
        return True

    def _digest_matches(self, key: str, obj_path: str, digest: str) -> bool:
        """The computed hash must be the store's content-address for this
        exact inode (``digests/<digest>`` hardlinked to ``objects/<key>``),
        or match the digest the commit recorded in the meta sidecar
        (private objects have no digest link). A computed hash that finds
        neither while the inode has extra hardlinks means the bytes
        diverged from their recorded address — only ``digests/`` ever
        hardlinks objects, so ``st_nlink >= 2`` proves a link exists
        under some OTHER hash. Objects with no recorded digest anywhere
        (hand-materialized fixtures) are accepted on the computed hash
        alone — there is nothing on record to disagree with."""
        link = os.path.join(str(self.store.root), "digests", digest)
        try:
            if os.stat(link).st_ino == os.stat(obj_path).st_ino:
                return True
        except OSError:
            pass
        meta = self.store.meta(key) or {}
        recorded = meta.get("sha256") or meta.get("digest")
        if recorded:
            return recorded == digest
        try:
            if os.stat(obj_path).st_nlink >= 2:
                return False  # content-addressed under a different hash
        except OSError:
            return False
        return True

    def invalidate(self, key: str) -> None:
        """Drop a key (store remove / re-put made the mapping stale)."""
        with self._lock:
            obj = self._objs.pop(key, None)
        if obj is not None:
            self._drop(obj)

    def _drop(self, obj: _HotObj) -> None:
        self.budget.release(obj.size)
        _tick("store_tier_evicted_bytes_total", "ram", obj.size)
        try:
            obj.mm.close()
        except BufferError:  # pragma: no cover — reads copy under the
            pass             # lock, so no exported view should be live

    def trim(self) -> int:
        """LRU-evict until the shared RAM budget is met (swarm chunk
        boards charge the same budget, so their landings push hot
        objects out first). Returns bytes evicted."""
        evicted = 0
        while self.budget.over() > 0:
            with self._lock:
                if not self._objs:
                    break  # the overshoot is chunk-board charge, not ours
                key = min(self._objs, key=lambda k: self._objs[k].last_use)
                obj = self._objs.pop(key)
            self._drop(obj)
            evicted += obj.size
        return evicted

    def describe(self) -> dict[str, Any]:
        with self._lock:
            objs, nbytes = len(self._objs), sum(
                o.size for o in self._objs.values())
        doc = self.budget.describe()
        doc.update({"tier": "ram", "objects": objs, "bytes": nbytes})
        return doc

    def close(self) -> None:
        with self._lock:
            objs, self._objs = list(self._objs.values()), {}
        for obj in objs:
            self._drop(obj)


# ---------------------------------------------------------- single-flight


class _Flight:
    """One in-flight cohort for one key: a leader landing bytes into the
    store partial, waiters following its progress watermark."""

    def __init__(self, key: str):
        self.key = key
        self.cv = threading.Condition()
        self.watermark = 0          # bytes durably in partial/<key>
        self.done = False
        self.ok = False
        self.error: BaseException | None = None
        self.leader_needed = False  # the leader died; next waiter claims
        self.waiters = 0
        self.handoffs = 0
        #: degraded read-through relay: when the disk can't land bytes the
        #: leader accumulates the object here instead of in partial/<key>;
        #: waiters read this buffer off the watermark and the herd still
        #: collapses onto one upstream stream
        self.buf: bytearray | None = None

    # leader side ---------------------------------------------------------
    def set_watermark(self, n: int) -> None:
        with self.cv:
            self.watermark = n
            self.cv.notify_all()

    def advance(self, n: int) -> None:
        with self.cv:
            self.watermark += n
            self.cv.notify_all()

    def start_relay(self, prefix: bytes) -> None:
        """Switch the flight to in-memory relay mode (degraded
        read-through), seeding it with whatever prefix already landed."""
        with self.cv:
            self.buf = bytearray(prefix)
            self.watermark = len(self.buf)
            self.cv.notify_all()

    def relay(self, chunk: bytes) -> None:
        with self.cv:
            assert self.buf is not None
            self.buf += chunk
            self.watermark = len(self.buf)
            self.cv.notify_all()

    def finish(self, ok: bool, error: BaseException | None = None) -> None:
        with self.cv:
            self.done = True
            self.ok = ok
            self.error = error
            self.cv.notify_all()

    def resign(self, error: BaseException) -> bool:
        """Leader failure: hand the flight to a waiter if any is present
        (returns True), else fail it. The partial stays on disk either
        way — the successor (this cohort's or a future flight's) resumes
        it with a ranged fetch instead of starting over."""
        with self.cv:
            if self.waiters > 0:
                self.leader_needed = True
                self.error = error  # surfaced if no waiter can take over
                self.cv.notify_all()
                return True
            self.done = True
            self.ok = False
            self.error = error
            self.cv.notify_all()
            return False


class SingleFlight:
    """Per-key admission registry: the first caller in becomes the
    leader, everyone else a waiter. A finished flight (ok or failed)
    leaves the registry immediately, so failure never poisons the key."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._flights: dict[str, _Flight] = {}

    def lease(self, key: str) -> tuple[_Flight, bool]:
        """(flight, is_leader). Waiters are counted in under the registry
        lock so a resigning leader can never miss them."""
        with self._lock:
            flight = self._flights.get(key)
            if flight is None:
                flight = _Flight(key)
                self._flights[key] = flight
                return flight, True
            with flight.cv:
                flight.waiters += 1
            return flight, False

    def finish(self, key: str, flight: _Flight) -> None:
        with self._lock:
            if self._flights.get(key) is flight:
                del self._flights[key]

    def in_flight(self) -> int:
        with self._lock:
            return len(self._flights)

    def describe(self) -> list[dict[str, Any]]:
        with self._lock:
            flights = list(self._flights.items())
        out = []
        for key, f in flights:
            with f.cv:
                out.append({"key": key, "watermark": f.watermark,
                            "waiters": f.waiters,
                            "handoffs": f.handoffs,
                            "leader_needed": f.leader_needed})
        return out

    # -- generic collapse (no watermark streaming) -----------------------
    def do(self, key: str, fn: Callable[[], Any],
           timeout: float | None = None) -> Any:
        """Collapse concurrent ``fn`` calls for one key: the leader runs
        it, waiters block on the outcome; a failed leader hands the call
        to the next waiter (each retry is ``fn`` again — resumable work
        resumes itself). Used at miss edges that land bytes positionally
        (parallel ranged peer fetch) where a linear watermark does not
        exist; the result of the leader's ``fn`` is NOT shared (callers
        re-read the store), only the admission is."""
        deadline = None if timeout is None else time.monotonic() + timeout
        flight, leader = self.lease(key)
        if not leader:
            _tick("singleflight_waiters_total")
            became_leader = False
            with flight.cv:
                while not flight.done and not flight.leader_needed:
                    if not _wait(flight.cv, deadline):
                        flight.waiters -= 1
                        raise TimeoutError(
                            f"single-flight wait for {key} timed out")
                if flight.leader_needed:
                    flight.leader_needed = False
                    flight.handoffs += 1
                    became_leader = True
                flight.waiters -= 1
                if not became_leader:
                    if flight.ok:
                        return None
                    raise flight.error or OSError(
                        f"single-flight fetch of {key} failed")
            _tick("singleflight_handoffs_total")
        _tick("singleflight_leaders_total")
        try:
            result = fn()
        except BaseException as e:
            if not flight.resign(e):
                self.finish(key, flight)
            raise
        flight.finish(ok=True)
        self.finish(key, flight)
        return result


def _wait(cv: threading.Condition, deadline: float | None) -> bool:
    """One bounded cv wait; False once the deadline passed."""
    if deadline is None:
        cv.wait()
        return True
    left = deadline - time.monotonic()
    if left <= 0:
        return False
    cv.wait(min(left, 1.0))
    return True


#: default per-waiter progress deadline: no watermark movement for this
#: long means the leader is wedged beyond the wire plane's own retries
_STALL_SECS = 60.0


class TieredStore:
    """The tier API: ``read`` consults RAM → disk → (via ``fetch``)
    peers/origin, with single-flight admission on the miss edge.

    ``fetch(key, offset)`` is the caller's upstream: an iterator of byte
    chunks starting at ``offset`` (a takeover leader passes the resumed
    partial's size — upstreams honoring Range resume pay only the tail).
    """

    def __init__(self, store: Store, hot_budget: TierBudget | None = None,
                 name: str = "tier"):
        self.store = store
        self.name = name
        self.hot = HotTier(store, hot_budget)
        self.flights = SingleFlight()
        # degraded read-through mode (storage-fault plane): entered when
        # an emergency-evicted disk still refuses a landing write; misses
        # then stream upstream → caller without landing bytes until a
        # rate-limited re-probe sees the disk accept writes again
        self._degraded_lock = threading.Lock()
        self._degraded = False
        self._degraded_since = 0.0
        self._degraded_entries = 0
        self._last_probe = 0.0
        with _tier_registry_lock:
            _tier_registry.add(self)

    # -- degraded read-through mode --------------------------------------
    def degraded(self) -> bool:
        with self._degraded_lock:
            return self._degraded

    def _enter_degraded(self, err: BaseException) -> None:
        with self._degraded_lock:
            if self._degraded:
                return
            self._degraded = True
            self._degraded_since = time.time()
            self._degraded_entries += 1
        HUB.set_gauge("store_degraded", 1)
        HUB.inc("store_degraded_entries_total")
        log.warning("store write failed (%s) after emergency eviction: "
                    "entering degraded read-through mode — misses stream "
                    "uncached until the disk accepts writes again", err)

    def _maybe_exit_degraded(self) -> None:
        """Rate-limited re-probe (``DEMODEL_STORE_REPROBE_SECS``): one
        small real write through the store's write path; success exits
        degraded mode automatically."""
        with self._degraded_lock:
            if not self._degraded:
                return
            now = time.monotonic()
            if now - self._last_probe < store_reprobe_secs():
                return
            self._last_probe = now
        if self.store.probe_writable():
            with self._degraded_lock:
                self._degraded = False
                self._degraded_since = 0.0
            HUB.set_gauge("store_degraded", 0)
            log.info("store writable again: leaving degraded read-through "
                     "mode")

    # -- the read path ---------------------------------------------------
    def read(self, key: str,
             fetch: Callable[[str, int], Iterable[bytes]] | None = None,
             meta: dict | None = None,
             expected_digest: str | None = None,
             timeout: float | None = None) -> bytes:
        """Full object bytes for ``key`` from the nearest tier; a miss
        with no ``fetch`` raises ``KeyError``."""
        hot = self.hot.read(key)
        if hot is not None:
            return hot
        _tick("store_tier_misses_total", "ram")
        self._maybe_exit_degraded()
        if self.store.has(key):
            _tick("store_tier_hits_total", "disk")
            try:
                body = self.store.get(key)
            except OSError as e:
                if e.errno != errno.EIO:
                    raise
                # EIO on a committed object: the media under it is bad —
                # quarantine (hot tier + fd cache + index invalidated by
                # the store) and re-enter the miss path below
                self.hot.invalidate(key)
                self.store.quarantine(key)
                log.warning("EIO reading committed object %s: quarantined, "
                            "re-entering miss path", key)
            else:
                self.hot.promote(key)
                return body
        _tick("store_tier_misses_total", "disk")
        if fetch is None:
            raise KeyError(key)
        flight, leader = self.flights.lease(key)
        if leader:
            return self._lead(flight, fetch, meta, expected_digest)
        return self._follow(flight, fetch, meta, expected_digest, timeout)

    def _lead(self, flight: _Flight,
              fetch: Callable[[str, int], Iterable[bytes]],
              meta: dict | None, expected_digest: str | None) -> bytes:
        key = flight.key
        _tick("singleflight_leaders_total")
        if self.degraded():
            # degraded read-through: no landing write may even be tried —
            # stream upstream → cohort through the in-memory relay
            return self._lead_relay(flight, fetch, expected_digest,
                                    stream=None, prefix=b"")
        with trace.span("tier.lead", key=key):
            try:
                w = self.store.begin(key, resume=True)
            except OSError as e:
                # a non-cohort writer (direct store user) owns the
                # partial; surface as a failed flight, key unpoisoned
                self.flights.finish(key, flight)
                flight.finish(ok=False, error=e)
                raise
            relaying = False
            try:
                with flight.cv:
                    flight.buf = None  # takeover after relay: disk again
                flight.set_watermark(w.offset)
                stream = iter(fetch(key, w.offset))
                unsynced = 0
                for chunk in stream:
                    try:
                        w.append(chunk)
                    except OSError as e:
                        if e.errno != errno.ENOSPC:
                            raise
                        # full disk mid-landing: emergency eviction + ONE
                        # retry; a still-full disk flips the node into
                        # degraded read-through and the cohort keeps
                        # streaming off an in-memory relay seeded with
                        # the durably landed prefix
                        self.enforce()
                        try:
                            w.append(chunk)
                        except OSError as e2:
                            if e2.errno != errno.ENOSPC:
                                raise
                            self._enter_degraded(e2)
                            prefix = _landed_prefix(self.store, key,
                                                    w.offset)
                            w.checkpoint()
                            w.abort(keep_partial=True)
                            relaying = True
                            return self._lead_relay(
                                flight, fetch, expected_digest,
                                stream=stream, prefix=prefix + chunk)
                    flight.advance(len(chunk))
                    unsynced += len(chunk)
                    if unsynced >= _CHECKPOINT_BYTES:
                        # durable resume point: a kill -9 past here costs
                        # the next incarnation at most _CHECKPOINT_BYTES
                        # of refetch (Store.recover truncates to this)
                        w.checkpoint()
                        unsynced = 0
                digest = w.digest()
                if expected_digest and digest != expected_digest:
                    # drop the partial: the BYTES are wrong, resuming
                    # them would re-fail every successor
                    w.abort(keep_partial=False)
                    err = DigestMismatch(
                        f"{key}: got {digest[:12]}, "
                        f"want {expected_digest[:12]}")
                    self.flights.finish(key, flight)
                    flight.finish(ok=False, error=err)
                    raise err
                try:
                    w.commit(meta or {})
                except OSError as e:
                    if e.errno != errno.ENOSPC:
                        raise
                    # commit-time ENOSPC (meta sidecar): the body is fully
                    # durable in the partial — release the writer guard
                    # keeping the partial (no-op when the native commit
                    # already released it), evict, re-open (resume
                    # rehashes the partial) and publish again
                    w.abort(keep_partial=True)
                    self.enforce()
                    self.store.begin(key, resume=True).commit(meta or {})
            except DigestMismatch:
                raise
            except BaseException as e:
                if not relaying:
                    w.abort(keep_partial=True)
                    if not flight.resign(e):
                        self.flights.finish(key, flight)
                raise
            self.flights.finish(key, flight)
            flight.finish(ok=True)
            body = self.store.get(key)
            self.hot.promote(key)
            return body

    def _lead_relay(self, flight: _Flight,
                    fetch: Callable[[str, int], Iterable[bytes]],
                    expected_digest: str | None,
                    stream: "Iterable[bytes] | None",
                    prefix: bytes) -> bytes:
        """Degraded read-through leader: upstream → cohort through the
        flight's in-memory relay, landing nothing on disk. ``stream``
        continues a partially-consumed fetch iterator (the mid-stream
        ENOSPC switch); ``prefix`` is whatever had already landed."""
        key = flight.key
        with trace.span("tier.lead_degraded", key=key):
            try:
                flight.start_relay(prefix)
                if stream is None:
                    stream = iter(fetch(key, len(prefix)))
                for chunk in stream:
                    flight.relay(chunk)
                buf = bytes(flight.buf or b"")
                if expected_digest:
                    digest = hashlib.sha256(buf).hexdigest()
                    if digest != expected_digest:
                        err = DigestMismatch(
                            f"{key}: got {digest[:12]}, "
                            f"want {expected_digest[:12]} (degraded)")
                        self.flights.finish(key, flight)
                        flight.finish(ok=False, error=err)
                        raise err
            except DigestMismatch:
                raise
            except BaseException as e:
                if not flight.resign(e):
                    self.flights.finish(key, flight)
                raise
            self.flights.finish(key, flight)
            flight.finish(ok=True)
            return buf

    def _follow(self, flight: _Flight,
                fetch: Callable[[str, int], Iterable[bytes]],
                meta: dict | None, expected_digest: str | None,
                timeout: float | None) -> bytes:
        """Progress-watermark reads off the landing stream: pread the
        growing ``partial/<key>`` as the leader's watermark advances —
        the fd stays valid across the commit rename, so the tail is
        readable even after publication."""
        key = flight.key
        _tick("singleflight_waiters_total")
        stall = _STALL_SECS if timeout is None else timeout
        part_path = os.path.join(str(self.store.root), "partial", key)
        out = bytearray()
        fd = -1
        counted = True  # still in the flight's waiter count
        try:
            with trace.span("tier.follow", key=key):
                while True:
                    with flight.cv:
                        deadline = time.monotonic() + stall
                        while (flight.watermark <= len(out)
                               and not flight.done
                               and not flight.leader_needed):
                            if not _wait(flight.cv, deadline):
                                raise TimeoutError(
                                    f"no landing-stream progress on {key} "
                                    f"for {stall:.0f}s")
                        if flight.leader_needed:
                            flight.leader_needed = False
                            flight.handoffs += 1
                            flight.waiters -= 1
                            counted = False
                            takeover = True
                        else:
                            takeover = False
                            wm, done, ok = (flight.watermark, flight.done,
                                            flight.ok)
                    if takeover:
                        _tick("singleflight_handoffs_total")
                        log.info("single-flight takeover: %s at %d bytes",
                                 key, flight.watermark)
                        return self._lead(flight, fetch, meta,
                                          expected_digest)
                    if wm > len(out):
                        # degraded read-through: the leader relays through
                        # the flight buffer instead of the partial
                        with flight.cv:
                            relay = flight.buf
                            if relay is not None:
                                out += bytes(
                                    relay[len(out):min(wm, len(relay))])
                        while len(out) < wm:
                            if fd < 0:
                                fd = os.open(part_path, os.O_RDONLY)
                            chunk = os.pread(fd, wm - len(out), len(out))
                            if not chunk:
                                break  # torn rename edge: retry via store
                            out += chunk
                    if done:
                        if not ok:
                            raise flight.error or OSError(
                                f"single-flight fetch of {key} failed")
                        with flight.cv:
                            relay = flight.buf
                        if relay is not None:
                            if len(out) < len(relay):
                                out += bytes(relay[len(out):])
                            return bytes(out)
                        if len(out) < flight.watermark:
                            # never opened the partial (commit landed
                            # between waits) — read the published object
                            return self.store.get(key)
                        self.hot.promote(key)
                        return bytes(out)
        finally:
            if counted:
                with flight.cv:
                    flight.waiters -= 1
            if fd >= 0:
                os.close(fd)

    # -- eviction --------------------------------------------------------
    def enforce(self) -> None:
        """Budget-driven eviction across both tiers (replaces the old
        post-pull ``_maybe_gc`` sweep): trim the RAM tier to the shared
        budget, then the disk tier to ``DEMODEL_CACHE_MAX_GB`` via
        :meth:`Store.gc` — pins shield exactly as before, and the
        ``store_evictions_total`` counters keep their semantics."""
        self.hot.trim()
        enforce_disk_budget(self.store)

    def describe(self) -> dict[str, Any]:
        doc = {"name": self.name, "tiers": [self.hot.describe()],
               "singleflight": {
                   "in_flight": self.flights.in_flight(),
                   "flights": self.flights.describe()}}
        max_gb = cache_max_gb()
        doc["tiers"].append({"tier": "disk",
                             "max_bytes": max_gb << 30 if max_gb else 0})
        with self._degraded_lock:
            storage = {"degraded": self._degraded,
                       "degraded_since": self._degraded_since,
                       "degraded_entries": self._degraded_entries}
        storage.update(self.store.storage_stats())
        doc["storage"] = storage
        return doc

    def close(self) -> None:
        self.hot.close()


def _landed_prefix(store: Store, key: str, size: int) -> bytes:
    """The durably landed prefix of ``partial/<key>`` — the relay seed for
    a mid-stream degraded switch (waiters already streamed these bytes, so
    a short read here must fail the flight, not desync it)."""
    if size <= 0:
        return b""
    path = os.path.join(str(store.root), "partial", key)
    with open(path, "rb") as f:
        data = f.read(size)
    if len(data) != size:
        raise OSError(errno.EIO, f"partial prefix short for {key}")
    return data


def enforce_disk_budget(store: Store) -> None:
    """Disk-tier budget: ``DEMODEL_CACHE_MAX_GB`` (0 = unbounded) through
    :meth:`Store.gc` — active writers/partials untouched, pinned keys
    shielded (native gc), eviction counters unchanged."""
    max_gb = cache_max_gb()
    if max_gb > 0:
        total, freed, evicted = store.gc(max_gb << 30)
        if evicted:
            log.info("disk tier: evicted %d objects (%.1f MB); %.1f MB in "
                     "use", evicted, freed / 1e6, total / 1e6)


#: weak registry of live TieredStores — statusz iterates it (sys.modules
#: peek; a collected tier falls out on its own)
_tier_registry_lock = threading.Lock()
_tier_registry: "weakref.WeakSet[TieredStore]" = weakref.WeakSet()

#: process-shared tier per store root (the restore server and the pull
#: plane must hit ONE hot tier + ONE flight registry per store)
_shared_lock = threading.Lock()
_shared: dict[str, "weakref.ReferenceType[TieredStore]"] = {}


def shared(store: Store) -> TieredStore:
    root = str(store.root)
    with _shared_lock:
        ref = _shared.get(root)
        tier = ref() if ref is not None else None
        if tier is None:
            tier = TieredStore(store, name=f"tier:{os.path.basename(root)}")
            _shared[root] = weakref.ref(tier)
        return tier


def shed_ram() -> int:
    """Trim every live hot tier to the shared RAM budget. The swarm
    plane calls this after charging chunk-board bytes, so a landing
    chunk pushes mmap'd hot objects out instead of overshooting host
    RAM (swarm-aware eviction). Returns bytes evicted."""
    with _tier_registry_lock:
        tiers = list(_tier_registry)
    return sum(t.hot.trim() for t in tiers)


def tiers_snapshot() -> list[dict[str, Any]]:
    """Live tier state for ``/debug/statusz`` (read-only): per-tier
    occupancy/budget plus in-flight single-flight leaders."""
    with _tier_registry_lock:
        tiers = list(_tier_registry)
    out = [t.describe() for t in sorted(tiers, key=lambda t: t.name)]
    budget = _ram_budget
    if budget is not None and not out:
        # chunk boards can charge the RAM budget before any TieredStore
        # exists — the budget is still worth reporting
        out.append({"name": "ram-budget", "tiers": [budget.describe()],
                    "singleflight": {"in_flight": 0, "flights": []}})
    return out
