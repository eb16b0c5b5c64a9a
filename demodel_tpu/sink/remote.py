"""Sharded pod delivery: place a checkpoint over the peer HTTP plane,
reading ONLY the byte ranges this host's devices need.

This is the composed "peer shard cache across pod hosts over ICI/DCN"
flow (`/root/reference/README.md:5-10`; SURVEY.md §2.3): where the whole-
file pull path copies every weight byte to every host, this path drives
:func:`~demodel_tpu.sink.hbm.deliver_safetensors` against a reader whose
``pread``/``pread_into`` are HTTP **Range** requests on a warm peer's
``/peer/object/{key}`` endpoint:

- a tensor sharded on axis 0 → each host fetches only its devices'
  contiguous row windows over DCN (native multi-stream window fan-out,
  socket reads landing directly in the ``device_put`` buffer);
- a replicated tensor with ``ici_complete`` → each host fetches 1/N of
  the rows, one XLA all-gather over ICI completes the replicas — every
  byte crosses the slow (DCN) path exactly once for the whole pod;
- delivery walks the model manifest in manifest order on every host, so
  the multi-controller collectives pair deterministically (the ordering
  problem that forces the streaming sink to disable ``ici_complete``,
  `sink/streaming.py`, does not exist here by construction).

The model manifest itself is discovered on the peer (the pull path
publishes a ``demodel://models/{source}/{model}`` record, so a cold pod
host needs NO registry round-trip at all — the warm peer is the source
of truth, matching the reference's "serve your friends" story).
"""

from __future__ import annotations

import ctypes
import json
import os
import threading
import time

import numpy as np
import requests

from demodel_tpu.delivery import manifest_key
from demodel_tpu.parallel import placement as swarm_placement
from demodel_tpu.parallel.placement import (
    ChunkBoard,
    HashRing,
    bitmap_indices,
    bounded_assign,
    chunk_count,
    chunk_span,
    default_chunk_bytes,
)
from demodel_tpu.sink.hbm import Placement, is_weight_file, merge_placement
from demodel_tpu.sink.plan import ShardingPlan
from demodel_tpu.utils import metrics, trace
from demodel_tpu.utils.env import env_int
from demodel_tpu.utils.faults import (
    PeerHealth,
    RangeIgnored,
    RetryPolicy,
    TruncatedBody,
    WireError,
    count_retry,
    peer_cannot_serve,
    request_with_retry,
    retryable,
)
from demodel_tpu.utils.logging import get_logger

log = get_logger("sink.remote")

#: window reads at/under this ride one pooled requests connection; larger
#: windows fan out over native range streams (connection setup ~free vs
#: the transfer beyond this size)
_NATIVE_MIN_BYTES = 4 << 20


class WindowAbort(IOError):
    """A window transfer died mid-body. ``got`` bytes already landed in
    the caller's buffer (real network bytes, never re-fetched); ``cause``
    carries the transport error for retry classification."""

    def __init__(self, got: int, cause: BaseException):
        super().__init__(str(cause))
        self.got = got
        self.cause = cause


class PeerBlobReader:
    """Store-shaped reads (``size``/``pread``/``pread_into``) served by
    HTTP Range requests against one object on one peer.

    Duck-types the subset of :class:`~demodel_tpu.store.Store` that
    :func:`~demodel_tpu.sink.hbm.deliver_safetensors` touches, so the
    whole sharded-placement machinery (per-device windows, ici staging,
    GGUF dispatch) runs unchanged over the wire. Thread-safe; counts
    ``bytes_fetched`` for the pod-delivery proof ("each host reads < the
    whole checkpoint").

    Window-level recovery: a failed Range read resumes at the exact
    received offset — first on the next healthy ``failover`` peer holding
    the same key (breaker-gated via the shared :class:`PeerHealth`), with
    backoff when no alternative exists — so one RST at shard 14/15 costs
    one re-issued window remainder, not the pipeline.
    """

    def __init__(self, peer: str, remote_key: str, size: int,
                 session: requests.Session | None = None,
                 streams: int | None = None, timeout: float | None = None,
                 path: str | None = None,
                 failover: list[str] | None = None,
                 health: PeerHealth | None = None,
                 policy: RetryPolicy | None = None):
        self.remote_key = remote_key
        #: served resource path — /peer/object/{key} by default; the
        #: restore client points this at /restore/{model}/tensor/{name}
        #: (same Range semantics on the native plane)
        self.path = path or f"/peer/object/{remote_key}"
        self._size = int(size)
        self.timeout = timeout if timeout is not None else float(
            env_int("DEMODEL_PEER_TIMEOUT", 120, minimum=1))
        from demodel_tpu.parallel.peer import _peer_streams

        self.streams = streams if streams is not None else _peer_streams()
        self._tls = threading.local()
        self._session = session
        self.bytes_fetched = 0
        self._count_lock = threading.Lock()
        first = peer.rstrip("/")
        self._peers = [first] + [q for q in
                                 (p.rstrip("/") for p in (failover or []))
                                 if q != first]
        self._health = health if health is not None else PeerHealth.shared()
        self._policy = policy if policy is not None else RetryPolicy()
        #: guards peer/_native_host/_native_port against torn reads —
        #: concurrent pread_into calls share this reader and one thread's
        #: failover must not hand another thread host A with port B
        self._peer_lock = threading.Lock()
        self._set_peer(first)

    def _set_peer(self, peer: str) -> None:
        import re as _re

        m = _re.match(r"^http://(\[[0-9a-fA-F:]+\]|[^:/]+)(?::(\d+))?$",
                      peer)
        with self._peer_lock:
            self.peer = peer
            # https/odd peers: every read takes the requests path
            self._native_host = m.group(1).strip("[]") if m else None
            self._native_port = int(m.group(2) or 80) if m else 0

    def _snapshot(self) -> tuple[str, str | None, int]:
        """A consistent (peer, native_host, native_port) for one attempt."""
        with self._peer_lock:
            return self.peer, self._native_host, self._native_port

    def _fail_over(self, from_peer: str,
                   exclude: set | frozenset = frozenset()) -> bool:
        """Rotate to the next breaker-admitted peer holding this key
        (skipping ``exclude`` — peers proven unable to serve this
        object). Returns True when the caller's source changed (it skips
        the backoff sleep — a healthy alternative needs no cooldown). If
        a concurrent window already rotated away from ``from_peer``,
        that counts: the caller retries against the new source."""
        with self._peer_lock:
            current = self.peer
        if current != from_peer and current not in exclude:
            return True
        if len(self._peers) > 1:
            i = self._peers.index(current)
            for step in range(1, len(self._peers)):
                cand = self._peers[(i + step) % len(self._peers)]
                if cand != from_peer and cand not in exclude \
                        and self._health.allow(cand):
                    self._set_peer(cand)
                    return True
        return False

    def _add_fetched(self, n: int) -> None:
        if n:
            with self._count_lock:
                self.bytes_fetched += n
            # the delivery-rate counter the adaptive tuner (and anyone
            # watching /debug/telemetry) reads as a sliding-window rate
            metrics.HUB.inc("pull_bytes_total", n)

    # -- Store duck-type ------------------------------------------------
    def size(self, key: str) -> int:  # noqa: ARG002 — single-object reader
        return self._size

    def pread(self, key: str, length: int, offset: int) -> bytes:
        out = np.empty(length, dtype=np.uint8)
        got = self.pread_into(key, out, offset)
        return out[:got].tobytes()

    def pread_into(self, key: str, out, offset: int = 0) -> int:  # noqa: ARG002
        view = memoryview(out).cast("B")
        length = view.nbytes
        if length == 0:
            return 0
        if offset < 0 or offset + length > self._size:
            raise IOError(f"window [{offset}, {offset + length}) outside "
                          f"object of {self._size} bytes")
        if not trace.active():
            # span() args are evaluated eagerly — guard so the fully
            # disabled (DEMODEL_OBS=0) hot path pays neither the attrs
            # dict nor the _snapshot() lock acquire per window
            return self._pread_into_traced(view, length, offset,
                                           trace.NOOP)
        with trace.span("window-read", key=self.remote_key, offset=offset,
                        length=length, peer=self._snapshot()[0]) as sp:
            return self._pread_into_traced(view, length, offset, sp)

    def _pread_into_traced(self, view, length: int, offset: int,
                           sp) -> int:
        got = 0
        attempt = 0
        start = self._policy.clock()
        cannot_serve: set = set()  # peers that 404'd/range-refused THIS key
        while True:
            peer, native_host, native_port = self._snapshot()
            try:
                while got < length:
                    remaining = length - got
                    sub = view[got:]
                    if native_host and remaining >= _NATIVE_MIN_BYTES:
                        n = self._window_native(sub, offset + got, remaining,
                                                peer, native_host,
                                                native_port)
                    else:
                        n = self._window_requests(sub, offset + got,
                                                  remaining, peer)
                    self._add_fetched(n)
                    got += n
            except WindowAbort as e:
                # e.got bytes are already in the buffer AND already moved
                # over the wire — count them, keep them, never re-fetch
                self._add_fetched(e.got)
                got += e.got
                if retryable(e.cause):
                    # wire-shaped failure: health event + backoff budget
                    self._health.record_failure(peer)
                    attempt += 1
                    delay = self._policy.should_retry(attempt, start,
                                                      e.cause)
                    if delay is None:
                        raise IOError(
                            f"window [{offset}, +{length}) of "
                            f"{self.remote_key} failed at +{got} after "
                            f"{attempt} attempt(s): {e.cause}") from e.cause
                    count_retry(peer, delay)
                    switched = self._fail_over(peer, exclude=cannot_serve)
                    sp.event("retry", attempt=attempt, peer=peer,
                             resume_at=got,
                             error=f"{type(e.cause).__name__}: {e.cause}")
                    if switched:
                        sp.event("failover", from_peer=peer,
                                 to_peer=self._snapshot()[0],
                                 resume_at=got)
                    log.warning(
                        "window [%d, +%d) of %s died at +%d on %s (%s); "
                        "resuming at the exact offset via %s "
                        "(attempt %d/%d)",
                        offset, length, self.remote_key, got, peer,
                        e.cause, self._snapshot()[0], attempt + 1,
                        self._policy.max_attempts)
                    if not switched:
                        self._policy.sleep(delay)
                elif peer_cannot_serve(e.cause):
                    # content-shaped refusal (missing blob, range-blind
                    # peer): NOT a health event and a same-peer retry is
                    # a deterministic re-failure — rotate once per such
                    # peer, give up when no untried peer remains. The
                    # rotation deliberately includes partially-warm peers
                    cannot_serve.add(peer)
                    if (self._policy.deadline_left(start) <= 0
                            or not self._fail_over(peer,
                                                   exclude=cannot_serve)):
                        raise IOError(
                            f"window [{offset}, +{length}) of "
                            f"{self.remote_key}: no peer in the rotation "
                            f"can serve it ({e.cause})") from e.cause
                    sp.event("failover", from_peer=peer,
                             to_peer=self._snapshot()[0],
                             reason="cannot-serve", resume_at=got)
                    log.warning(
                        "peer %s cannot serve %s (%s); failing the window "
                        "over to %s", peer, self.remote_key, e.cause,
                        self._snapshot()[0])
                else:
                    raise IOError(
                        f"window [{offset}, +{length}) of "
                        f"{self.remote_key} failed at +{got}: "
                        f"{e.cause}") from e.cause
            else:
                self._health.record_success(peer)
                return length

    # -- transports -----------------------------------------------------
    def _window_native(self, view: memoryview, offset: int, length: int,
                       peer: str, native_host: str,
                       native_port: int) -> int:
        from demodel_tpu import native

        arr = np.frombuffer(view, dtype=np.uint8)
        errbuf = ctypes.create_string_buffer(512)
        n = native.lib().dm_peer_fetch_window(
            native_host.encode(), native_port,
            self.path.encode(),
            offset, length, self._size, self.streams,
            arr.ctypes.data_as(ctypes.c_void_p), errbuf, 512)
        if n != length:
            log.warning("native window fetch [%d,+%d) of %s failed (%s); "
                        "using requests", offset, length, self.remote_key,
                        errbuf.value.decode(errors="replace"))
            return self._window_requests(view, offset, length, peer)
        return int(n)

    def _window_requests(self, view: memoryview, offset: int,
                         length: int, peer: str) -> int:
        """One Range attempt against ``peer`` (an explicit snapshot — a
        concurrent failover must not swap the target mid-attempt). Bytes
        land in ``view`` as they arrive; any failure raises
        :class:`WindowAbort` carrying how many did, so the recovery loop
        in :meth:`pread_into` resumes — not restarts — the window."""
        s = getattr(self._tls, "session", None) or self._session
        if s is None:
            s = self._tls.session = requests.Session()
        got = 0
        try:
            # the ambient window-read span's traceparent rides the raw
            # streaming GET too (this path bypasses request_with_retry —
            # resume semantics live in pread_into)
            headers = trace.inject_headers(
                {"Range": f"bytes={offset}-{offset + length - 1}"})
            r = s.get(f"{peer}{self.path}", headers=headers,
                      stream=True, timeout=self.timeout)
            try:
                r.raise_for_status()
                if r.status_code != 206 and not (
                        r.status_code == 200 and offset == 0
                        and length == self._size):
                    raise RangeIgnored(
                        f"peer ignored Range (status {r.status_code}) "
                        f"for {self.remote_key}")
                for chunk in r.iter_content(1 << 20):
                    if not chunk:
                        continue
                    take = min(len(chunk), length - got)
                    view[got:got + take] = chunk[:take]
                    got += take
                    if got >= length:
                        break
            finally:
                r.close()
        except (requests.RequestException, WireError, OSError) as e:
            raise WindowAbort(got, e) from e
        if got != length:
            raise WindowAbort(got, TruncatedBody(
                f"short peer window read: {got} != {length} "
                f"for {self.remote_key}"))
        return got


def fetch_manifest(peers: list[str], model: str, source: str = "hf",
                   timeout: float = 30.0,
                   health: PeerHealth | None = None,
                   policy: RetryPolicy | None = None) -> tuple[str, dict]:
    """Locate and fetch the model-manifest record on a warm peer. Returns
    ``(peer_base_url, manifest_dict)``. The record is what the pull path
    persisted (`delivery._persist_manifest`), so ``files`` carries names,
    store keys, sizes, and digests — everything needed to place the model
    without any upstream registry round-trip.

    Breaker-aware: peers whose circuit breaker is open are skipped until
    their half-open probe succeeds (a dead peer must not cost discovery a
    full connect timeout); each attempted peer rides the retry policy."""
    mkey = manifest_key(source, model)
    health = health if health is not None else PeerHealth.shared()
    policy = policy if policy is not None else RetryPolicy()
    s = requests.Session()
    with trace.span("manifest-discovery", model=model, source=source,
                    peers=len(peers)):
        return _fetch_manifest(peers, mkey, model, source, timeout,
                               health, policy, s)


def _fetch_manifest(peers, mkey, model, source, timeout, health, policy,
                    s) -> tuple[str, dict]:
    last_err: Exception | None = None
    candidates = [p.rstrip("/") for p in peers]
    # read-only admission filter (burns no probe slots); the claiming
    # allow() happens right before each dial below
    admitted = [p for p in candidates if health.admissible(p)]
    if len(admitted) < len(candidates):
        log.info("manifest discovery skipping %d breaker-open peer(s)",
                 len(candidates) - len(admitted))
    last_resort = not admitted
    if last_resort:
        # every breaker refuses: a last-resort sweep beats turning a
        # brown-out into an outage
        admitted = candidates
    for peer in admitted:
        if not last_resort and not health.allow(peer):
            continue  # raced shut, or another caller owns the probe
        try:
            r = request_with_retry(
                s, "GET", f"{peer}/peer/object/{mkey}",
                policy=policy, health=health, peer=peer,
                ok_statuses=(404,), timeout=timeout,
                what=f"manifest {source}/{model} from {peer}")
            if r.status_code == 404:
                continue
            return peer, r.json()
        except (requests.RequestException, OSError, ValueError) as e:
            last_err = e
            log.warning("peer %s manifest for %s failed: %s", peer, model, e)
    raise IOError(f"no peer holds a manifest for {source}/{model}"
                  + (f" (last error: {last_err})" if last_err else ""))


def _peer_alive(peer: str, timeout: float = 3.0) -> bool:
    """Short-deadline liveness probe (``/healthz`` on the native proxy).
    Only gates which peers join the striping rotation — the manifest
    peer is already proven by the manifest fetch itself. Single attempt
    (a retry would defeat the short deadline); the outcome feeds the
    shared breaker registry."""
    try:
        request_with_retry(
            requests, "GET", f"{peer}/healthz",
            policy=RetryPolicy(max_attempts=1, deadline=timeout),
            health=PeerHealth.shared(), peer=peer.rstrip("/"),
            timeout=timeout, what=f"liveness {peer}")
        return True
    except (requests.RequestException, OSError):
        return False


def _alive_peers(peers: list, timeout: float = 3.0) -> list:
    """Probe every candidate peer CONCURRENTLY under one shared deadline.

    The striping rotation used to probe candidates one at a time: K
    stale peer URLs on the pull critical path cost K × timeout before
    the first byte moved. Here each probe rides ``asyncio.to_thread``
    and the whole rotation build is bounded by ~timeout: stragglers are
    cancelled at the deadline (on every exit path — the
    ``orphaned-async-task`` discipline) and treated as dead. Their probe
    threads may run on to their socket timeout; ``asyncio.run`` joins
    them at loop shutdown, so nothing leaks — worst case is ~2×timeout
    total, independent of peer count.
    """
    if not peers:
        return []
    import asyncio

    try:
        asyncio.get_running_loop()
    except RuntimeError:
        pass  # no loop in this thread — the asyncio path below owns one
    else:
        # asyncio.run would raise "cannot be called from a running event
        # loop": a serving node's async handler pulling a model lands
        # exactly here — probe on a thread pool instead
        return _alive_peers_threaded(peers, timeout)

    async def _probe_all() -> list:
        tasks = {
            p: asyncio.create_task(asyncio.to_thread(_peer_alive, p, timeout))
            for p in peers
        }
        done: set = set()
        try:
            done, _pending = await asyncio.wait(
                set(tasks.values()), timeout=timeout + 0.5)
        finally:
            for t in tasks.values():
                t.cancel()  # no-op on done tasks; orphans none on errors
        return [p for p, t in tasks.items()
                if t in done and not t.cancelled()
                and t.exception() is None and t.result()]

    return asyncio.run(_probe_all())


def _alive_peers_threaded(peers: list, timeout: float = 3.0) -> list:
    """`_alive_peers` for callers whose thread already runs an event loop:
    same shape — concurrent probes, one shared deadline — on a thread
    pool. Stragglers past the deadline are treated dead; their probe
    threads run on to the socket timeout and exit on their own
    (``shutdown(wait=False)`` — joining them here would hold the caller
    for the full socket timeout, the exact stall this function exists to
    avoid; worst case is ~2×timeout of background lingering, same bound
    as the asyncio path's loop-shutdown join)."""
    from concurrent.futures import ThreadPoolExecutor, wait

    ex = ThreadPoolExecutor(max_workers=min(32, len(peers)),
                            thread_name_prefix="peer-probe")
    try:
        futs = {p: ex.submit(_peer_alive, p, timeout) for p in peers}
        done, _pending = wait(set(futs.values()), timeout=timeout + 0.5)
        return [p for p, f in futs.items()
                if f in done and not f.cancelled()
                and f.exception() is None and f.result()]
    finally:
        ex.shutdown(wait=False, cancel_futures=True)


def _responsive_peers(peers: list, timeout: float = 3.0) -> list:
    """The striping-rotation membership check, gossip-first: peers whose
    background index refresh (:class:`~demodel_tpu.parallel.peer
    .PeerGossip`) answered recently join with ZERO wire traffic on the
    pull critical path, fresh-failed peers drop out, and only peers the
    gossip has never heard from fall back to the one-shot concurrent
    probe round (the cold-start shape). Every pull also enrolls its
    peers for background refresh, so pull #2 onward probes nothing."""
    if not peers:
        return []
    from demodel_tpu.parallel.peer import PeerGossip

    gossip = PeerGossip.shared()
    gossip.track(peers)
    alive, dead, unknown = gossip.split(peers)
    if dead:
        log.info("striping rotation drops %d gossip-dead peer(s)",
                 len(dead))
    return alive + (_alive_peers(unknown, timeout) if unknown else [])


def _reader_and_index(f: dict, peer_order: list[str], streams):
    """Open ``f`` on the first peer that can serve its safetensors index
    (header reads fail over peer-by-peer here; window reads during
    delivery recover inside the reader — resume-at-offset plus failover
    to the rest of the rotation)."""
    from demodel_tpu.formats import safetensors as st

    last_err: Exception | None = None
    for i, source_peer in enumerate(peer_order):
        reader = PeerBlobReader(
            source_peer, f["key"], int(f["size"]), streams=streams,
            failover=peer_order[i + 1:] + peer_order[:i])
        try:
            with trace.span("index-read", file=f["name"],
                            peer=source_peer):
                index = st.read_index_from(
                    lambda off, ln: reader.pread(f["key"], ln, off),
                    total_size=reader.size(f["key"]))
            return reader, index
        except (OSError, ValueError) as e:
            # ValueError: a corrupted/truncated safetensors header parses
            # as junk — same failover as a transport error, the next peer
            # holds a good copy
            last_err = e
            log.warning("index of %s from %s failed (%s); trying next "
                        "peer", f["name"], source_peer, e)
    raise IOError(f"no peer could serve {f['name']}") from last_err


# --------------------------------------------------------------- swarm fetch
#
# Pod-scale cold pull: N hosts pulling the same manifest partition every
# file's fixed chunk grid over a consistent-hash ring (disjoint origin
# chunk sets), fetch ONLY their owned chunks from origin, and cross-fill
# the rest from each other as possession advertisements land — aggregate
# origin traffic ≈ 1× the manifest, origin-bound wall-clock ≈ size/N.
# The per-chunk transport is the existing window machinery
# (PeerBlobReader.pread_into: resume-at-offset, breaker-gated failover),
# so WindowAbort semantics hold inside every chunk.


def _swarm_chunk_id(key: str, index: int) -> str:
    return f"{key}:{index}"


def _swarm_origin_read(reader: PeerBlobReader, key: str, offset: int,
                       length: int) -> bytes:
    """THE origin transport of the swarm plane: one owned (or re-owned)
    chunk off the origin/warm-peer rotation. Every origin byte a swarm
    pull moves goes through here — the ``swarm-owner-only-origin``
    analyzer rule keeps callers inside :class:`SwarmScheduler`, where the
    ownership decision lives, so no code path can quietly degrade the
    aggregate-origin-bytes ≈ 1× contract back into N× origin pulls."""
    buf = bytearray(length)
    with trace.span("chunk-origin", key=key, offset=offset, bytes=length):
        reader.pread_into(key, buf, offset)
    metrics.HUB.inc("swarm_origin_bytes_total", length)
    return bytes(buf)


class SwarmScheduler:
    """Chunk-level swarm fetch for one pull on one host.

    ``participants``: ``{host_id: base_url}`` of every host in the swarm
    (including this one — ``self_id`` selects which). All hosts build the
    same :class:`HashRing` over the sorted host ids, so chunk ownership
    needs no coordination traffic at all.

    Three background roles run between :meth:`start` and :meth:`close`:

    - the **origin pump** fetches this host's owned chunks from the
      origin rotation, rarest-first-ish (fewest known advertisers, hash
      tie-break — hosts' request orders decorrelate, so the swarm's
      earliest cross-fills spread over the whole grid);
    - the **gossip poller** refreshes every sibling's possession bitmap
      (``/swarm/{pull}/{host}/chunks``) and declares siblings dead after
      consecutive poll failures;
    - **fill workers** pull advertised non-owned chunks from whichever
      sibling has them (``chunk-peer-fill``), landing them on the local
      :class:`ChunkBoard` — which the restore server re-serves, so a
      chunk crosses origin once and then propagates peer-to-peer.

    Death handling is succession, not re-pull: a dead owner's chunk is
    re-owned by the next live host on its ring arc; only that successor
    goes back to origin (counted in ``swarm_chunks_refetched_total``),
    everyone else cross-fills from the successor.
    """

    def __init__(self, pull_id: str, self_id: str,
                 participants: dict[str, str],
                 chunk_bytes: int | None = None,
                 health: PeerHealth | None = None,
                 policy: RetryPolicy | None = None):
        if self_id not in participants:
            raise ValueError(f"self_id {self_id!r} not in participants")
        self.pull_id = pull_id
        self.self_id = self_id
        self.participants = dict(participants)
        self.chunk_bytes = chunk_bytes or default_chunk_bytes()
        self.ring = HashRing(sorted(participants))
        self.board = ChunkBoard(pull_id, self_id)
        self._health = health if health is not None else PeerHealth.shared()
        self._policy = policy if policy is not None else RetryPolicy()
        #: per-owner wait before a chunk succeeds to the next ring host.
        #: Sized for a live-but-busy owner, not a dead one (death is
        #: detected in ~3 gossip ticks): on a big manifest the LAST
        #: chunk of an owner's rarest-first queue legitimately takes its
        #: whole owned share's origin time to appear, so a small value
        #: here re-fetches healthy hosts' chunks and erodes the 1×
        #: origin contract
        self._fill_timeout = swarm_placement.default_fill_timeout()
        self._gossip_s = env_int(
            "DEMODEL_SWARM_GOSSIP_MS", 500, minimum=10) / 1000.0
        self._fill_streams = env_int(
            "DEMODEL_SWARM_FILL_STREAMS", 4, minimum=1)
        #: concurrent origin CONNECTIONS per host (the pump + any
        #: ensure-inline re-own fetch share it): the disjoint-chunk-set
        #: contract bounds each host's origin LINK use, so the default
        #: is one stream — multi-stream parallelism belongs inside a
        #: window (DEMODEL_PEER_STREAMS), not across origin chunks
        self._origin_sem = threading.Semaphore(
            swarm_placement.default_origin_streams())
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        #: file key → (size, n_chunks, origin PeerBlobReader)
        self._files: dict[str, tuple[int, int, PeerBlobReader]] = {}
        self._primary: dict[tuple[str, int], str] = {}
        self._owned: list[tuple[str, int]] = []
        self._inflight: set[tuple[str, int]] = set()
        self._peer_have: dict[str, dict[str, set[int]]] = {}
        #: gossiped done-sets (have ∪ reaped) per sibling — the reap
        #: gate; _peer_have stays strictly what a sibling can SERVE
        self._peer_done: dict[str, dict[str, set[int]]] = {}
        self._peer_ver: dict[str, int] = {}
        self._poll_fails: dict[str, int] = {}
        self._dead: set[str] = set()
        self._peer_bytes: dict[str, int] = {}   # file key → peer-fill bytes
        self._spread: dict[tuple[str, int], int] = {}  # rarest tie-break
        self.chunks_refetched = 0
        #: offsets of in-flight read_into calls per file — the reaper
        #: never frees below an active read's start
        self._active_reads: dict[str, list[int]] = {}
        #: per-file local consumption watermark (highest byte offset a
        #: read_into has fully passed) — the reaper only frees chunks the
        #: local delivery is already beyond, so a long pull's board stops
        #: retaining the whole file set until close()
        self._consumed_upto: dict[str, int] = {}
        self._reap = swarm_placement.reap_enabled()
        self._reap_s = max(2 * self._gossip_s, 0.5)
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        self._tls = threading.local()
        swarm_placement.register_board(self.board)

    # -- planning --------------------------------------------------------
    def add_file(self, key: str, size: int,
                 origin_reader: PeerBlobReader) -> None:
        """Register one manifest file's chunk grid (call for every
        weight file BEFORE start — ownership is assigned over the WHOLE
        grid at once so the capacity bound balances across files)."""
        if self._threads:
            raise RuntimeError("add_file after start(): the ownership "
                               "assignment is already fixed")
        n = chunk_count(size, self.chunk_bytes)
        with self._lock:
            self._files[key] = (int(size), n, origin_reader)
            self._peer_bytes.setdefault(key, 0)
        self.board.add_file(key, n)

    def _plan(self) -> None:
        """The ownership decision for the whole grid: ring succession
        for agreement + death recovery, bounded loads for balance (the
        swarm's wall-clock is the LARGEST owned share's origin time)."""
        with self._lock:
            grid = [(k, i) for k, (_s, n, _r) in sorted(self._files.items())
                    for i in range(n)]
        with trace.span("swarm-schedule", chunks=len(grid),
                        files=len(self._files),
                        hosts=len(self.participants)) as sp:
            assigned = bounded_assign(
                self.ring, [_swarm_chunk_id(k, i) for k, i in grid])
            # demodel: allow(atomic-snapshot) — _plan runs from start()
            # BEFORE any pump thread exists and add_file refuses
            # post-start registration, so the grid cannot change between
            # the two holds (single-threaded by lifecycle contract)
            with self._lock:
                self._primary = {
                    (k, i): assigned[_swarm_chunk_id(k, i)]
                    for k, i in grid}
                self._owned = [c for c, owner in self._primary.items()
                               if owner == self.self_id]
                owned_n = len(self._owned)
            sp.set_attr("owned", owned_n)

    def start(self) -> "SwarmScheduler":
        if self._threads:
            return self
        self._plan()
        self._threads.append(threading.Thread(
            target=self._pump_origin, name="swarm-pump", daemon=True))
        if self._reap:
            self._threads.append(threading.Thread(
                target=self._pump_reap, name="swarm-reap", daemon=True))
        if len(self.participants) > 1:
            self._threads.append(threading.Thread(
                target=self._pump_gossip, name="swarm-gossip", daemon=True))
            for i in range(self._fill_streams):
                self._threads.append(threading.Thread(
                    target=self._pump_fill, name=f"swarm-fill-{i}",
                    daemon=True))
        for t in self._threads:
            t.start()
        return self

    def close(self) -> None:
        """Stop the pumps, free the board, unregister the serve surface.
        The caller decides WHEN: closing before every sibling has the
        bytes pushes the swarm's stragglers back to origin."""
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        for t in self._threads:
            t.join(timeout=30)
        self._threads.clear()
        swarm_placement.unregister_board(self.board)
        self.board.clear()

    # -- read surface ----------------------------------------------------
    def peer_bytes_for(self, key: str) -> int:
        with self._lock:
            return self._peer_bytes.get(key, 0)

    def read_into(self, key: str, view: memoryview, offset: int) -> int:
        """Copy ``[offset, offset+len(view))`` of ``key`` out of the
        board, blocking per covering chunk until the swarm lands it."""
        with self._lock:
            size, _n, _r = self._files[key]
        length = view.nbytes
        if offset < 0 or offset + length > size:
            raise IOError(f"swarm window [{offset}, {offset + length}) "
                          f"outside {key} of {size} bytes")
        # register as an in-flight read: the reaper's safe-to-free floor
        # is min(active read starts, completed high-water) — prefetch
        # workers complete out of order as the norm, and a reap under a
        # still-running lower-offset read would force an origin re-fetch
        with self._lock:
            self._active_reads.setdefault(key, []).append(offset)
        try:
            pos = 0
            while pos < length:
                idx = (offset + pos) // self.chunk_bytes
                c_off, c_len = chunk_span(size, self.chunk_bytes, idx)
                data = self.ensure(key, idx)
                lo = offset + pos - c_off
                take = min(c_len - lo, length - pos)
                view[pos:pos + take] = data[lo:lo + take]
                pos += take
        finally:
            with self._lock:
                self._active_reads[key].remove(offset)
        # completed-read high-water: delivery walks files in (mostly)
        # ascending offset order, so chunks wholly below it — and below
        # every still-active read — are done locally; a rare later
        # re-read of a reaped chunk degrades to one counted re-fetch,
        # never a wrong byte
        with self._lock:
            if offset + length > self._consumed_upto.get(key, 0):
                self._consumed_upto[key] = offset + length
        return length

    def fetch_all(self) -> None:
        """Block until EVERY chunk of every registered file is on the
        board — swarm participation for a host that isn't also delivering
        to HBM (bench hosts, warm standbys)."""
        with self._lock:
            grid = [(k, i) for k, (_s, n, _r) in sorted(self._files.items())
                    for i in range(n)]
        for key, idx in grid:
            self.ensure(key, idx)

    # -- chunk acquisition ----------------------------------------------
    def ensure(self, key: str, index: int) -> bytes:
        """The ownership decision: return chunk bytes, sourcing them per
        the assignment — owned → origin; non-owned → wait for the
        owner's advertisement and cross-fill; owner dead/stuck →
        succession along the raw ring order, where only the next live
        host re-sources from origin."""
        chunk_id = _swarm_chunk_id(key, index)
        with self._lock:
            primary = self._primary.get((key, index))
        if primary is None:
            raise RuntimeError("ensure() before start(): no ownership "
                               "assignment yet")
        owners = [primary] + [
            o for o in self.ring.owners(chunk_id, len(self.participants))
            if o != primary]
        waited_since: dict[str, float] = {}
        while not self._stop.is_set():
            data = self.board.get(key, index)
            if data is not None:
                return data
            if self.board.reaped(key, index):
                # a local re-read below the consumption watermark wants a
                # chunk the reaper freed: re-land it from origin OURSELVES.
                # The chunk already crossed the wire once, and the live
                # siblings have likely reaped it too (reaping requires
                # every one of them to have advertised it) — the
                # owner-wait path below would stall out the fill timeout
                # and falsely condemn a healthy owner that simply cannot
                # serve a chunk it also freed.
                self.board.unreap(key, index)
                metrics.HUB.inc("swarm_chunks_unreaped_total")
                self._fetch_origin(key, index, reowned=False)
                continue
            live = [o for o in owners if o not in self._snapshot_dead()]
            target = live[0] if live else self.self_id
            if target == self.self_id:
                self._fetch_origin(key, index,
                                   reowned=(owners[0] != self.self_id))
                continue
            # a sibling owns it: grab it the moment an advertiser shows
            # (ANY advertiser — cross-filled copies count), else wait
            adv = self._advertisers(key, index)
            if adv:
                if self._fetch_peer(key, index, adv):
                    continue
            now = time.monotonic()
            waited_since.setdefault(target, now)
            if now - waited_since[target] > self._fill_timeout:
                # the live owner never produced the chunk (wedged, not
                # dead-dialed): succession treats it as gone
                with self._lock:
                    self._dead.add(target)
                    self._cv.notify_all()
                log.warning(
                    "swarm owner %s never advertised chunk %s/%d within "
                    "%.0fs; treating it as dead (succession)", target,
                    key, index, self._fill_timeout)
                # its other orphans join our pump where we're successor
                self._take_over_orphans()
                continue
            with self._cv:
                self._cv.wait(timeout=min(0.2, self._gossip_s))
        raise IOError(f"swarm pull {self.pull_id} closed while waiting "
                      f"for chunk {key}/{index}")

    def _snapshot_dead(self) -> set[str]:
        with self._lock:
            return set(self._dead)

    def _advertisers(self, key: str, index: int) -> list[str]:
        with self._lock:
            return [h for h, files in self._peer_have.items()
                    if h not in self._dead and index in files.get(key, ())]

    def _claim(self, key: str, index: int) -> bool:
        with self._lock:
            if (key, index) in self._inflight \
                    or self.board.done(key, index):
                return False
            self._inflight.add((key, index))
            return True

    def _release(self, key: str, index: int) -> None:
        with self._cv:
            self._inflight.discard((key, index))
            self._cv.notify_all()

    def _fetch_origin(self, key: str, index: int,
                      reowned: bool = False) -> None:
        if not self._claim(key, index):
            # someone else is on it — wait for their outcome
            with self._cv:
                self._cv.wait(timeout=0.2)
            return
        try:
            with self._lock:
                size, _n, reader = self._files[key]
            off, ln = chunk_span(size, self.chunk_bytes, index)
            with self._origin_sem:
                data = _swarm_origin_read(reader, key, off, ln)
            if reowned:
                with self._lock:
                    self.chunks_refetched += 1
                metrics.HUB.inc("swarm_chunks_refetched_total")
                log.info("swarm re-owned chunk %s/%d from origin "
                         "(owner dead)", key, index)
            self.board.put(key, index, data)
        finally:
            self._release(key, index)

    def _session(self) -> requests.Session:
        s = getattr(self._tls, "session", None)
        if s is None:
            s = self._tls.session = requests.Session()
        return s

    def _fetch_peer(self, key: str, index: int,
                    advertisers: list[str]) -> bool:
        """One cross-fill attempt off the best advertiser (ring owner
        first). Returns True when the chunk landed (or someone else's
        fetch is in flight — the caller re-checks the board)."""
        if not self._claim(key, index):
            return True
        chunk_id = _swarm_chunk_id(key, index)
        order = [o for o in self.ring.owners(chunk_id,
                                             len(self.participants))
                 if o in advertisers] or advertisers
        try:
            with self._lock:
                size, _n, _r = self._files[key]
            _off, ln = chunk_span(size, self.chunk_bytes, index)
            for host in order:
                url = self.participants[host]
                try:
                    with trace.span("chunk-peer-fill", key=key,
                                    index=index, peer=host, bytes=ln):
                        r = request_with_retry(
                            self._session(), "GET",
                            f"{url}/swarm/{self.pull_id}/{host}"
                            f"/chunk/{key}/{index}",
                            policy=RetryPolicy(max_attempts=2,
                                               deadline=30.0),
                            health=self._health, peer=url.rstrip("/"),
                            timeout=30.0,
                            what=f"swarm chunk {key}/{index} from {host}")
                    if len(r.content) != ln:
                        raise TruncatedBody(
                            f"swarm chunk {key}/{index}: "
                            f"{len(r.content)} != {ln}")
                    metrics.HUB.inc("swarm_peer_bytes_total", ln)
                    with self._lock:
                        self._peer_bytes[key] = \
                            self._peer_bytes.get(key, 0) + ln
                    self.board.put(key, index, r.content)
                    return True
                except (requests.RequestException, WireError, OSError) as e:
                    log.warning("swarm fill of %s/%d from %s failed: %s",
                                key, index, host, e)
                    self._poll_failed(host)
            return False
        finally:
            self._release(key, index)

    # -- background pumps ------------------------------------------------
    def _pump_origin(self) -> None:
        """Owned chunks off origin, rarest-first-ish: among the remaining
        owned set, always the chunk the fewest siblings advertise (hash
        tie-break decorrelates hosts) — the swarm's rarest pieces cross
        origin earliest, classic BitTorrent scheduling. Runs until
        close(): succession can grow the owned set at any time
        (_take_over_orphans), so an idle pump parks on the cv instead of
        exiting."""
        while not self._stop.is_set():
            with self._lock:
                remaining = [c for c in self._owned
                             if c not in self._inflight
                             and not self.board.done(*c)]
                # one possession snapshot per pick, not one lock-held
                # _advertisers() scan per candidate: a 13 GB manifest is
                # ~1700 owned chunks on a solo host and re-scoring the
                # whole remainder under the scheduler lock every fetch
                # contends with ensure()/fill workers for the pull's
                # entire duration
                peer_have = {h: files
                             for h, files in self._peer_have.items()
                             if h not in self._dead}
            if not remaining:
                with self._cv:
                    self._cv.wait(timeout=0.5)
                continue

            def rarity(c: tuple[str, int]) -> tuple[int, int]:
                sk = self._spread.get(c)
                if sk is None:
                    sk = self._spread[c] = swarm_placement.spread_key(
                        _swarm_chunk_id(*c))
                n = sum(1 for files in peer_have.values()
                        if c[1] in files.get(c[0], ()))
                return (n, sk)

            key, index = min(remaining, key=rarity)
            with self._lock:
                reowned = self._primary.get((key, index)) != self.self_id
            try:
                # demodel: allow(atomic-snapshot) — _primary is
                # write-once at plan time (pre-start), so the reowned
                # verdict cannot go stale between the holds; the fetch
                # itself re-claims under the lock before any work
                self._fetch_origin(key, index, reowned=reowned)
            except IOError as e:
                log.warning("swarm origin fetch of %s/%d failed: %s "
                            "(will retry / re-ensure on demand)",
                            key, index, e)
                with self._cv:
                    self._cv.wait(timeout=0.5)

    def _pump_gossip(self) -> None:
        # dead hosts stay in the poll rotation: death is a ROUTING
        # verdict (stop waiting on it, succession takes its chunks), not
        # a ban — a wedged-then-recovered or restarted sibling re-enters
        # on its first successful poll (merge_summary resurrects it)
        siblings = [h for h in self.participants if h != self.self_id]
        while not self._stop.is_set():
            for host in siblings:
                if self._stop.is_set():
                    return
                self._poll_one(host)
            self._stop.wait(self._gossip_s)

    def _poll_one(self, host: str) -> None:
        # deliberately span-free and single-attempt (a raw session.get,
        # not request_with_retry): a background poll failing against a
        # dead sibling is ROUTINE — it must not become an error-status
        # root span that trips the flight recorder's incident dump, and
        # the next poll tick IS the retry
        url = self.participants[host]
        try:
            r = self._session().get(
                f"{url}/swarm/{self.pull_id}/{host}/chunks", timeout=5.0)
            r.raise_for_status()
            self.merge_summary(host, r.json())
        except (requests.RequestException, OSError, ValueError,
                TypeError):
            self._poll_failed(host)

    def merge_summary(self, host: str, summary: dict) -> None:
        """Versioned merge of one sibling's possession bitmap (also fed
        by tests/bench driving in-process boards directly)."""
        if not isinstance(summary, dict):
            return
        try:
            version = int(summary.get("v", 0))
            files = summary.get("files", {})
            have = {
                str(k): bitmap_indices(str(spec.get("have", "")),
                                       int(spec.get("n", 0)))
                for k, spec in files.items() if isinstance(spec, dict)
            }
            # done ⊇ have: landed-at-least-once (reaped included) — the
            # reap gate. A summary without it (older sibling) degrades
            # to have, which merely delays our reap, never corrupts
            done = {
                str(k): bitmap_indices(str(spec.get("done",
                                                    spec.get("have", ""))),
                                       int(spec.get("n", 0)))
                for k, spec in files.items() if isinstance(spec, dict)
            }
        except (TypeError, ValueError, AttributeError):
            return  # junk gossip degrades to nothing, never a crash
        with self._cv:
            # a DEAD host's successful poll always wins: a restarted
            # sibling's board restarts its version counter near zero, so
            # holding it to the old high-water mark would veto the very
            # resurrection _pump_gossip promises
            if host not in self._dead \
                    and version < self._peer_ver.get(host, -1):
                return  # stale reordering
            self._peer_ver[host] = version
            self._peer_have[host] = have
            self._peer_done[host] = done
            self._poll_fails[host] = 0
            if host in self._dead:
                # resurrection: chunks already taken over stay ours
                # (board dedupe makes the overlap at most one extra
                # origin chunk each), but the host serves cross-fills
                # and keeps its not-yet-orphaned chunks again
                self._dead.discard(host)
                log.info("swarm sibling %s resurrected (gossip poll "
                         "succeeded)", host)
            self._cv.notify_all()

    def _poll_failed(self, host: str) -> None:
        died = False
        with self._cv:
            fails = self._poll_fails.get(host, 0) + 1
            self._poll_fails[host] = fails
            if fails >= 3 and host not in self._dead:
                self._dead.add(host)
                died = True
                log.warning("swarm sibling %s declared dead after %d "
                            "straight failures; its chunks re-own via "
                            "ring succession", host, fails)
            self._cv.notify_all()
        if died:
            self._take_over_orphans()

    def _take_over_orphans(self) -> None:
        """Proactive succession: chunks whose primary is dead and whose
        first LIVE ring successor is this host join the origin pump now
        — a waiting sibling cross-fills from us instead of timing out
        into its own origin fetch (which would double-move the bytes)."""
        with self._cv:
            dead = set(self._dead)
            mine = set(self._owned)
            takeover = []
            for (key, idx), primary in self._primary.items():
                if primary not in dead or (key, idx) in mine:
                    continue
                chunk_id = _swarm_chunk_id(key, idx)
                live = [o for o in self.ring.owners(
                            chunk_id, len(self.participants))
                        if o == self.self_id or o not in dead]
                if live and live[0] == self.self_id:
                    takeover.append((key, idx))
            if not takeover:
                return
            self._owned.extend(takeover)
            self._cv.notify_all()
        log.info("swarm succession: taking over %d orphaned chunk(s) "
                 "from dead sibling(s) %s", len(takeover), sorted(dead))

    def _pump_reap(self) -> None:
        """The chunk-board reaper (ROADMAP swarm item b): periodically
        frees chunks that (a) EVERY live sibling already advertises
        possessing — the possession data is already gossiped, so nobody
        will ask us for them — and (b) the local delivery has consumed
        past, so a long pull's board stops retaining the whole file set
        until close(). A solo board (no siblings) reaps on consumption
        alone: there is no swarm left to serve."""
        while not self._stop.is_set():
            self._stop.wait(self._reap_s)
            if self._stop.is_set():
                return
            for key, index in self._reap_candidates():
                freed = self.board.reap(key, index)
                if freed:
                    metrics.HUB.inc("swarm_chunks_reaped_total")
                    metrics.HUB.inc("swarm_bytes_reaped_total", freed)

    def _reap_candidates(self) -> list[tuple[str, int]]:
        with self._lock:
            live = [h for h in self.participants
                    if h != self.self_id and h not in self._dead]
            # gate on the gossiped DONE sets (have ∪ reaped): a sibling
            # that reaped first stops ADVERTISING a chunk, and gating on
            # its have-set would block everyone who consumes later from
            # ever reaping (the normal case in a skewed pod)
            peer_done = {h: self._peer_done.get(h, {}) for h in live}
            sizes = {k: s for k, (s, _n, _r) in self._files.items()}
            consumed = dict(self._consumed_upto)
            # an in-flight read at offset s may still need chunks ≥ s:
            # prefetch workers complete out of order as the NORM, so the
            # completed-read high-water alone would reap under a slower
            # low-offset job and force counted origin re-fetches
            floors = {k: min(starts) for k, starts
                      in self._active_reads.items() if starts}
        out = []
        for key, index in self.board.held():
            size = sizes.get(key)
            if size is None:
                continue
            c_off, c_len = chunk_span(size, self.chunk_bytes, index)
            safe_upto = min(consumed.get(key, 0),
                            floors.get(key, float("inf")))
            if c_off + c_len > safe_upto:
                continue  # local delivery may still need it
            if all(index in peer_done[h].get(key, ()) for h in live):
                out.append((key, index))
        return out

    def _pump_fill(self) -> None:
        """Cross-fill any advertised, non-local, non-owned chunk — the
        keep-the-pipe-full role; ensure() only ever waits for chunks the
        pumps haven't reached yet."""
        while not self._stop.is_set():
            target = None
            with self._lock:
                for host, files in self._peer_have.items():
                    if host in self._dead:
                        continue
                    for key, idxs in files.items():
                        if key not in self._files:
                            continue
                        for i in sorted(idxs):
                            if (key, i) not in self._inflight \
                                    and not self.board.done(key, i):
                                target = (key, i)
                                break
                        if target:
                            break
                    if target:
                        break
            if target is None:
                with self._cv:
                    self._cv.wait(timeout=self._gossip_s)
                continue
            # demodel: allow(atomic-snapshot) — the pick is ADVISORY:
            # _advertisers re-reads liveness and _fetch_peer's _claim
            # re-validates inflight/done under the lock before any
            # bytes move, so a stale pick costs one no-op loop, never
            # a wrong transfer
            adv = self._advertisers(*target)
            if adv:
                # demodel: allow(atomic-snapshot) — same advisory pick:
                # _claim re-validates under the lock before any bytes move
                self._fetch_peer(*target, adv)

    def stats(self) -> dict:
        with self._lock:
            out = {
                "pull": self.pull_id, "host": self.self_id,
                "hosts": len(self.participants),
                "owned_chunks": len(self._owned),
                "chunks_refetched": self.chunks_refetched,
                "dead": sorted(self._dead),
                "peer_fill_bytes": sum(self._peer_bytes.values()),
            }
        out.update(self.board.stats())
        return out


class SwarmBlobReader:
    """Store-shaped reads served off a swarm scheduler's chunk board —
    what the delivery pipeline sees instead of a raw origin reader when
    a pull runs in swarm mode. ``bytes_fetched`` keeps the pod-delivery
    accounting honest: origin bytes (via the wrapped reader, headers
    included) + peer-fill bytes attributed to this file."""

    def __init__(self, scheduler: SwarmScheduler, remote_key: str,
                 size: int, origin_reader: PeerBlobReader):
        self.scheduler = scheduler
        self.remote_key = remote_key
        self._size = int(size)
        self._origin = origin_reader

    @property
    def bytes_fetched(self) -> int:
        return self._origin.bytes_fetched \
            + self.scheduler.peer_bytes_for(self.remote_key)

    def size(self, key: str) -> int:  # noqa: ARG002 — single-object reader
        return self._size

    def pread(self, key: str, length: int, offset: int) -> bytes:
        out = bytearray(length)
        self.pread_into(key, out, offset)
        return bytes(out)

    def pread_into(self, key: str, out, offset: int = 0) -> int:  # noqa: ARG002
        view = memoryview(out).cast("B")
        if view.nbytes == 0:
            return 0
        return self.scheduler.read_into(self.remote_key, view, offset)


class PipelineFailure(OSError):
    """A mid-pipeline delivery failure carrying the tensors that DID
    land before the error — the caller resumes from them instead of
    redoing every device transfer (VERDICT r4 weak #4: one flaky window
    at shard 14 of a 15-shard pull must not cost the whole pull)."""

    def __init__(self, cause: OSError, partial: "Placement"):
        super().__init__(str(cause))
        self.cause = cause
        self.partial = partial


def _deliver_jobs_pipelined(jobs, mesh, plan, cast_to=None,
                            prefetch_depth: int | None = None) -> Placement:
    """Single-process safetensors delivery with a tensor prefetch window
    spanning FILE boundaries: while tensor N's ``device_put`` is in
    flight, the next ``prefetch_depth`` tensors' byte windows download
    (multi-stream, native) — wall-clock ≈ max(network, host→HBM) instead
    of their sum, with no bubble between files. Only used when this
    process addresses the whole mesh (a pod host must fetch exactly its
    shard windows instead — prefetching whole tensors would defeat shard
    reads).

    ``jobs``: ``[(reader, key, name, spec)]`` in manifest order.
    """
    from concurrent.futures import ThreadPoolExecutor

    from demodel_tpu.formats.safetensors import _np_dtype
    from demodel_tpu.sink import tuner as tuner_mod
    from demodel_tpu.sink.hbm import place_tensor
    from demodel_tpu.sink.streaming import ByteBudget

    if prefetch_depth is None:
        # prefetch overlap needs either a SPARE core or a transfer that
        # leaves the core: on a single-CPU host with the CPU backend,
        # "device_put" is a memcpy on the same core and even one
        # background fetch thread contends (598 vs 238 MB/s at 1 GiB) —
        # default 0, fully synchronous. On a REAL TPU the host→device
        # transfer runs in the runtime off the GIL, so one fetch thread
        # overlaps it even on one core; multi-core keeps depth 2.
        import jax as _jax

        from demodel_tpu.utils.env import available_cpus

        if available_cpus() > 1:
            default_depth = 2
        elif _jax.default_backend() == "tpu":
            default_depth = 1
        else:
            default_depth = 0
        prefetch_depth = env_int(
            "DEMODEL_SINK_PREFETCH", default_depth, minimum=0)
    out = Placement(mesh_desc=f"{dict(mesh.shape)}")
    # landing buffers are charged to the SAME byte budget the streaming
    # sink enforces (DEMODEL_SINK_BUFFER_MB): before this, prefetch
    # workers could pin depth × tensor bytes of host RAM with no bound —
    # the accounting gap the hbm-budget analyzer rule flags
    budget = ByteBudget(env_int("DEMODEL_SINK_BUFFER_MB", 1024,
                                minimum=1) << 20)

    # FIFO admission tickets: budget grants MUST follow job order. The
    # main loop consumes futures in order, so if a later window could
    # win capacity freed for an earlier one, the three-way wait closes:
    # main blocks on the earlier future, whose worker blocks in acquire,
    # waiting for a release that only happens when main places the LATER
    # buffer. With tickets, the head job is the only one in acquire, and
    # everything it waits on is already in main's consume path.
    admission = {"next": 0, "dead": False}
    admit_cv = threading.Condition()

    # the closed loop: an AIMD controller reads the live windowed
    # telemetry (window-read p99, retry rate, budget-wait share, delivery
    # rate) and moves streams / window size / prefetch depth between
    # windows — DEMODEL_TUNER=0 keeps every knob at its fixed default
    tuner = (tuner_mod.PullTuner(budget=budget,
                                 prefetch_depth=prefetch_depth).start()
             if tuner_mod.tuner_enabled() else None)

    def fetch(job, idx):
        reader, key, name, spec = job
        nbytes = spec.end - spec.start
        with trace.span("prefetch-fetch", tensor=name, bytes=nbytes,
                        job=idx):
            # the admission-ticket wait + budget charge together are the
            # "waiting for RAM" stage of a slow pull — own span so the
            # critical path can name it
            with trace.span("budget-wait", bytes=nbytes):
                with admit_cv:
                    while admission["next"] != idx \
                            and not admission["dead"]:
                        admit_cv.wait()
                got = False
                try:
                    # charge before the bytes exist, so a worker blocks
                    # HERE rather than allocating past the budget;
                    # released after place()
                    budget.acquire(nbytes)
                    got = True
                finally:
                    try:
                        with admit_cv:
                            admission["next"] = idx + 1
                            admit_cv.notify_all()
                    except BaseException:
                        # the ticket is held by now: a raise on the
                        # hand-over path must give it back or the
                        # budget is down nbytes forever
                        if got:
                            budget.release(nbytes)
                        raise
            try:
                buf = np.empty(nbytes, dtype=np.uint8)
                tuner_mod.fetch_windows(reader, key, buf, spec.start,
                                        tuner)
            except BaseException:
                budget.release(nbytes)
                raise
            return buf

    def place(buf, name, spec):
        mv = memoryview(buf)
        start = spec.start

        def read_at(off, ln, _mv=mv, _s=start):
            return _mv[off - _s:off - _s + ln]

        np_dtype = _np_dtype(spec.dtype)
        if name in out.arrays:
            raise ValueError(f"duplicate tensor across shards: {name}")
        sharding = plan.sharding_for(name, spec.shape, np_dtype.itemsize)
        with trace.span("place", tensor=name, bytes=buf.nbytes):
            out.arrays[name] = place_tensor(
                read_at, spec.shape, np_dtype, spec.start, sharding,
                cast_to)

    # phase accounting (exposed via the pull report): fetch wall vs
    # place wall tells whether a slow pull is network-bound or
    # device-transfer-bound.
    # Under prefetch overlap the first key is the EXPOSED stall on the
    # next buffer (overlapped network time hides inside place), so it is
    # named fetch_stall_secs there, not fetch_secs.
    fetch_key = "fetch_secs" if prefetch_depth == 0 else "fetch_stall_secs"
    phases = {fetch_key: 0.0, "place_secs": 0.0}
    out.phase_secs = phases

    if prefetch_depth == 0:
        # thread-free: fetch inline, place, next — the fastest shape
        # when there is no core to hide the fetch on
        try:
            for i, (reader, key, name, spec) in enumerate(jobs):
                t0 = time.perf_counter()
                try:
                    buf = fetch((reader, key, name, spec), i)
                except OSError as e:
                    raise PipelineFailure(e, out) from e
                t1 = time.perf_counter()
                try:
                    place(buf, name, spec)
                finally:
                    budget.release(buf.nbytes)
                t2 = time.perf_counter()
                phases[fetch_key] += t1 - t0
                phases["place_secs"] += t2 - t1
        finally:
            if tuner is not None:
                tuner.stop()
        return out

    # with a live tuner the pool is sized to the prefetch CEILING and the
    # submit loop keeps only the tuner's CURRENT depth in flight — depth
    # changes apply between jobs, never mid-fetch
    pool_size = tuner.max_prefetch if tuner is not None else prefetch_depth
    with ThreadPoolExecutor(max_workers=max(1, pool_size)) as ex:
        # the try must live INSIDE the `with`: on an exception the
        # executor's __exit__ joins its workers during unwinding, so a
        # worker blocked in budget.acquire has to be woken by abort()
        # BEFORE that join runs — an outer handler would run after it,
        # i.e. after the deadlock
        try:
            # trace.wrap: executor threads don't inherit contextvars, so
            # capture the pull span's context at the submit site
            pending: list = []
            next_job = 0

            def top_up() -> None:
                nonlocal next_job
                depth = (max(1, min(tuner.prefetch_depth, pool_size))
                         if tuner is not None else prefetch_depth)
                while len(pending) < depth and next_job < len(jobs):
                    pending.append(ex.submit(trace.wrap(fetch),
                                             jobs[next_job], next_job))
                    next_job += 1

            top_up()
            for i, (reader, key, name, spec) in enumerate(jobs):
                t0 = time.perf_counter()
                try:
                    buf = pending.pop(0).result()
                except OSError as e:
                    # surface WHAT already landed: placed tensors are
                    # final (their bytes are verified views of fetched
                    # windows) — the failover path resumes from them
                    for p in pending:
                        p.cancel()
                    raise PipelineFailure(e, out) from e
                t1 = time.perf_counter()
                top_up()
                try:
                    place(buf, name, spec)
                finally:
                    budget.release(buf.nbytes)
                phases[fetch_key] += t1 - t0
                phases["place_secs"] += time.perf_counter() - t1
        except BaseException:
            # in-flight buffers die with this call; their charges are
            # moot. Wake BOTH wait states before the executor join:
            # acquire-waiters via abort, ticket-waiters via "dead"
            budget.abort()
            with admit_cv:
                admission["dead"] = True
                admit_cv.notify_all()
            raise
        finally:
            if tuner is not None:
                tuner.stop()
    return out


def pull_manifest_to_hbm(
    model: str,
    peers: list[str],
    mesh=None,
    plan: ShardingPlan | None = None,
    source: str = "hf",
    cast_to=None,
    ici_complete: bool | None = None,
    streams: int | None = None,
    swarm: "SwarmScheduler | None" = None,
):
    """Place ``model`` into HBM straight off a warm peer, shard-reads only.

    ``swarm``: a started-or-startable :class:`SwarmScheduler` makes this
    a swarm-mode cold pull — this host fetches only its ring-owned chunk
    set from the warm-peer rotation and cross-fills the rest from its
    swarm siblings (aggregate origin bytes ≈ 1× the manifest across the
    pod, not N×). The caller owns the scheduler lifecycle: keep it open
    until the whole pod is done, then ``close()`` it.

    Every host of a ``jax.distributed`` pod calls this with the same
    arguments; each fetches only its devices' byte windows over DCN and
    replicated tensors complete over ICI (each host reads 1/N). Returns
    ``(report, Placement)`` where ``report["network_bytes"]`` is THIS
    host's DCN byte count — the pod-delivery proof asserts it is a strict
    fraction of the checkpoint.

    Weight files deliver in manifest order (identical on every host), so
    cross-host collectives pair deterministically — see module docstring.
    """
    import os

    from demodel_tpu.parallel.mesh import make_mesh

    if mesh is None:
        mesh = make_mesh()
    if plan is None:
        plan = ShardingPlan(mesh)
    profile_dir = os.environ.get("DEMODEL_PROFILE_DIR", "").strip()
    profiling = False
    if profile_dir:
        # SURVEY §5 tracing: same jax.profiler window the whole-file pull
        # gets — open in xprof to see window fetch vs device transfer
        try:
            import jax.profiler as _profiler

            _profiler.start_trace(profile_dir)
            profiling = True
        except Exception as e:  # noqa: BLE001 — tracing must never break a pull
            log.warning("jax.profiler trace not started: %s", e)
    try:
        # the ROOT span of a sharded pull: every window read, budget
        # wait, retry and failover below stitches under this trace id —
        # and across hosts via the traceparent the wire calls carry
        with trace.span("pull", model=model, source=source,
                        swarm=(swarm.self_id if swarm else None)):
            return _pull_manifest_to_hbm(model, peers, mesh, plan, source,
                                         cast_to, ici_complete, streams,
                                         swarm)
    finally:
        if profiling:
            try:
                import jax.profiler as _profiler

                _profiler.stop_trace()
                log.info("sharded-pull trace written to %s", profile_dir)
            except Exception as e:  # noqa: BLE001
                log.warning("jax.profiler stop_trace failed: %s", e)


def _pull_manifest_to_hbm(model, peers, mesh, plan, source, cast_to,
                          ici_complete, streams, swarm=None):
    import jax

    from demodel_tpu.sink.hbm import deliver_safetensors

    t0 = time.perf_counter()
    peer, manifest = fetch_manifest(peers, model, source=source)
    placement = Placement(mesh_desc=f"{dict(mesh.shape)}")
    report: dict = {
        "name": model, "source": source, "peer": peer,
        "files": list(manifest.get("files", [])),
        "network_bytes": 0, "weight_bytes": 0,
    }
    readers: list[PeerBlobReader] = []
    # Peer policy, single-process: files stripe round-robin over the
    # RESPONSIVE peers (pipelined path below rotates the primary per
    # file), with the rest of the order as failover — a header/window
    # failure retries the file (or, mid-pipeline, rebuilds via the
    # per-file path). Peers are liveness-probed once up front with a
    # short deadline so a hung-but-accepting peer never lands on the
    # critical path at its full read timeout.
    # Multi-host meshes pin everything to the manifest peer and re-raise
    # on failure: a host that locally retried a file whose earlier
    # tensors already ran their redistribute() collectives would re-issue
    # them while other hosts sit in later ones — same-shaped tensors
    # would pair silently wrong, different shapes deadlock; the caller
    # restarts the pull pod-wide instead.
    if jax.process_count() == 1:
        others = [p.rstrip("/") for p in peers if p.rstrip("/") != peer]
        peer_order = [peer] + _responsive_peers(others)
    else:
        peer_order = [peer]
    weight_files = []
    for f in manifest.get("files", []):
        if not is_weight_file(f["name"], f.get("media_type", "")):
            continue
        if int(f.get("size") or 0) <= 0:
            raise IOError(f"manifest entry {f['name']} lacks a size")
        weight_files.append(f)

    # single-process safetensors: one prefetch pipeline over ALL tensors
    # of ALL files in manifest order — tensor N's device transfer overlaps
    # tensor N+1..N+depth's downloads with no bubble at file boundaries
    pipelined = False
    resume_skip: set = set()       # tensors placed by a failed pipeline
    file_tensors: dict = {}        # file key → its tensor names
    if (jax.process_count() == 1
            and weight_files
            and all(f["name"].endswith(".safetensors")
                    for f in weight_files)):
        try:
            jobs = []
            health = PeerHealth.shared()
            # files stripe over the RESPONSIVE peers by consistent hash
            # with BOUNDED LOADS: every host computes the same file→peer
            # primary from the same ring+capacity walk, so the striping
            # needs no rotation counter — and no peer's primary share
            # exceeds ceil(files/N) (pure ring ownership is lumpy on a
            # small file set; a capacity-spilled file's primary is still
            # on its ring succession, so PeerSet.locate's ring-first
            # guess misses at most into its probe fallback). The rest of
            # the ring order is the failover rotation; peers whose
            # breaker opened mid-pull drop out HERE — a peer that died
            # at file 3 must not greet files 4..N with a full
            # read-timeout each (it re-enters via its half-open probe
            # once the cooldown elapses)
            stripe_ring = HashRing(peer_order)
            stripe = bounded_assign(
                stripe_ring, [f["key"] for f in weight_files])
            for f in weight_files:
                primary = stripe.get(f["key"]) or peer_order[0]
                rotated = [primary] + [p for p in peer_order
                                       if p != primary]
                reader, index = _reader_and_index(
                    f, health.healthy(rotated), streams)
                fkey, fsize = f["key"], int(f["size"])
                file_tensors[fkey] = set(index.tensors)
                if swarm is not None:
                    swarm.add_file(fkey, fsize, reader)
                    reader = SwarmBlobReader(swarm, fkey, fsize, reader)
                readers.append(reader)
                for tname, spec in index.tensors.items():
                    jobs.append((reader, fkey, tname, spec))
            if swarm is not None:
                swarm.start()
            delivered = _deliver_jobs_pipelined(
                jobs, mesh, plan, cast_to=cast_to)
            merge_placement(placement, delivered)
            report["phase_secs"] = delivered.phase_secs
            report["weight_bytes"] += sum(int(f["size"])
                                          for f in weight_files)
            pipelined = True
        except PipelineFailure as e:
            # mid-pipeline peer failure: keep every tensor that already
            # landed (their bytes are verified fetched windows) and let
            # the per-file failover below deliver ONLY the missing ones
            # — a flaky window at shard 14 of 15 costs the remaining
            # windows, not a full redo of the device transfers
            merge_placement(placement, e.partial)
            # the phase split for what DID land — the flaky-pull case is
            # exactly where the fetch/place diagnosis matters most. The
            # resumed remainder below accumulates no phase timings, so
            # flag the split as partial: a consumer summing phase_secs
            # against wall-clock must not mistake pre-failure seconds
            # for the whole pull's
            report["phase_secs"] = e.partial.phase_secs
            report["phase_secs_partial"] = True
            resume_skip = set(e.partial.arrays)
            log.warning("pipelined delivery failed (%s); %d tensors "
                        "landed — resuming the rest with per-file "
                        "failover", e.cause, len(resume_skip))
            report["weight_bytes"] = 0
        except OSError as e:
            # failure outside the pipeline loop (header/index reads):
            # nothing landed, full per-file fallback
            log.warning("pipelined delivery failed (%s); retrying with "
                        "per-file failover", e)
            placement = Placement(mesh_desc=f"{dict(mesh.shape)}")
            report["weight_bytes"] = 0

    if not pipelined:
        from demodel_tpu.sink.hbm import deliver_gguf

        for f in weight_files:
            name, key = f["name"], f["key"]
            size = int(f["size"])
            if resume_skip and key in file_tensors \
                    and file_tensors[key] <= resume_skip:
                # every tensor of this file survived the failed pipeline:
                # no reader, no header re-fetch, bytes already accounted
                report["weight_bytes"] += size
                continue
            placed = None
            last_err: Exception | None = None
            retry_order = PeerHealth.shared().healthy(peer_order)
            for pi, source_peer in enumerate(retry_order):
                reader = PeerBlobReader(
                    source_peer, key, size, streams=streams,
                    failover=retry_order[pi + 1:] + retry_order[:pi])
                try:
                    if name.endswith(".safetensors"):
                        # skip ONLY the resume survivors — skipping the
                        # whole accumulated placement would silently
                        # disable the cross-shard duplicate-tensor guard
                        placed = deliver_safetensors(
                            reader, key, mesh=mesh, plan=plan,
                            cast_to=cast_to, ici_complete=ici_complete,
                            skip=resume_skip)
                    else:
                        placed = deliver_gguf(reader, key, mesh=mesh,
                                              plan=plan)
                    readers.append(reader)
                    break
                except (OSError, ValueError) as e:
                    # OSError: transport (incl. requests exceptions mapped
                    # by the reader); ValueError: corrupt header bytes
                    last_err = e
                    readers.append(reader)  # count wasted bytes honestly
                    log.warning("delivery of %s from %s failed (%s); "
                                "trying next peer", name, source_peer, e)
            if placed is None:
                raise IOError(f"no peer could serve {name}") from last_err
            merge_placement(placement, placed)
            report["weight_bytes"] += size
    t_block = time.perf_counter()
    # demodel: allow(no-host-sync-in-hot-path) — the pod pull's single
    # end-of-delivery sync: block_secs is reported, and every device
    # transfer has already been dispatched when we get here
    jax.block_until_ready(list(placement.arrays.values()))
    report["block_secs"] = round(time.perf_counter() - t_block, 3)
    report["network_bytes"] = sum(r.bytes_fetched for r in readers)
    report["secs"] = round(time.perf_counter() - t0, 3)
    log.info("pod-placed %d tensors (%.1f MB weights) from %s: this host "
             "fetched %.1f MB over DCN in %.2fs",
             len(placement.arrays), report["weight_bytes"] / 1e6, peer,
             report["network_bytes"] / 1e6, report["secs"])
    return report, placement


def materialize_aux_files(manifest: dict, peer: str, dest,
                          timeout: float = 60.0) -> list:
    """Fetch the small non-weight files (config/tokenizer/index) of a
    peer-held model into ``dest`` — consumers (`transformers`) need them
    on disk next to nothing else; weight bytes stay on the wire→HBM path."""
    from pathlib import Path

    dest = Path(dest)
    dest.mkdir(parents=True, exist_ok=True)
    s = requests.Session()
    health = PeerHealth.shared()
    policy = RetryPolicy()
    out = []
    for f in manifest.get("files", []):
        if is_weight_file(f["name"], f.get("media_type", "")):
            continue
        r = request_with_retry(
            s, "GET", f"{peer}/peer/object/{f['key']}",
            policy=policy, health=health, peer=peer.rstrip("/"),
            timeout=timeout, what=f"aux file {f['name']}")
        p = dest / f["name"].replace("/", "_")
        p.write_bytes(r.content)
        out.append(p)
    return out
