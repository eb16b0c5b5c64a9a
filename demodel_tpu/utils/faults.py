"""Wire-plane fault tolerance: retry policy + shared peer-health breakers.

The pull/restore/registry plane talks to *friends'* machines over flaky
links ("serve your friends", PAPER.md): peer resets, stalls, and 5xx are
the steady state, not the exception. Every HTTP call on that plane routes
through this module — the ``wire-call-policy`` analyzer rule enforces it —
so the whole wire surface shares one behavior:

- :class:`RetryPolicy` — exponential backoff with **full jitter**, bounded
  by both an attempt cap (``DEMODEL_RETRY_MAX``) and a wall-clock deadline
  (``DEMODEL_RETRY_DEADLINE``), with an explicit retryable-error
  classification (:func:`retryable`): connect errors, resets, timeouts,
  429/5xx, and truncated bodies retry; digest mismatches and other 4xx
  don't — re-reading poisoned bytes or a missing object cannot help.
- :class:`PeerHealth` — a process-wide registry of per-peer
  :class:`CircuitBreaker`\\ s (closed → open after consecutive failures →
  half-open probe after cooldown), shared by the peer shard cache, the
  striping rotation, and manifest discovery: a peer that dies mid-pull
  stops landing on the critical path at full read-timeout for every
  remaining file.
- :func:`request_with_retry` — the one choke point that composes both
  around a ``requests`` call and feeds the retry/breaker counters in
  :mod:`demodel_tpu.utils.metrics`.

Sleeps and clocks are injectable so the whole state machine unit-tests
with a clock stub — no real sleeps on any fast path.
"""

from __future__ import annotations

import random
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, ClassVar, TypeVar

import requests

from demodel_tpu.utils import metrics, trace
from demodel_tpu.utils.env import env_int
from demodel_tpu.utils.logging import get_logger

log = get_logger("faults")

T = TypeVar("T")


# ------------------------------------------------------------ error classes


class WireError(IOError):
    """A transport-shaped failure worth retrying (reset, truncation, a peer
    answering the wrong protocol) — as opposed to a content-shaped one."""


class TruncatedBody(WireError):
    """The peer promised N bytes and delivered fewer before a clean close —
    retryable: the next attempt resumes at the received offset."""


class RangeIgnored(WireError):
    """The peer answered 200-from-zero to a nonzero Range request.
    NOT retryable against the same peer (it will ignore the next Range
    too — re-dialing a deterministic failure just burns the backoff
    budget and poisons the breaker); :func:`peer_cannot_serve` marks it
    failover-eligible, another peer may do ranges."""


class DigestMismatch(IOError):
    """Delivered bytes hash wrong. NOT retryable: the transfer completed,
    so the wire is fine and the peer's copy (or our expectation) is
    poisoned — re-reading the same object cannot converge."""


class BreakerOpen(IOError):
    """A request was refused locally because the peer's breaker is open."""


#: HTTP statuses a retry can plausibly outlive (408 request-timeout, 429
#: backpressure, and the transient 5xx family — the bounded session pool
#: itself answers 503+Retry-After under flood)
RETRYABLE_STATUS = frozenset({408, 429, 500, 502, 503, 504})


def retryable(exc: BaseException) -> bool:
    """The explicit classification every wire caller shares: transport
    errors, resets, timeouts, 429/5xx and truncated bodies retry; digest
    mismatches, JSON junk, and other 4xx don't."""
    if isinstance(exc, (DigestMismatch, BreakerOpen, RangeIgnored)):
        return False
    if isinstance(exc, WireError):
        return True
    if isinstance(exc, requests.HTTPError):
        resp = exc.response
        if resp is None:
            return True
        return resp.status_code in RETRYABLE_STATUS or resp.status_code >= 500
    if isinstance(exc, ValueError):
        # junk content (incl. requests' JSONDecodeError, which subclasses
        # both ValueError and RequestException): the peer-json-shape
        # degrade contract, not a wire fault — checked BEFORE the generic
        # RequestException arm below
        return False
    if isinstance(exc, (requests.ConnectionError, requests.Timeout)):
        return True
    if isinstance(exc, requests.RequestException):
        # ChunkedEncodingError, ContentDecodingError, … — mid-body
        # transport failures
        return True
    if isinstance(exc, (ConnectionError, TimeoutError)):
        # raw socket resets/timeouts (ConnectionResetError et al.)
        return True
    return False


def peer_cannot_serve(exc: BaseException) -> bool:
    """THIS peer cannot serve THIS object, though the peer is healthy:
    a missing blob (404/410), an unsatisfiable or ignored Range, an
    unimplemented method. Not a health event and not worth a same-peer
    retry — but a rotation holding the same key should try its next
    peer before giving up."""
    if isinstance(exc, RangeIgnored):
        return True
    if isinstance(exc, requests.HTTPError):
        resp = exc.response
        return resp is not None and 400 <= resp.status_code < 500 \
            and resp.status_code not in RETRYABLE_STATUS
    return False


# --------------------------------------------------------------- RetryPolicy


def _default_max_attempts() -> int:
    return env_int("DEMODEL_RETRY_MAX", 4, minimum=1)


def _default_deadline() -> float:
    """Wall-clock budget across all attempts of one logical operation.
    MUST comfortably exceed the largest per-attempt read timeout
    (DEMODEL_PEER_TIMEOUT 120 s windows, 300 s object streams): a
    deadline smaller than one attempt means a first-attempt stall eats
    the whole budget and the failover branch never runs. The attempt cap
    is the primary bound; this is the backstop."""
    return float(env_int("DEMODEL_RETRY_DEADLINE", 600, minimum=1))


def _default_base_delay() -> float:
    return env_int("DEMODEL_RETRY_BASE_MS", 100, minimum=1) / 1000.0


def default_breaker_threshold() -> int:
    return env_int("DEMODEL_BREAKER_THRESHOLD", 3, minimum=1)


def default_breaker_cooldown() -> float:
    return float(env_int("DEMODEL_BREAKER_COOLDOWN", 15, minimum=1))


@dataclass
class RetryPolicy:
    """Exponential backoff with full jitter, capped by attempts AND a
    wall-clock deadline (AWS-style full jitter: ``uniform(0, base·2^k)``
    decorrelates a fleet of pod hosts hammering the same recovering peer).
    """

    max_attempts: int = field(default_factory=_default_max_attempts)
    #: wall-clock budget across ALL attempts of one logical operation
    deadline: float = field(default_factory=_default_deadline)
    base_delay: float = field(default_factory=_default_base_delay)
    max_delay: float = 5.0
    #: injectables — tests swap in stubs; no real sleeps on fast paths
    sleep: Callable[[float], None] = time.sleep
    clock: Callable[[], float] = time.monotonic
    rng: random.Random = field(default_factory=random.Random)

    def next_delay(self, attempt: int) -> float:
        """Full-jitter delay before retry number ``attempt`` (1-based)."""
        ceiling = min(self.base_delay * (2 ** max(0, attempt - 1)),
                      self.max_delay)
        return self.rng.uniform(0.0, ceiling)

    def deadline_left(self, start: float) -> float:
        return self.deadline - (self.clock() - start)

    def should_retry(self, attempt: int, start: float,
                     exc: BaseException) -> float | None:
        """The one retry decision, shared by every loop that needs its own
        resume semantics (partial windows, store partials): ``None`` means
        give up (non-retryable / attempt cap / deadline), otherwise the
        jittered, deadline-clipped backoff to sleep before attempt+1."""
        if not retryable(exc):
            return None
        left = self.deadline_left(start)
        if attempt >= self.max_attempts or left <= 0:
            return None
        return min(self.next_delay(attempt), left)

    def call(self, fn: Callable[[], T], *, what: str = "",
             peer: str | None = None,
             health: "PeerHealth | None" = None) -> T:
        """Run ``fn`` under this policy. Retryable failures back off and
        re-try until the attempt cap or deadline; every outcome feeds
        ``health`` (when given) and the retry counters."""
        start = self.clock()
        attempt = 0
        while True:
            attempt += 1
            try:
                result = fn()
            except Exception as e:  # noqa: BLE001 — classified right below
                if health is not None and peer is not None and retryable(e):
                    health.record_failure(peer)
                left = self.deadline_left(start)
                if (not retryable(e) or attempt >= self.max_attempts
                        or left <= 0):
                    raise
                if health is not None and peer is not None \
                        and not health.admissible(peer):
                    # the breaker opened under our own failures: further
                    # same-peer retries are the exact stampede it exists
                    # to stop — surface the cause, not BreakerOpen
                    # (read-only check: this loop is giving up, not
                    # claiming the probe slot)
                    raise
                delay = min(self.next_delay(attempt), max(0.0, left))
                count_retry(peer, delay)
                trace.event("retry", attempt=attempt, peer=peer,
                            error=f"{type(e).__name__}: {e}",
                            backoff_secs=round(delay, 4))
                log.warning("%s failed (%s: %s); retry %d/%d in %.2fs",
                            what or "wire call", type(e).__name__, e,
                            attempt, self.max_attempts - 1, delay)
                self.sleep(delay)
            else:
                if health is not None and peer is not None:
                    health.record_success(peer)
                return result


# ----------------------------------------------------------- circuit breaker

#: ``peer_breaker_state`` gauge values
STATE_CLOSED, STATE_HALF_OPEN, STATE_OPEN = 0, 1, 2

_STATE_NAMES = {STATE_CLOSED: "closed", STATE_HALF_OPEN: "half-open",
                STATE_OPEN: "open"}


class CircuitBreaker:
    """Per-peer breaker: closed → open after ``threshold`` consecutive
    failures → one half-open probe per ``cooldown`` until a success closes
    it again. Thread-safe; the clock is injectable (unit tests drive the
    cooldown with a stub, no real sleeps)."""

    def __init__(self, peer: str, threshold: int, cooldown: float,
                 clock: Callable[[], float] = time.monotonic) -> None:
        self.peer = peer
        self.threshold = threshold
        self.cooldown = cooldown
        self._clock = clock
        self._lock = threading.Lock()
        self._state = STATE_CLOSED
        self._failures = 0
        self._opened_at = 0.0
        self._probing = False
        self._probe_started = 0.0

    def state(self) -> int:
        with self._lock:
            return self._state

    def admissible(self) -> bool:
        """Read-only: could a request go to this peer right now? For pure
        FILTERS (rotation building, locate scans) that may never dial the
        peer — it claims nothing, so it can be called any number of times
        without burning the half-open probe slot (``allow`` claims)."""
        with self._lock:
            if self._state == STATE_CLOSED:
                return True
            now = self._clock()
            if self._state == STATE_OPEN:
                return now - self._opened_at >= self.cooldown
            return not (self._probing
                        and now - self._probe_started < self.cooldown)

    def allow(self) -> bool:
        """May a request go to this peer right now? Call this immediately
        before DIALING — an open breaker whose cooldown elapsed admits
        exactly ONE caller as the half-open probe (the claim is this
        call); everyone else keeps being refused until the probe
        reports. A filter that may not dial must use :meth:`admissible`
        instead, or the claimed slot starves the real probe."""
        with self._lock:
            if self._state == STATE_CLOSED:
                return True
            now = self._clock()
            if self._state == STATE_OPEN:
                if now - self._opened_at < self.cooldown:
                    return False
                self._set_state(STATE_HALF_OPEN)
                self._probing = True
                self._probe_started = now
                return True
            # half-open: one probe in flight; re-admit if the prober
            # vanished without reporting (died mid-request)
            if self._probing and now - self._probe_started < self.cooldown:
                return False
            self._probing = True
            self._probe_started = now
            return True

    def record_success(self) -> None:
        with self._lock:
            self._failures = 0
            self._probing = False
            if self._state != STATE_CLOSED:
                log.info("peer %s breaker closed (probe succeeded)",
                         self.peer)
                self._set_state(STATE_CLOSED)

    def record_failure(self) -> None:
        with self._lock:
            self._failures += 1
            failed_probe = self._state == STATE_HALF_OPEN
            self._probing = False
            if self._state == STATE_OPEN:
                # a direct dial past the elapsed cooldown (admissible()
                # filter paths never claim the probe) failed: the peer is
                # still dead — re-arm the cooldown, or admissible() would
                # re-admit it to every rotation forever, one full
                # read-timeout at a time
                self._opened_at = self._clock()
                return
            if failed_probe or (self._state == STATE_CLOSED
                                and self._failures >= self.threshold):
                self._opened_at = self._clock()
                if self._state != STATE_OPEN:
                    self._set_state(STATE_OPEN)
                    metrics.HUB.inc(metrics.labeled(
                        "peer_breaker_open_total", peer=self.peer))
                    log.warning(
                        "peer %s breaker OPEN (%d consecutive failures); "
                        "cooling down %.1fs", self.peer, self._failures,
                        self.cooldown)

    def describe(self) -> dict[str, Any]:
        """Live-state snapshot for ``/debug/statusz``: state name,
        consecutive failures, cooldown, and — when open — how long the
        peer has been cooling (the "which peer is the breaker punishing"
        answer, readable from curl)."""
        with self._lock:
            out: dict[str, Any] = {
                "state": _STATE_NAMES.get(self._state, str(self._state)),
                "failures": self._failures,
                "threshold": self.threshold,
                "cooldown_sec": self.cooldown,
            }
            if self._state != STATE_CLOSED:
                out["open_age_sec"] = round(
                    max(0.0, self._clock() - self._opened_at), 3)
                out["probe_in_flight"] = self._probing
            return out

    def _set_state(self, state: int) -> None:
        # caller holds self._lock
        self._state = state
        # the transition lands on whatever span drove the failing/probing
        # call — the operation that PAID for it (no-op outside a span)
        trace.event("breaker", peer=self.peer,
                    state=_STATE_NAMES.get(state, str(state)))
        metrics.HUB.set_gauge(
            metrics.labeled("peer_breaker_state", peer=self.peer),
            float(state))


class PeerHealth:
    """Process-wide breaker registry, shared by every wire caller so one
    component's failures protect every other component's critical path."""

    # (defaults resolve through module helpers below so the statusz
    # effective-config surface reports the values this class really uses)

    _shared: ClassVar["PeerHealth | None"] = None
    _shared_lock: ClassVar[threading.Lock] = threading.Lock()

    def __init__(self, threshold: int | None = None,
                 cooldown: float | None = None,
                 clock: Callable[[], float] = time.monotonic) -> None:
        self.threshold = (threshold if threshold is not None
                          else default_breaker_threshold())
        self.cooldown = (cooldown if cooldown is not None
                         else default_breaker_cooldown())
        self._clock = clock
        self._lock = threading.Lock()
        self._breakers: dict[str, CircuitBreaker] = {}

    @classmethod
    def shared(cls) -> "PeerHealth":
        with cls._shared_lock:
            if cls._shared is None:
                cls._shared = cls()
            return cls._shared

    @classmethod
    def reset_shared(cls) -> None:
        """Drop the process-wide registry (tests only)."""
        with cls._shared_lock:
            cls._shared = None

    def breaker(self, peer: str) -> CircuitBreaker:
        peer = peer.rstrip("/")
        with self._lock:
            b = self._breakers.get(peer)
            if b is None:
                b = self._breakers[peer] = CircuitBreaker(
                    peer, self.threshold, self.cooldown, self._clock)
            return b

    def allow(self, peer: str) -> bool:
        """Claiming check — call immediately before dialing ``peer``."""
        return self.breaker(peer).allow()

    def admissible(self, peer: str) -> bool:
        """Read-only check — for filters that may never dial ``peer``."""
        return self.breaker(peer).admissible()

    def record_success(self, peer: str) -> None:
        self.breaker(peer).record_success()

    def record_failure(self, peer: str) -> None:
        self.breaker(peer).record_failure()

    def describe(self) -> dict[str, dict[str, Any]]:
        """``peer → breaker snapshot`` for every peer this process has
        talked to (statusz). Read-only: never creates breakers, never
        touches probe slots."""
        with self._lock:
            breakers = dict(self._breakers)
        return {peer: b.describe() for peer, b in sorted(breakers.items())}

    def healthy(self, peers: list[str]) -> list[str]:
        """``peers`` filtered to those the breakers admit, order preserved
        — read-only (:meth:`admissible`), so building a rotation burns no
        probe slots. Falls back to the full list when every breaker
        refuses — a rotation with zero sources would turn a brown-out
        into an outage."""
        alive = [p for p in peers if self.admissible(p)]
        return alive if alive else list(peers)


# ------------------------------------------------------------------ metrics


def count_retry(peer: str | None, delay: float | None = None) -> None:
    """One retry happened against ``peer`` (or an upstream when None);
    ``delay`` (the jittered backoff about to be slept) feeds the
    ``retry_delay_seconds`` histogram — backoff time is invisible wall
    clock unless it lands on the scrape as a distribution."""
    name = "peer_retries_total"
    metrics.HUB.inc(metrics.labeled(name, peer=peer) if peer else name)
    if delay is not None:
        metrics.HUB.observe("retry_delay_seconds", delay)


# ------------------------------------------------------------ request choke


def request_with_retry(
    sender: Any,
    method: str,
    url: str,
    *,
    policy: RetryPolicy | None = None,
    health: PeerHealth | None = None,
    peer: str | None = None,
    ok_statuses: tuple[int, ...] = (),
    check_status: bool = True,
    what: str = "",
    **kw: Any,
) -> requests.Response:
    """THE wire choke point: one HTTP request under breaker + retry policy.

    ``sender`` is a ``requests.Session`` (or the ``requests`` module — both
    expose ``request``). ADMISSION is the caller's job (`health.allow` /
    `health.healthy` before dialing — an allow() on a cooled-down breaker
    IS the half-open probe slot, so re-checking here would refuse the very
    probe the caller was admitted for); this helper feeds the breaker with
    the outcome and stops retrying if it opens mid-loop. ``ok_statuses``
    pass through without raising (e.g. 404 on a manifest probe is an
    answer, not a failure); other non-2xx raise ``requests.HTTPError``,
    classified retryable for 429/5xx only. ``check_status=False`` returns
    whatever arrived (probes that read ``.ok`` themselves).

    Tracing: the whole retried operation runs under one span (retry
    attempts and breaker transitions land on it as events), and the
    span's W3C ``traceparent`` rides the request headers — the server
    side extracts it, so a multi-host pull stitches into one trace.
    """
    pol = policy if policy is not None else RetryPolicy()

    def one_attempt() -> requests.Response:
        r: requests.Response = sender.request(method, url, **kw)
        if check_status and r.status_code not in ok_statuses:
            r.raise_for_status()
        return r

    def run() -> requests.Response:
        return pol.call(one_attempt, what=what or f"{method} {url}",
                        peer=peer, health=health)

    if not trace.active():
        return run()
    with trace.span("http.request", method=method, url=url,
                    peer=peer) as sp:
        kw["headers"] = trace.inject_headers(kw.get("headers"))
        r = run()
        sp.set_attr("status", r.status_code)
        return r
