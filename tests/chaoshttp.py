"""Deterministic fault-injection HTTP shim for the wire plane.

A :class:`ChaosPeer` sits in front of a REAL peer (a warm no-MITM
``ProxyServer`` or a restore node) and forwards every GET — Range headers
included — while injecting faults per a seeded :class:`FaultPlan`:

- ``reset-at-byte``: serve N body bytes, then kill the socket with an RST
  (``SO_LINGER 0``) — the sharpest mid-window failure shape;
- ``stall``: sit on the request past the client's read deadline, then
  drop the connection (the hung-but-accepting-peer shape);
- ``503-burst``: answer ``503 Retry-After: 0`` for the next K matching
  requests (the bounded-pool overflow shape);
- ``truncate``: promise the full Content-Length, deliver N bytes, close
  cleanly (FIN) — a short body the client must detect and resume;
- ``corrupt``: flip a byte and serve the full (wrong) body — digests must
  catch it downstream; the wire itself looks healthy.
- ``die``: the whole peer goes dark — the matching request gets an RST
  and EVERY later request does too (the mid-pull host-death shape the
  swarm's ownership-succession recovery is built for).

``ChaosPeer(throttle_bps=...)`` rate-limits body writes — the
constrained-origin-link shape the swarm bench uses to make "aggregate
origin bytes" the measurable bottleneck on localhost.

Faults are consumed deterministically (first matching spec, declared
order, ``times`` each); ``plan.injected`` records what actually fired so
tests can assert the fault really happened. Randomized byte positions
(``at_byte=-1``) come from the plan's seeded RNG — replayable runs.
"""

from __future__ import annotations

import socket
import struct
import threading
import time
from dataclasses import dataclass, field, replace
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from random import Random

import requests

from demodel_tpu.utils import trace

KINDS = ("reset-at-byte", "stall", "503-burst", "truncate", "corrupt",
         "die")


#: faults applied before any upstream forwarding (no body involved)
PRE_KINDS = ("503-burst", "stall", "die")


@dataclass
class FaultSpec:
    kind: str
    #: substring the request path must contain ("" matches every request)
    path: str = ""
    #: how many matching requests this spec poisons before going inert
    times: int = 1
    #: body position for reset/truncate/corrupt; -1 = seeded-random
    at_byte: int = -1
    #: how long a "stall" sits before dropping the connection
    stall_secs: float = 5.0
    #: body faults only fire on responses at least this large — lets a
    #: mid-window fault skip the tiny header reads that share the path
    min_body: int = 0

    def __post_init__(self) -> None:
        if self.kind not in KINDS:
            raise ValueError(f"unknown fault kind {self.kind!r}")


@dataclass
class Injection:
    """One fault that actually fired (the proof side of the harness)."""

    kind: str
    path: str
    at_byte: int = -1


class FaultPlan:
    """Thread-safe, seeded, deterministic fault source."""

    def __init__(self, *specs: FaultSpec, seed: int = 0):
        self._specs = [replace(s) for s in specs]  # private mutable copies
        self._rng = Random(seed)
        self._lock = threading.Lock()
        self.injected: list[Injection] = []

    def take(self, path: str, body_len: int | None = None) -> FaultSpec | None:
        """Consume the first matching live spec for this request.
        ``body_len=None`` is the pre-forward phase (503/stall only);
        with a length, body-phase faults (reset/truncate/corrupt) match,
        gated on ``min_body``."""
        with self._lock:
            for s in self._specs:
                if s.times <= 0 or (s.path and s.path not in path):
                    continue
                if body_len is None:
                    if s.kind not in PRE_KINDS:
                        continue
                else:
                    if s.kind in PRE_KINDS or body_len < s.min_body:
                        continue
                s.times -= 1
                return s
        return None

    def position(self, spec: FaultSpec, body_len: int) -> int:
        if spec.at_byte >= 0:
            return min(spec.at_byte, max(0, body_len - 1))
        with self._lock:
            return self._rng.randrange(body_len) if body_len else 0

    def record(self, kind: str, path: str, at_byte: int = -1) -> None:
        with self._lock:
            self.injected.append(Injection(kind, path, at_byte))

    def fired(self, kind: str) -> int:
        with self._lock:
            return sum(1 for i in self.injected if i.kind == kind)

    def exhausted(self) -> bool:
        with self._lock:
            return all(s.times == 0 for s in self._specs)


class _QuietThreadingHTTPServer(ThreadingHTTPServer):
    daemon_threads = True

    def handle_error(self, request, client_address):  # noqa: ARG002
        # forced RSTs make the handler machinery raise on its own socket;
        # that noise is the POINT of this server
        pass


class ChaosPeer:
    """The in-process shim. ``url`` is what the system under test dials;
    everything forwards to ``upstream`` (a real peer) minus the injected
    faults. Counts ``bytes_served`` (body bytes actually written) so tests
    can cross-check window-resume accounting from the wire side."""

    def __init__(self, upstream: str, plan: FaultPlan,
                 forward_timeout: float = 30.0,
                 throttle_bps: int | None = None):
        self.upstream = upstream.rstrip("/")
        self.plan = plan
        self.forward_timeout = forward_timeout
        #: body bytes/sec cap per connection (None = line rate): the
        #: constrained-origin-link simulation for the swarm bench
        self.throttle_bps = throttle_bps
        self.dead = False  # a fired "die" fault (or kill()) sticks
        self.bytes_served = 0
        #: every request seen: (path, Range header or "") — lets tests
        #: prove a recovery resumed at the received offset instead of
        #: redoing the window/file from zero
        self.requests_log: list[tuple[str, str]] = []
        self._count_lock = threading.Lock()
        self._stop = threading.Event()
        outer = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):  # noqa: ARG002
                pass

            def do_GET(self):
                outer._serve(self)

            def finish(self):
                try:
                    super().finish()
                except (OSError, ValueError):
                    pass  # we already killed the socket on purpose

        self._srv = _QuietThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.url = f"http://127.0.0.1:{self._srv.server_address[1]}"
        self._thread = threading.Thread(target=self._srv.serve_forever,
                                        daemon=True)
        self._thread.start()

    # -- lifecycle -------------------------------------------------------
    def close(self) -> None:
        self._stop.set()
        self._srv.shutdown()
        self._srv.server_close()

    def kill(self) -> None:
        """Deterministic mid-test host death: every request from now on
        is RST — the direct-control twin of the ``die`` fault kind."""
        self.dead = True

    def __enter__(self) -> "ChaosPeer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- request handling ------------------------------------------------
    def _count(self, n: int) -> None:
        with self._count_lock:
            self.bytes_served += n

    def _write_body(self, h: BaseHTTPRequestHandler, body: bytes) -> None:
        """Body write, rate-limited to ``throttle_bps`` when set (64 KB
        slices + sleeps — coarse, but the aggregate rate is what the
        bench's origin-link simulation needs)."""
        if not self.throttle_bps:
            h.wfile.write(body)
            return
        slice_bytes = 64 << 10
        t0 = time.monotonic()
        sent = 0
        while sent < len(body) and not self._stop.is_set():
            h.wfile.write(body[sent:sent + slice_bytes])
            sent += slice_bytes
            ahead = sent / self.throttle_bps - (time.monotonic() - t0)
            if ahead > 0:
                time.sleep(ahead)

    def _rst(self, h: BaseHTTPRequestHandler) -> None:
        """Kill the client socket with an RST, not a FIN.

        The rfile/wfile wrappers hold ``_io_refs`` on the socket, so a
        bare ``connection.close()`` only *defers* the OS close (no RST
        ever reaches the client — it blocks until its read timeout).
        Close the wrappers first so the linger-0 close really fires."""
        h.close_connection = True
        try:
            h.connection.setsockopt(
                socket.SOL_SOCKET, socket.SO_LINGER,
                struct.pack("ii", 1, 0))
        except OSError:
            pass
        for f in (h.wfile, h.rfile):
            try:
                f.close()
            except (OSError, ValueError):
                pass
        try:
            h.connection.close()
        except OSError:
            pass

    def _serve(self, h: BaseHTTPRequestHandler) -> None:
        # the PEER half of the trace stitch: extract the client's W3C
        # traceparent and serve under a child span, so a traced chaos
        # pull shows client window-reads and the peer-side serves (and
        # which got faulted) in ONE trace
        with trace.span("serve.peer",
                        remote_parent=h.headers.get("traceparent"),
                        path=h.path,
                        range=h.headers.get("Range", "")) as sp:
            self._serve_traced(h, sp)

    def _serve_traced(self, h: BaseHTTPRequestHandler, sp) -> None:
        with self._count_lock:
            self.requests_log.append((h.path, h.headers.get("Range", "")))
        if self.dead:
            sp.event("fault", kind="dead-host")
            self._rst(h)
            return
        fault = self.plan.take(h.path)

        if fault is not None and fault.kind == "die":
            self.plan.record("die", h.path)
            sp.event("fault", kind="die")
            self.dead = True
            self._rst(h)
            return

        if fault is not None and fault.kind == "503-burst":
            self.plan.record("503-burst", h.path)
            sp.event("fault", kind="503-burst")
            body = b"chaos: injected 503"
            h.send_response(503)
            h.send_header("Retry-After", "0")
            h.send_header("Content-Length", str(len(body)))
            h.end_headers()
            h.wfile.write(body)
            return

        if fault is not None and fault.kind == "stall":
            self.plan.record("stall", h.path)
            sp.event("fault", kind="stall")
            deadline = time.monotonic() + fault.stall_secs
            while time.monotonic() < deadline and not self._stop.is_set():
                time.sleep(0.05)
            # the client's read timeout fired long ago; drop what's left
            self._rst(h)
            return

        # Connection: close — the upstream's bounded session pool holds a
        # worker for a connection's whole keep-alive lifetime; a shim that
        # leaves its forwards idling would exhaust the pool and turn every
        # later forward into a queue wait (observed as 30 s stalls)
        headers = {"Connection": "close"}
        if "Range" in h.headers:
            headers["Range"] = h.headers["Range"]
        if "traceparent" in h.headers:
            # keep the stitch intact through the forward leg too
            headers["traceparent"] = h.headers["traceparent"]
        try:
            # fresh request per call: handler threads run concurrently
            # (multi-stream window reads) and Session isn't thread-safe
            r = requests.get(f"{self.upstream}{h.path}", headers=headers,
                             timeout=self.forward_timeout)
        except requests.RequestException:
            self._rst(h)
            return
        body = r.content

        h.send_response(r.status_code)
        for name in ("Content-Range", "Accept-Ranges", "Content-Type",
                     "ETag"):
            if name in r.headers:
                h.send_header(name, r.headers[name])
        h.send_header("Content-Length", str(len(body)))

        if body and r.status_code < 400:
            fault = self.plan.take(h.path, body_len=len(body))
        else:
            fault = None
        if fault is None:
            h.end_headers()
            self._write_body(h, body)
            self._count(len(body))
            return

        pos = self.plan.position(fault, len(body))
        if fault.kind == "corrupt":
            self.plan.record("corrupt", h.path, pos)
            sp.event("fault", kind="corrupt", at_byte=pos)
            mutated = bytearray(body)
            mutated[pos] ^= 0xFF
            h.end_headers()
            h.wfile.write(bytes(mutated))
            self._count(len(mutated))
            return
        if fault.kind == "reset-at-byte":
            self.plan.record("reset-at-byte", h.path, pos)
            sp.event("fault", kind="reset-at-byte", at_byte=pos)
            h.end_headers()
            h.wfile.write(body[:pos])
            h.wfile.flush()
            self._count(pos)
            self._rst(h)
            return
        # truncate: full Content-Length promised, fewer bytes delivered,
        # clean FIN — the client must detect the short body and resume
        self.plan.record("truncate", h.path, pos)
        sp.event("fault", kind="truncate", at_byte=pos)
        h.close_connection = True
        h.end_headers()
        h.wfile.write(body[:pos])
        self._count(pos)
