"""Restore API server — the successor of the legacy Rust generation's axum
control/serving surface (``Cargo.lock:458-474``, SURVEY.md §2.2) and the
north star's "Orbax-compatible ``/restore`` endpoint that JetStream/MaxText
hit instead of GCS" (``BASELINE.json``).

Serves checkpoint-shaped HTTP over the content-addressed store:

- ``GET /restore/models``                    → registered model names
- ``GET /restore/{model}/manifest``          → pytree skeleton: every tensor's
  dtype/shape/nbytes (+ which stored blob holds it)
- ``GET /restore/{model}/tensor/{name}``     → that tensor's raw bytes,
  **Range-aware** so a restoring host fetches exactly its shards' byte
  ranges — the property that makes sharded multi-host restore bandwidth-
  optimal (each byte crosses DCN once).

Tensor-name addressing (rather than file addressing) is what Orbax-style
restores need; actual Orbax checkpoint interop lives in
:mod:`demodel_tpu.restore.orbax_compat`.
"""

from __future__ import annotations

import json
import queue
import re
import threading
from dataclasses import dataclass
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from demodel_tpu.formats import safetensors as st
from demodel_tpu.store import Store
from demodel_tpu.utils import metrics, trace
from demodel_tpu.utils.logging import get_logger
from demodel_tpu.utils.metrics import labeled

log = get_logger("restore")

#: pre-register the /generate HTTP outcome families (house idiom) — the
#: serve plane itself may never be imported on this node, but the scrape
#: should still type the surface
for _code in ("200", "400", "411", "413", "500", "503", "504"):
    metrics.HUB.inc(labeled("gen_http_total", code=_code), 0)


def _gen_engine():
    """Resolve the process-wide generation engine WITHOUT importing the
    serve plane (which imports jax): an engine can only exist if this
    process booted one (``serve.boot``/``serve.load_model``) — a
    dep-light restore node that never serves tokens answers 503 and
    never pays the import. Returns (serve module, engine) or (None,
    None)."""
    import sys

    serve = sys.modules.get("demodel_tpu.serve")
    if serve is None:
        return None, None
    return serve, serve.current()


def _swarm_board(pull_id: str, host_id: str):
    """Resolve a swarm chunk board WITHOUT importing the swarm plane: a
    board can only exist if this process runs a :class:`SwarmScheduler`
    (which imports the placement module) — a dep-light restore node that
    never swarms answers 404 and never pays the import."""
    import sys

    placement = sys.modules.get("demodel_tpu.parallel.placement")
    if placement is None:
        return None
    return placement.board(pull_id, host_id)


@dataclass(frozen=True)
class _TensorLoc:
    key: str      # store key of the safetensors blob
    dtype: str    # safetensors dtype tag
    shape: tuple[int, ...]
    start: int    # absolute offset within the blob
    nbytes: int


class RestoreRegistry:
    """model name → tensor locations, built from stored safetensors blobs."""

    def __init__(self, store: Store):
        self.store = store
        self._models: dict[str, dict[str, _TensorLoc]] = {}
        self._pinned: dict[str, list[str]] = {}  # model → GC-pinned keys
        self._lock = threading.Lock()
        self._native = None  # ProxyServer carrying the C++ data plane
        self._native_port: int | None = None
        self._data_endpoint: str | None = None

    def register_safetensors(self, model: str, keys: list[str]) -> int:
        if not keys:
            raise ValueError(f"model {model}: no safetensors blobs to register")
        tensors: dict[str, _TensorLoc] = {}
        for key in keys:
            index = st.read_index_from(
                lambda off, ln, k=key: self.store.pread(k, ln, off)
            )
            for name, spec in index.tensors.items():
                if name in tensors:
                    raise ValueError(f"duplicate tensor {name} in model {model}")
                tensors[name] = _TensorLoc(
                    key=key, dtype=spec.dtype, shape=spec.shape,
                    start=spec.start, nbytes=spec.nbytes,
                )
        for key in keys:
            # GC must not evict a blob this registry is advertising
            # (ADVICE r3 medium); the native proxy pins its own store
            # instance when the mapping is mirrored below. Pins are
            # refcounted, and a re-registration releases the replaced
            # checkpoint's pins — otherwise every model update would leak
            # a full checkpoint out of the GC cap's reach.
            self.store.pin(key)
        with self._lock:
            old_keys = self._pinned.pop(model, [])
            stale = set(self._models.get(model, ())) - set(tensors)
            self._pinned[model] = list(keys)
            self._models[model] = tensors
            native = self._native
        if native is not None:
            # mirror the mapping into the C++ data plane: tensor bytes then
            # serve from the proxy port via sendfile, GIL-free. New-set
            # entries first (same-name tensors replace atomically under
            # the native lock, pin-new-before-unpin-old), THEN drop only
            # the names absent from the new set — a drop-all-re-add
            # window would briefly 404 live fetches of kept tensors and
            # leave their keys unpinned against a concurrent GC
            # (advisor r4 + reviewer r5)
            for name, loc in tensors.items():
                native.register_tensor(model, name, loc.key, loc.start,
                                       loc.nbytes)
            for name in stale:
                native.unregister_tensor(model, name)
        # Python-handle pins released only after the native mirror holds
        # its own pins on every new-set key: no instant at which a kept
        # blob is pin-free
        for key in old_keys:
            self.store.unpin(key)
        log.info("registered model %s: %d tensors", model, len(tensors))
        return len(tensors)

    def register_report(self, model: str, report) -> int:
        files = report.files if hasattr(report, "files") else report["files"]
        keys = [
            (f.key if hasattr(f, "key") else f["key"])
            for f in files
            if (f.name if hasattr(f, "name") else f["name"]).endswith(".safetensors")
        ]
        return self.register_safetensors(model, keys)

    def attach_native(self, proxy, advertise: str | None = None) -> None:
        """Serve tensor bytes from ``proxy``'s C++ plane (VERDICT r2 weak
        #5: the GIL-bound Python server capped the north-star restore
        path). Existing and future registrations are mirrored; manifests
        advertise the data endpoint so clients fetch bytes there.

        ``advertise`` (or ``DEMODEL_ADVERTISE_HOST``) pins the host name
        remote clients should use. Without it, the endpoint host is derived
        per-request from the manifest request's ``Host`` header (ADVICE r3
        high: advertising ``proxy.url`` handed remote restore clients a
        ``127.0.0.1`` URL — their OWN machine — whenever the proxy bound
        0.0.0.0)."""
        import os

        advertise = advertise or os.environ.get("DEMODEL_ADVERTISE_HOST")
        with self._lock:
            self._native = proxy
            self._native_port = proxy.port
            if advertise:
                if advertise.startswith("["):
                    # bracketed IPv6, maybe with port
                    host = advertise if "]:" in advertise else \
                        f"{advertise}:{proxy.port}"
                elif advertise.count(":") > 1:
                    # bare IPv6 literal: bracket it, then add the port
                    host = f"[{advertise}]:{proxy.port}"
                elif ":" in advertise:
                    host = advertise  # host:port already
                else:
                    host = f"{advertise}:{proxy.port}"
                self._data_endpoint = f"http://{host}"
            elif proxy.cfg.host not in ("0.0.0.0", ""):
                # explicit bind address: externally meaningful, advertise it
                self._data_endpoint = proxy.url
            else:
                # wildcard bind: no single routable name exists — leave the
                # static endpoint unset and derive per-request (manifest())
                self._data_endpoint = None
            models = {m: dict(t) for m, t in self._models.items()}
        for model, tensors in models.items():
            for name, loc in tensors.items():
                proxy.register_tensor(model, name, loc.key, loc.start,
                                      loc.nbytes)

    def models(self) -> list[str]:
        with self._lock:
            return sorted(self._models)

    def unregister(self, model: str) -> bool:
        """Full teardown of a model: drop it from the registry AND the
        native data plane, releasing every pin so GC can reclaim the
        checkpoint. Returns False when the model wasn't registered."""
        with self._lock:
            if model not in self._models:
                return False
            del self._models[model]
            old_keys = self._pinned.pop(model, [])
            native = self._native
        if native is not None:
            native.unregister_model(model)
        for key in old_keys:
            self.store.unpin(key)
        log.info("unregistered model %s", model)
        return True

    def put_safetensors(self, model: str, src, length: int) -> int:
        """Commit a pushed safetensors blob (``src``: readable stream of
        ``length`` bytes) into the store and register it for restore — the
        server half of the network-Orbax *save* path. Returns the tensor
        count. A re-push replaces the previous registration."""
        from demodel_tpu.store import key_for_uri

        key = key_for_uri(f"demodel://restore/{model}/pushed")
        if self.store.has(key):
            self.store.remove(key)
        w = self.store.begin(key)
        try:
            remaining = length
            while remaining > 0:
                chunk = src.read(min(1 << 20, remaining))
                if not chunk:
                    raise ValueError(f"body truncated at {length - remaining}"
                                     f"/{length} bytes")
                w.append(chunk)
                remaining -= len(chunk)
            w.commit({"kind": "pushed-checkpoint", "model": model,
                      "size": length})
        except BaseException:
            if w._open:  # noqa: SLF001 — writer state check
                w.abort(keep_partial=False)
            raise
        try:
            return self.register_safetensors(model, [key])
        except Exception:
            # an unparsable blob must not stay registered or cached
            self.store.remove(key)
            raise

    # -- streamed per-tensor push (VERDICT r3 #7) ----------------------

    @staticmethod
    def _tensor_blob_key(digest: str) -> str:
        from demodel_tpu.store import key_for_uri

        return key_for_uri(f"demodel://restore/tensor/{digest}")

    def has_tensor_blob(self, digest: str) -> bool:
        """True when a pushed single-tensor blob with this content digest
        is already stored — the dedup probe of the streamed save: an
        unchanged tensor is never re-transferred or re-stored."""
        return self.store.has(self._tensor_blob_key(digest))

    def put_tensor_blob(self, digest: str, src, length: int) -> None:
        """Commit one single-tensor safetensors blob under its content
        address. Streamed in 1 MB chunks (server RAM is O(1)); the store's
        rolling sha256 must match ``digest`` or the push is rejected."""
        if not (len(digest) == 64
                and all(c in "0123456789abcdef" for c in digest)):
            raise ValueError("digest must be 64 hex chars")
        key = self._tensor_blob_key(digest)
        if self.store.has(key):
            # content-addressed: same digest == same bytes; drain the body
            # so the connection stays usable, then no-op
            remaining = length
            while remaining > 0:
                chunk = src.read(min(1 << 20, remaining))
                if not chunk:
                    break
                remaining -= len(chunk)
            return
        w = self.store.begin(key)
        try:
            remaining = length
            while remaining > 0:
                chunk = src.read(min(1 << 20, remaining))
                if not chunk:
                    raise ValueError(f"body truncated at {length - remaining}"
                                     f"/{length} bytes")
                w.append(chunk)
                remaining -= len(chunk)
            got = w.digest()
            if got != digest:
                w.abort(keep_partial=False)
                raise ValueError(f"blob digest mismatch: got {got}")
            w.commit({"kind": "pushed-tensor", "sha256": digest,
                      "size": length})
        except BaseException:
            if w._open:  # noqa: SLF001 — writer state check
                w.abort(keep_partial=False)
            raise

    def commit_push(self, model: str, digests: list[str]) -> int:
        """Register ``model`` from previously pushed per-tensor blobs.
        Returns the tensor count; unknown digests raise before any
        registration changes."""
        keys = []
        for d in digests:
            key = self._tensor_blob_key(d)
            if not self.store.has(key):
                raise ValueError(f"no pushed tensor blob for digest {d[:12]}")
            keys.append(key)
        return self.register_safetensors(model, keys)

    def _lazy_resolve(self, model: str) -> bool:
        """Register ``model`` from a pull-manifest record in the store
        (written by :func:`demodel_tpu.delivery.pull`), if one exists."""
        import json as _json

        from demodel_tpu.delivery import manifest_key

        for source in ("hf", "ollama"):
            mkey = manifest_key(source, model)
            if not self.store.has(mkey):
                continue
            try:
                record = _json.loads(self.store.get(mkey).decode())
                self.register_report(model, record)
                return True
            except (ValueError, KeyError) as e:
                log.warning("manifest record for %s unusable: %s", model, e)
        return False

    def manifest(self, model: str, request_host: str | None = None) -> dict | None:
        """``request_host``: the manifest request's ``Host`` header. When the
        native plane is attached on a wildcard bind, the data endpoint is
        the host the CLIENT reached us by, with the native port swapped in —
        the only name known to be routable from that client."""
        with self._lock:
            tensors = self._models.get(model)
        if tensors is None and self._lazy_resolve(model):
            with self._lock:
                tensors = self._models.get(model)
        if tensors is None:
            return None
        out = {
            "model": model,
            "format": "safetensors-ranges",
            "tensors": {
                name: {"dtype": t.dtype, "shape": list(t.shape), "nbytes": t.nbytes}
                for name, t in tensors.items()
            },
        }
        # bytes live on the native plane; this server stays control-only
        if self._data_endpoint:
            out["data_endpoint"] = self._data_endpoint
        elif self._native_port is not None and request_host:
            host = request_host.rsplit(":", 1)[0] if not request_host.startswith("[") \
                else request_host.rpartition("]")[0] + "]"
            out["data_endpoint"] = f"http://{host}:{self._native_port}"
        return out

    def locate(self, model: str, tensor: str) -> _TensorLoc | None:
        with self._lock:
            loc = self._models.get(model, {}).get(tensor)
        if loc is None and model not in self.models() and self._lazy_resolve(model):
            with self._lock:
                loc = self._models.get(model, {}).get(tensor)
        return loc


def make_handler(registry: RestoreRegistry, proxy=None):
    class RestoreHandler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def _send(self, status, body: bytes, ctype="application/json", extra=None):
            self.send_response(status)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            for k, v in (extra or {}).items():
                self.send_header(k, v)
            self.end_headers()
            if self.command != "HEAD":
                self.wfile.write(body)

        def do_HEAD(self):
            self.do_GET()

        def _traced(self, fn):
            """Run one request handler under a server-side span, parented
            on the client's W3C ``traceparent`` header when present — the
            server half of the cross-host trace stitch. No-op (a shared
            noop span, zero allocation) when tracing is disabled."""
            with trace.span("serve.restore",
                            remote_parent=self.headers.get("traceparent"),
                            method=self.command, path=self.path):
                return fn()

        def _content_length(self) -> int:
            try:
                return int(self.headers.get("Content-Length", "0"))
            except ValueError:
                return 0

        def do_PUT(self):
            self._traced(self._put)

        def _put(self):
            # push surfaces for the network-Orbax save path:
            #   /restore/{model}/safetensors — one whole-checkpoint blob
            #   /restore/blob/{digest}       — one single-tensor blob,
            #     content-addressed (streamed save; VERDICT r3 #7)
            m = re.match(r"^/restore/blob/([0-9a-f]{64})$", self.path)
            if m:
                length = self._content_length()
                if length <= 0:
                    self._send(411, b'{"error":"Content-Length required"}')
                    return
                try:
                    registry.put_tensor_blob(m.group(1), self.rfile, length)
                except Exception as e:  # noqa: BLE001 — bad blob → client error
                    self._send(400, json.dumps({"error": str(e)}).encode())
                    return
                metrics.HUB.inc("restore_put_bytes_total", length)
                self._send(200, b'{"ok":true}')
                return
            m = re.match(r"^/restore/(.+)/safetensors$", self.path)
            if m is None:
                self._send(404, b'{"error":"not found"}')
                return
            model = m.group(1)
            length = self._content_length()
            if length <= 0:
                self._send(411, b'{"error":"Content-Length required"}')
                return
            try:
                n = registry.put_safetensors(model, self.rfile, length)
            except Exception as e:  # noqa: BLE001 — bad blob → client error
                self._send(400, json.dumps({"error": str(e)}).encode())
                return
            metrics.HUB.inc("restore_put_total")
            metrics.HUB.inc("restore_put_bytes_total", length)
            self._send(200, json.dumps({"model": model, "tensors": n}).encode())

        def do_POST(self):
            self._traced(self._post)

        def _post(self):
            if self.path == "/generate":
                self._generate()
                return
            # finalize a streamed save: the ordered digest list becomes the
            # model registration (every blob must already be pushed)
            m = re.match(r"^/restore/(.+)/commit$", self.path)
            if m is None:
                self._send(404, b'{"error":"not found"}')
                return
            length = self._content_length()
            if not 0 < length <= (16 << 20):
                self._send(411, b'{"error":"Content-Length required"}')
                return
            try:
                body = json.loads(self.rfile.read(length))
                digests = body["digests"]
                if not isinstance(digests, list) or not digests:
                    raise ValueError("digests must be a non-empty list")
                n = registry.commit_push(m.group(1), digests)
            except Exception as e:  # noqa: BLE001 — bad commit → client error
                self._send(400, json.dumps({"error": str(e)}).encode())
                return
            metrics.HUB.inc("restore_put_total")
            self._send(200, json.dumps({"model": m.group(1),
                                        "tensors": n}).encode())

        def _generate(self):  # noqa: C901
            # the token-serving surface: tokens-in, tokens-out against
            # the process-wide continuous-batching engine. Dep-light:
            # no engine booted → 503, the jax import never happens here.
            serve, engine = _gen_engine()
            if engine is None:
                metrics.HUB.inc(labeled("gen_http_total", code="503"))
                self._send(503, b'{"error":"serving disabled '
                                b'(no engine booted)"}')
                return
            length = self._content_length()
            if length <= 0:
                metrics.HUB.inc(labeled("gen_http_total", code="411"))
                self._send(411, b'{"error":"Content-Length required"}')
                return
            if length > (8 << 20):
                metrics.HUB.inc(labeled("gen_http_total", code="413"))
                self._send(413, b'{"error":"body exceeds 8 MiB limit"}')
                return
            try:
                # the handler's own share of a request: body read, parse
                # and validation, up to the hand-off to the engine
                with trace.span("serve.http-parse", bytes=length):
                    body = json.loads(self.rfile.read(length))
                    prompt = body["prompt"]
                    if not isinstance(prompt, list) or not prompt:
                        raise ValueError("prompt must be a non-empty "
                                         "list of token ids")
                    max_new = int(body.get("max_new_tokens", 16))
                    stream = bool(body.get("stream", False))
                    timeout = float(body.get("timeout", 300.0))
            except Exception as e:  # noqa: BLE001 — bad body → client error
                metrics.HUB.inc(labeled("gen_http_total", code="400"))
                self._send(400, json.dumps({"error": str(e)}).encode())
                return
            try:
                req = engine.submit(prompt, max_new)
            except serve.QueueOverflow as e:
                # the proxy plane's admission contract: loud rejection
                # with a backoff hint, never a silent drop
                metrics.HUB.inc(labeled("gen_http_total", code="503"))
                self._send(503, json.dumps({
                    "error": str(e),
                    "retry_after": e.retry_after}).encode(),
                    extra={"Retry-After": str(e.retry_after)})
                return
            except (ValueError, RuntimeError) as e:
                metrics.HUB.inc(labeled("gen_http_total", code="400"))
                self._send(400, json.dumps({"error": str(e)}).encode())
                return
            if not stream:
                try:
                    toks = req.result(timeout=timeout)
                except TimeoutError:
                    req.cancel()
                    metrics.HUB.inc(labeled("gen_http_total", code="504"))
                    self._send(504, b'{"error":"generation timed out"}')
                    return
                except RuntimeError as e:
                    metrics.HUB.inc(labeled("gen_http_total", code="500"))
                    self._send(500,
                               json.dumps({"error": str(e)}).encode())
                    return
                metrics.HUB.inc(labeled("gen_http_total", code="200"))
                self._send(200, json.dumps({
                    "id": req.id, "tokens": toks,
                    "prompt_tokens": len(req.prompt),
                    "queue_ms": round(
                        ((req.started_s or req.submitted_s)
                         - req.submitted_s) * 1e3, 3),
                    "total_ms": round(
                        ((req.finished_s or req.submitted_s)
                         - req.submitted_s) * 1e3, 3)}).encode())
                return
            # streaming: chunked NDJSON — one {"token": id} line as each
            # token decodes, then a {"done": true} summary line
            metrics.HUB.inc(labeled("gen_http_total", code="200"))
            self.send_response(200)
            self.send_header("Content-Type", "application/x-ndjson")
            self.send_header("Transfer-Encoding", "chunked")
            self.end_headers()

            def _chunk(obj) -> None:
                data = (json.dumps(obj) + "\n").encode()
                self.wfile.write(f"{len(data):x}\r\n".encode()
                                 + data + b"\r\n")

            try:
                for tok in req.iter_tokens(timeout=timeout):
                    _chunk({"token": tok})
                _chunk({"done": True, "id": req.id, "tokens": req.tokens})
            except RuntimeError as e:
                _chunk({"error": str(e)})
            except (queue.Empty, BrokenPipeError, ConnectionResetError):
                # consumer gone or stream stalled: evict the sequence so
                # its blocks free now instead of decoding to a dead pipe
                req.cancel()
                return
            self.wfile.write(b"0\r\n\r\n")

        def do_GET(self):  # noqa: C901
            self._traced(self._get)

        def _get(self):  # noqa: C901
            if self.path == "/metrics":
                # Prometheus exposition: hub counters + native proxy
                # counters + store gauges (SURVEY.md §5 — the reference
                # has no metrics endpoint at all)
                body = metrics.render(proxy=proxy, store=registry.store).encode()
                self._send(200, body, ctype="text/plain; version=0.0.4")
                return
            if self.path.startswith("/debug/telemetry/history"):
                # the durable tier: per-family series reconstructed from
                # the on-disk archive, spanning restarts. Same dep-light
                # stance as the swarm board: an archive can only exist if
                # retention was started (DEMODEL_TELEMETRY_ARCHIVE), so
                # peek sys.modules instead of importing the module
                import sys as _sys
                from urllib.parse import parse_qs, urlsplit

                retention = _sys.modules.get("demodel_tpu.utils.retention")
                archive = retention.current() if retention is not None \
                    else None
                if archive is None:
                    self._send(404, b'{"error":"no telemetry archive '
                                    b'(set DEMODEL_TELEMETRY_ARCHIVE)"}')
                    return
                q = parse_qs(urlsplit(self.path).query)

                def _qs(key):
                    v = q.get(key, [None])[0]
                    return v if v else None

                def _qf(key):
                    v = _qs(key)
                    try:
                        return float(v) if v is not None else None
                    except ValueError:
                        return None

                # pick up windows the background flusher hasn't reached
                # yet, so history is current up to this very poll
                archive.flush_once()
                doc = archive.history(  # demodel: allow(metric-hygiene) — the family comes from the query string; an unknown family is an empty (not wrong) series, which is this endpoint's contract
                    family=_qs("family"), label=_qs("label"),
                    since=_qf("since"), until=_qf("until"))
                doc["server"] = "restore"
                self._send(200, json.dumps(doc, default=str).encode())
                return
            if self.path == "/debug/telemetry":
                # the time-series view: 30 s / 5 min sliding-window rates
                # and delta-bucket quantiles over the Python hub, plus the
                # native proxy's scrape-diffed mirror when one is attached
                doc = metrics.telemetry_doc(proxy=proxy)
                doc["server"] = "restore"
                self._send(200, json.dumps(doc, default=str).encode())
                return
            if self.path.startswith("/debug/profile"):
                # the continuous profiler: ?seconds= captures a windowed
                # diff of the always-on aggregate (0 = cumulative), ?hz=
                # temporarily raises the rate, ?format=collapsed|json.
                # utils.profiler is stdlib-only, so a direct import keeps
                # the node dep-light; DEMODEL_OBS=0 → 503 (tier is off).
                from urllib.parse import parse_qs, urlsplit

                from demodel_tpu.utils import profiler

                q = parse_qs(urlsplit(self.path).query)

                def _qp(key, default, cast):
                    v = q.get(key, [None])[0]
                    try:
                        return cast(v) if v else default
                    except ValueError:
                        return default

                seconds = _qp("seconds", 1.0, float)
                hz = _qp("hz", 0, int)
                fmt = _qp("format", "json", str)
                prof = profiler.capture(seconds=seconds, hz=hz)
                if prof is None:
                    self._send(503, b'{"error":"profiler disabled '
                                    b'(DEMODEL_OBS=0)"}')
                    return
                prof["server"] = "restore"
                if fmt == "collapsed":
                    self._send(200, profiler.collapse(prof).encode(),
                               ctype="text/plain; charset=utf-8")
                else:
                    self._send(200,
                               json.dumps(prof, default=str).encode())
                return
            if self.path == "/debug/statusz":
                # live introspection: open breakers, budget charge,
                # in-flight span tree, flight-recorder state — "what is
                # this node doing right now", from curl
                from demodel_tpu.utils import statusz

                doc = statusz.snapshot(extra={
                    "server": "restore",
                    "models": registry.models(),
                })
                self._send(200, json.dumps(doc, default=str).encode())
                return
            if self.path == "/restore/models":
                self._send(200, json.dumps({"models": registry.models()}).encode())
                return
            m = re.match(r"^/swarm/([^/]+)/([^/]+)/chunks$", self.path)
            if m:
                board = _swarm_board(m.group(1), m.group(2))
                if board is None:
                    self._send(404, b'{"error":"no such swarm board"}')
                    return
                self._send(200, json.dumps(board.summary()).encode())
                return
            m = re.match(r"^/swarm/([^/]+)/([^/]+)/chunk/([^/]+)/(\d+)$",
                         self.path)
            if m:
                board = _swarm_board(m.group(1), m.group(2))
                data = board.get(m.group(3), int(m.group(4))) \
                    if board is not None else None
                if data is None:
                    self._send(404, b'{"error":"chunk not held"}')
                    return
                metrics.HUB.inc("swarm_chunks_served_total")
                metrics.HUB.inc("swarm_bytes_served_total", len(data))
                self._send(200, data, ctype="application/octet-stream")
                return
            m = re.match(r"^/restore/blob/([0-9a-f]{64})$", self.path)
            if m:
                # dedup probe of the streamed save: 200 = skip the upload
                if registry.has_tensor_blob(m.group(1)):
                    self._send(200, b'{"present":true}')
                else:
                    self._send(404, b'{"present":false}')
                return
            m = re.match(r"^/restore/(.+)/manifest$", self.path)
            if m:
                manifest = registry.manifest(
                    m.group(1), request_host=self.headers.get("Host"))
                if manifest is None:
                    self._send(404, b'{"error":"model not registered"}')
                    return
                self._send(200, json.dumps(manifest).encode())
                return
            m = re.match(r"^/restore/(.+)/tensor/(.+)$", self.path)
            if m:
                loc = registry.locate(m.group(1), m.group(2))
                if loc is None:
                    self._send(404, b'{"error":"no such tensor"}')
                    return
                off, length, status = 0, loc.nbytes, 200
                extra = {"Accept-Ranges": "bytes"}
                rng = self.headers.get("Range")
                if rng and rng.startswith("bytes="):
                    # RFC 9110 §14.2: an unparsable Range is ignored; a
                    # parsable-but-unsatisfiable one (past-end start,
                    # reversed, zero suffix) gets 416
                    try:
                        a, _, b = rng[6:].partition("-")
                        if a:
                            off = int(a)
                            end = int(b) if b else loc.nbytes - 1
                        else:
                            n = int(b)
                            if n <= 0:
                                self._send(416, b"")
                                return
                            off = max(0, loc.nbytes - n)
                            end = loc.nbytes - 1
                    except ValueError:
                        off, end = 0, loc.nbytes - 1
                    else:
                        if off >= loc.nbytes or end < off:
                            self._send(416, b"")
                            return
                        end = min(end, loc.nbytes - 1)
                        status = 206
                        extra["Content-Range"] = f"bytes {off}-{end}/{loc.nbytes}"
                    length = end - off + 1
                body = registry.store.pread(loc.key, length, loc.start + off)
                metrics.HUB.inc("restore_tensor_requests_total")
                metrics.HUB.inc("restore_bytes_total", len(body))
                self._send(status, body, ctype="application/octet-stream", extra=extra)
                return
            self._send(404, b'{"error":"not found"}')

    return RestoreHandler


class _Listener(ThreadingHTTPServer):
    #: socketserver's default backlog is 5; a gateway's sessions connect
    #: together (32 in one wave of the benchmark's warm-up) and one of
    #: them was reset by the peer (chip run, PR 29)
    request_queue_size = 128


class RestoreServer:
    """Threaded HTTP server over a RestoreRegistry. ``proxy`` (optional)
    adds the native data-plane counters to ``/metrics``."""

    def __init__(self, registry: RestoreRegistry, host: str = "0.0.0.0",
                 port: int = 0, proxy=None):
        self.registry = registry
        self._proxy = proxy
        self.httpd = _Listener((host, port), make_handler(registry, proxy))
        self.port = self.httpd.server_address[1]
        self._thread = threading.Thread(target=self.httpd.serve_forever, daemon=True)

    def start(self) -> "RestoreServer":
        self._thread.start()
        # durable telemetry rides the serving node: only when the archive
        # knob is set does the retention module get imported/started at
        # all — unset leaves this path byte-identical to a tree without it
        from demodel_tpu.utils.env import telemetry_archive_dir

        if telemetry_archive_dir():
            from demodel_tpu.utils import retention

            retention.ensure(proxy=self._proxy)
        # the continuous profiler is always-on at the observe tier (a
        # serving node must be profilable from curl without a restart);
        # DEMODEL_OBS=0 makes this a no-op — no thread ever starts
        from demodel_tpu.utils import profiler

        profiler.ensure()
        # background scrubber: same opt-in stance as retention — only a
        # node with DEMODEL_SCRUB_INTERVAL_SECS set pays the import or
        # the thread; off (the default) leaves this path inert
        from demodel_tpu.utils.env import scrub_interval_secs

        if scrub_interval_secs() > 0:
            from demodel_tpu import scrub

            scrub.ensure(self.registry.store)
        log.info("restore API listening on :%d", self.port)
        return self

    def stop(self) -> None:
        import sys

        scrub = sys.modules.get("demodel_tpu.scrub")
        if scrub is not None:
            scrub.stop_all()
        self.httpd.shutdown()
        self.httpd.server_close()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
