"""A Hugging Face hub on loopback, serving one seeded checkpoint.

The only way to reach ``serve.load_model`` with no network. It speaks the
part of the hub's protocol the program's registry client uses: the
revision listing, ``/resolve`` (LFS-style 302 to a CDN path carrying
``X-Linked-Etag``/``X-Linked-Size`` for weight shards, a direct 200 for
small files) and a CDN path that honours ``Range``. A copy, cut to that,
of ``tests/fake_registries.make_hf_handler`` (tests may change; the
yardstick may not), with one difference: a shard is a
:class:`checkpoint.VirtualFile` streamed as it is generated.

Imports nothing of the program under test.
"""

from __future__ import annotations

import contextlib
import json
import re
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

COMMIT = "c0ffee" * 6 + "c0ff"


def _make_handler(repo_id: str, files: dict, digests: dict[str, str]):
    by_digest = {sha: fn for fn, sha in digests.items()}

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def _head(self, status: int, length: int, ctype: str,
                  extra: dict | None = None) -> None:
            self.send_response(status)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(length))
            for k, v in (extra or {}).items():
                self.send_header(k, v)
            self.end_headers()

        def _send(self, status: int, body: bytes,
                  ctype: str = "application/json",
                  extra: dict | None = None) -> None:
            self._head(status, len(body), ctype, extra)
            if self.command != "HEAD":
                self.wfile.write(body)

        def _send_range(self, body, sha: str) -> None:
            """``body`` whole, or the part a ``Range`` header names."""
            size = len(body)
            start, end, status = 0, size, 200
            extra = {"ETag": f'"{sha}"', "Accept-Ranges": "bytes"}
            rng = self.headers.get("Range")
            if rng and rng.startswith("bytes="):
                s, _, e = rng[6:].partition("-")
                start = int(s)
                end = min(int(e) + 1 if e else size, size)
                status = 206
                extra["Content-Range"] = f"bytes {start}-{end - 1}/{size}"
            self._head(status, end - start, "application/octet-stream", extra)
            if self.command == "HEAD":
                return
            if isinstance(body, bytes):
                self.wfile.write(body[start:end])
            else:
                for part in body.read(start, end):
                    self.wfile.write(part)

        def do_HEAD(self):
            self.do_GET()

        def do_GET(self):
            path = self.path.split("?", 1)[0]
            m = re.match(r"^/api/models/(.+?)/revision/([^/]+)$", path)
            if m:
                if m.group(1) != repo_id:
                    self._send(404, b'{"error":"RepoNotFound"}')
                    return
                self._send(200, json.dumps({
                    "sha": COMMIT, "id": repo_id,
                    "siblings": [{"rfilename": f} for f in sorted(files)],
                }).encode())
                return
            m = re.match(r"^/(.+?)/resolve/([^/]+)/(.+)$", path)
            if m:
                body = files.get(m.group(3)) if m.group(1) == repo_id else None
                if body is None:
                    self._send(404, b'{"error":"EntryNotFound"}')
                    return
                sha = digests[m.group(3)]
                if m.group(3).endswith(".safetensors"):
                    host = self.headers.get("Host", "127.0.0.1")
                    self._send(302, b"", extra={
                        "Location": f"http://{host}/cdn/{repo_id}/{sha}",
                        "X-Linked-Etag": f'"{sha}"',
                        "X-Linked-Size": str(len(body)),
                        "X-Repo-Commit": COMMIT,
                        "Accept-Ranges": "bytes"})
                else:
                    self._send(200, body, ctype="application/octet-stream",
                               extra={"ETag": f'"{sha}"',
                                      "X-Repo-Commit": COMMIT,
                                      "Accept-Ranges": "bytes"})
                return
            m = re.match(r"^/cdn/(.+?)/([0-9a-f]{64})$", path)
            if m:
                fn = by_digest.get(m.group(2)) if m.group(1) == repo_id \
                    else None
                if fn is None:
                    self._send(404, b"")
                    return
                self._send_range(files[fn], m.group(2))
                return
            self._send(404, b'{"error":"not found"}')

    return Handler


@contextlib.contextmanager
def serving(repo_id: str, checkpoint, digests: dict[str, str]):
    """Serve ``checkpoint`` (and the sha256 of each of its files) as
    ``repo_id``; yields the endpoint URL."""
    handler = _make_handler(repo_id, checkpoint.files, digests)
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), handler)
    httpd.daemon_threads = True
    thread = threading.Thread(target=httpd.serve_forever, name="bench-hub",
                              daemon=True)
    thread.start()
    try:
        yield f"http://127.0.0.1:{httpd.server_port}"
    finally:
        httpd.shutdown()
        httpd.server_close()
        thread.join(timeout=10)
