"""The one general load generator: a traffic file's parameters → requests
over HTTP ``/generate``, timed by the host's clock from the client's side.

A traffic file (``benchmark/traffic/<name>.json``) has

``loop``              ``closed`` (each caller sends its next request when
                      its last one ended) or ``open`` (requests are sent on
                      a schedule whatever the server does);
``stream``            whether replies are streamed (NDJSON, a line a token);
``groups``            a list of ``{"callers": n, "cycle": [{"prompt": p,
                      "output": o, "count": c}, ...]}``: ``n`` callers that
                      each send, for ever, seeded permutations of that cycle
                      (so every seed offers the same work in another order);
``stationary_start``  closed loops only: each caller's first request gets a
                      seeded residual output length, uniform from 1 to the
                      one it drew, and the window opens when every caller
                      has its first token: a full batch in its steady state;
``rate``, ``burst``   open loops only: requests a second, and how many
                      arrive together. Gaps are a seeded permutation of the
                      exponential's quantiles; a request is timed from the
                      moment it was due, and the generator's lateness is
                      reported.

Nothing here imports the program under test or JAX.
"""

from __future__ import annotations

import http.client
import json
import math
import socket
import threading
import time

import numpy as np

_TOKENS_STREAM = 1_000_003      # sub-stream ids, to keep seeded draws apart
_RESIDUAL_STREAM = 1_000_033
_ARRIVAL_STREAM = 1_000_037
_WARMUP_STREAM = 1_000_039


def expand(cycle: list[dict]) -> list[tuple[int, int]]:
    out = []
    for row in cycle:
        out += [(int(row["prompt"]), int(row["output"]))] * int(row["count"])
    return out


def callers_of(traffic: dict) -> list[list[tuple[int, int]]]:
    """One expanded cycle per caller, in the file's order."""
    out = []
    for group in traffic["groups"]:
        out += [expand(group["cycle"])] * int(group["callers"])
    return out


def request_stream(cycle: list[tuple[int, int]], seed: int, caller: int,
                   vocab: int):
    """Yield ``(prompt_ids, output_len)`` for ever: cycle after cycle, each
    a permutation drawn from ``(seed, caller, cycle number)``."""
    n = 0
    while True:
        order = np.random.default_rng([seed, caller, n]).permutation(
            len(cycle))
        for j, i in enumerate(order):
            p, o = cycle[i]
            ids = np.random.default_rng(
                [seed, caller, _TOKENS_STREAM, n * len(cycle) + j]
            ).integers(0, vocab, p)
            yield [int(t) for t in ids], o
        n += 1


def warmup_waves(traffic: dict,
                 max_batch: int) -> list[list[tuple[int, int]]]:
    """The requests that make the server compile every shape this traffic
    reaches, as waves of ``(prompt_len, output_len)`` sent together.

    One-token traffic reaches one prefill per prompt length. Streamed
    multi-token traffic from ``n`` callers (an open loop: as many as the
    engine's ``max_batch``) reaches, besides, decode steps over 1..n
    sequences at each cache width its prompt lengths lead to; a
    wave of one prompt of that length and ``n - 1`` of the shortest, with
    output lengths n+1, 2, 3, ..., n, runs one step at each batch size from
    n down to 1 with the long sequence in all of them."""
    pairs = [pair for cycle in callers_of(traffic) for pair in cycle]
    lengths = sorted({p for p, _o in pairs})
    n = max_batch if traffic["loop"] == "open" else min(
        max_batch, len(callers_of(traffic)))
    if max(o for _p, o in pairs) == 1:
        return [[(p, 1)] for p in lengths]
    return [[(p, n + 1)] + [(lengths[0], i + 1) for i in range(1, n)]
            for p in lengths]


def warmup_prompts(wave: list[tuple[int, int]], seed: int, index: int,
                   vocab: int) -> list[list[int]]:
    rng = np.random.default_rng([seed, _WARMUP_STREAM, index])
    return [[int(t) for t in rng.integers(0, vocab, p)] for p, _o in wave]


class Record:
    """One request as its client saw it. Times are ``time.time()``."""

    __slots__ = ("caller", "prompt", "max_new", "due", "sent", "status",
                 "times", "tokens", "done", "cut", "error")

    def __init__(self, caller: int, prompt: list[int], max_new: int):
        self.caller = caller
        self.prompt = prompt
        self.max_new = max_new
        self.due: float | None = None     # open loops: when it should go
        self.sent: float | None = None
        self.status: int | None = None
        self.times: list[float] = []      # arrival of each token
        self.tokens: list[int] = []
        self.done = False                 # the server's closing line came
        self.cut = False                  # the window's end cut it
        self.error: str | None = None


class Client:
    """Sends one request and fills its :class:`Record`; ``cut()`` from
    another thread ends it where it stands."""

    def __init__(self, port: int, stream: bool):
        self.port = port
        self.stream = stream
        self._conn: http.client.HTTPConnection | None = None
        self._lock = threading.Lock()
        self._cutting = False

    def cut(self) -> None:
        with self._lock:
            self._cutting = True
            conn = self._conn
        if conn is not None and conn.sock is not None:
            try:
                conn.sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass

    def send(self, rec: Record, on_first=None) -> Record:
        """``on_first`` is called once, at the first token or, if none
        comes, when the request ends."""
        conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=900)
        with self._lock:
            if self._cutting:
                rec.cut = True
                if on_first is not None:
                    on_first()
                return rec
            self._conn = conn
        body = json.dumps({"prompt": rec.prompt,
                           "max_new_tokens": rec.max_new,
                           "stream": self.stream, "timeout": 900})
        try:
            rec.sent = time.time()
            conn.request("POST", "/generate", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            rec.status = resp.status
            if resp.status != 200:
                rec.error = resp.read(2000).decode(errors="replace")
            elif not self.stream:
                reply = json.loads(resp.read())
                now = time.time()
                rec.tokens = list(reply["tokens"])
                rec.times = [now] * len(rec.tokens)
                rec.done = True
            else:
                for line in iter(resp.readline, b""):
                    now = time.time()
                    item = json.loads(line)
                    if "token" in item:
                        rec.tokens.append(item["token"])
                        rec.times.append(now)
                        if on_first is not None:
                            on_first()
                            on_first = None
                    elif "error" in item:
                        rec.error = str(item["error"])
                    elif item.get("done"):
                        rec.done = True
                        if item.get("tokens") != rec.tokens:
                            rec.error = "closing line disagrees with stream"
                if not rec.done and rec.error is None:
                    rec.error = "stream ended without its closing line"
        except (OSError, http.client.HTTPException, ValueError) as exc:
            rec.error = f"{type(exc).__name__}: {exc}"
        finally:
            with self._lock:
                self._conn = None
                if self._cutting:       # whatever it died of, we did it
                    rec.cut, rec.error = not rec.done, None
            conn.close()
            if on_first is not None:
                on_first()
        return rec


def run_wave(port: int, stream: bool, prompts: list[list[int]],
             outputs: list[int]) -> list[Record]:
    """Send ``prompts`` together (the first a moment ahead, so that it is
    admitted first) and wait for all of them."""
    recs = [Record(-1, p, o) for p, o in zip(prompts, outputs)]
    threads = [threading.Thread(target=Client(port, stream).send, args=(r,))
               for r in recs]
    for i, t in enumerate(threads):
        t.start()
        if i == 0:
            time.sleep(0.02)
    for t in threads:
        t.join()
    return recs


class Window:
    """Drives one traffic file against ``port`` and keeps every record.

    ``open()`` starts the callers and returns once the window's clock
    starts; ``close()`` waits for the window's end, cuts what is in
    flight, joins every thread. ``t0``/``t1`` bound the window."""

    def __init__(self, traffic: dict, port: int, seed: int, vocab: int,
                 seconds: float):
        self.traffic = traffic
        self.port = port
        self.seed = int(seed)
        self.vocab = vocab
        self.seconds = float(seconds)
        self.stream = bool(traffic.get("stream", True))
        self.records: list[Record] = []
        self.lateness: list[float] = []
        self.t0 = self.t1 = 0.0
        self._lock = threading.Lock()
        self._closing = threading.Event()
        self._clients: list[Client] = []
        self._threads: list[threading.Thread] = []
        self._first = threading.Semaphore(0)
        self._generator: threading.Thread | None = None

    # ------------------------------------------------------------- closed
    def _caller(self, k: int, cycle: list[tuple[int, int]],
                client: Client) -> None:
        first = True
        for prompt, out in request_stream(cycle, self.seed, k, self.vocab):
            if self._closing.is_set():
                break
            if first and self.traffic.get("stationary_start"):
                out = int(np.random.default_rng(
                    [self.seed, k, _RESIDUAL_STREAM]).integers(1, out + 1))
            rec = Record(k, prompt, out)
            with self._lock:
                self.records.append(rec)
            client.send(rec, on_first=self._first.release if first else None)
            first = False
            if rec.cut:
                break
            if rec.error is not None or rec.status != 200:
                time.sleep(0.05)        # a failing server is not hammered

    # --------------------------------------------------------------- open
    def _schedule(self) -> list[float]:
        """Offsets at which requests are due: bursts of ``burst`` with
        gaps that are a seeded permutation of the exponential's quantiles
        (mean ``burst / rate``), so every seed offers the same gaps."""
        rate, burst = float(self.traffic["rate"]), int(
            self.traffic.get("burst", 1))
        n = max(1, math.ceil(self.seconds * rate / burst))
        q = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-q) * burst / rate
        gaps = gaps[np.random.default_rng(
            [self.seed, _ARRIVAL_STREAM]).permutation(n)]
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        return [float(t) for t in due for _ in range(burst)
                if t < self.seconds]

    def _open_loop(self) -> None:
        cycle = [pair for c in callers_of(self.traffic) for pair in c]
        stream = request_stream(cycle, self.seed, 0, self.vocab)
        for due in self._schedule():
            prompt, out = next(stream)
            wait = self.t0 + due - time.time()
            if wait > 0 and self._closing.wait(wait):
                break
            if self._closing.is_set():
                break
            rec = Record(0, prompt, out)
            rec.due = self.t0 + due
            self.lateness.append(time.time() - rec.due)
            client = Client(self.port, self.stream)
            t = threading.Thread(target=client.send, args=(rec,))
            with self._lock:
                self.records.append(rec)
                self._clients.append(client)
                self._threads.append(t)
            t.start()

    # ------------------------------------------------------------ driving
    def open(self) -> None:
        if self.traffic["loop"] == "open":
            self.t0 = time.time()
            self._generator = threading.Thread(target=self._open_loop,
                                               name="bench-open")
            self._generator.start()
        elif self.traffic["loop"] == "closed":
            cycles = callers_of(self.traffic)
            stationary = bool(self.traffic.get("stationary_start"))
            if not stationary:
                self.t0 = time.time()
            for k, cycle in enumerate(cycles):
                client = Client(self.port, self.stream)
                t = threading.Thread(target=self._caller,
                                     args=(k, cycle, client),
                                     name=f"bench-caller-{k}")
                self._clients.append(client)
                self._threads.append(t)
                t.start()
            if stationary:
                for _ in cycles:
                    self._first.acquire()
                self.t0 = time.time()
        else:
            raise ValueError(f"unknown loop kind {self.traffic['loop']!r}")
        self.t1 = self.t0 + self.seconds

    def close(self, now: bool = False) -> None:
        """Wait for the window's end (or end it ``now``), cut what is in
        flight and join every thread. Safe to call twice."""
        if not now:
            time.sleep(max(0.0, self.t1 - time.time()))
        self._closing.set()
        if self._generator is not None:
            self._generator.join(timeout=60)
        with self._lock:
            clients = list(self._clients)
        for c in clients:
            c.cut()
        for t in list(self._threads):
            t.join(timeout=60)
        alive = [t.name for t in self._threads if t.is_alive()]
        if alive:
            raise RuntimeError(f"load generator threads still alive: {alive}")
