"""A seeded checkpoint that is never held in memory.

The benchmark's weights are a pure function of ``(seed, tensor, position)``.
Which tensors there are, and what fills each, is the table of the
configuration's family (:mod:`families`: ``tensors(config)``, chosen by
``model_type``): ``normal`` is N(0, 1/fan_in) (the 65536 quantiles of the
normal, one drawn per element by a counter-seeded SFC64 stream), ``ones``
and ``zeros`` are what they say, all in bfloat16 and in the names and
layout the family gives (Hugging Face's, ``[out, in]``). The files of the
sharded safetensors repository exist only as :class:`VirtualFile` objects
that produce any byte range on demand, one MiB-sized chunk at a time, so

- the loopback hub (:mod:`hub`) serves 12 GB (or 46 GB) of weights without
  allocating them: on the chip machine touching fresh memory costs more
  than generating the numbers (PERF.md, set-up);
- the float32 reference (:mod:`reference`) reads any tensor again after
  the program's copy is freed, from the seed, having taken nothing from
  the program.

Nothing here imports the program under test.
"""

from __future__ import annotations

import hashlib
import json
import os
import statistics
import struct
from concurrent.futures import ThreadPoolExecutor

import ml_dtypes
import numpy as np

from . import families

#: elements generated per counter-seeded stream (1 MiB of bfloat16)
CHUNK = 1 << 19

BF16 = np.dtype(ml_dtypes.bfloat16)


def _normal_quantiles() -> np.ndarray:
    nd = statistics.NormalDist()
    return np.array([nd.inv_cdf((i + 0.5) / 65536.0) for i in range(65536)],
                    np.float32)


class _Tensor:
    __slots__ = ("index", "name", "shape", "nbytes", "offset", "fill",
                 "fan_in")

    def __init__(self, index: int, name: str, spec: families.Filled):
        shape, fill, fan_in = families.Filled(*spec)
        if fill not in ("normal", "ones", "zeros") \
                or (fill == "normal" and int(fan_in) <= 0):
            raise ValueError(f"tensor {name!r}: fill {fill!r} with fan-in "
                             f"{fan_in!r}; a family says normal (with the "
                             "fan-in), ones or zeros")
        self.index = index
        self.name = name
        self.shape = tuple(int(n) for n in shape)
        self.nbytes = int(np.prod(self.shape)) * 2
        self.offset = 0          # byte offset in its file's data section
        self.fill = fill
        self.fan_in = int(fan_in)


class VirtualFile:
    """One ``.safetensors`` shard: a real header, then tensors whose bytes
    are generated when read."""

    def __init__(self, ckpt: "Checkpoint", tensors: list[_Tensor]):
        self._ckpt = ckpt
        self.tensors = tensors
        header: dict = {}
        off = 0
        for t in tensors:
            t.offset = off
            header[t.name] = {"dtype": "BF16", "shape": list(t.shape),
                              "data_offsets": [off, off + t.nbytes]}
            off += t.nbytes
        raw = json.dumps(header, separators=(",", ":")).encode()
        raw += b" " * (-len(raw) % 8)
        self.header = struct.pack("<Q", len(raw)) + raw
        self.size = len(self.header) + off

    def __len__(self) -> int:
        return self.size

    def read(self, start: int = 0, end: int | None = None):
        """Yield the bytes of ``[start, end)`` in order, as buffers."""
        end = self.size if end is None else min(end, self.size)
        hl = len(self.header)
        if start < hl:
            yield self.header[start:min(end, hl)]
        for t in self.tensors:
            lo = max(start, hl + t.offset)
            hi = min(end, hl + t.offset + t.nbytes)
            if lo < hi:
                yield from self._ckpt.tensor_bytes(
                    t, lo - hl - t.offset, hi - hl - t.offset)

    def sha256(self) -> str:
        h = hashlib.sha256()
        for part in self.read():
            h.update(part)
        return h.hexdigest()


class Checkpoint:
    """The repository ``{filename: bytes | VirtualFile}`` for ``config``
    (the Hugging Face ``config.json`` as a dict) and ``seed``."""

    def __init__(self, config: dict, seed: int, n_shards: int = 8):
        if config.get("torch_dtype") != "bfloat16":
            raise ValueError("the benchmark's checkpoints are bfloat16; the "
                             f"config says {config.get('torch_dtype')!r}")
        self.config = config
        self.seed = int(seed)
        base = _normal_quantiles()
        self._tables: dict[int, np.ndarray] = {}
        self.tensors: dict[str, _Tensor] = {}
        table = families.of(config).tensors(config)
        for i, (name, spec) in enumerate(table.items()):
            t = _Tensor(i, name, spec)
            self.tensors[name] = t
            if t.fill == "normal" and t.fan_in not in self._tables:
                self._tables[t.fan_in] = (
                    base / np.sqrt(np.float32(t.fan_in))
                ).astype(BF16).view(np.uint16)
        self._const = {"ones": np.full(CHUNK, 1.0, BF16).view(np.uint16),
                       "zeros": np.zeros(CHUNK, np.uint16)}

        total = sum(t.nbytes for t in self.tensors.values())
        self.files: dict[str, bytes | VirtualFile] = {
            "config.json": json.dumps(config).encode()}
        weight_map: dict[str, str] = {}
        shard: list[_Tensor] = []
        held = 0

        def flush() -> None:
            name = f"model-{len(self.files):05d}-of-{n_shards:05d}.safetensors"
            self.files[name] = VirtualFile(self, list(shard))
            weight_map.update((t.name, name) for t in shard)
            shard.clear()

        for t in self.tensors.values():
            shard.append(t)
            held += t.nbytes
            if held >= total * len(self.files) / n_shards \
                    and len(self.files) < n_shards:
                flush()
        if shard:
            flush()
        self.files["model.safetensors.index.json"] = json.dumps(
            {"metadata": {"total_size": total}, "weight_map": weight_map}
        ).encode()
        self.total_bytes = total

    # ---------------------------------------------------------- generation
    def _chunk(self, t: _Tensor, j: int) -> np.ndarray:
        """Chunk ``j`` of tensor ``t`` as uint16 bit patterns of bfloat16."""
        n = min(CHUNK, t.nbytes // 2 - j * CHUNK)
        if t.fill != "normal":
            return self._const[t.fill][:n]
        raw = np.random.SFC64([self.seed, t.index, j]).random_raw(
            (n + 3) // 4).view(np.uint16)[:n]
        return np.take(self._tables[t.fan_in], raw, mode="wrap")

    def tensor_bytes(self, t: _Tensor, start: int, end: int):
        """Yield tensor ``t``'s bytes ``[start, end)`` chunk by chunk."""
        cb = CHUNK * 2
        for j in range(start // cb, (end + cb - 1) // cb):
            buf = self._chunk(t, j).view(np.uint8)
            lo = max(start - j * cb, 0)
            hi = min(end - j * cb, buf.size)
            yield memoryview(buf)[lo:hi]

    def tensor(self, name: str) -> np.ndarray:
        """Tensor ``name`` whole, bfloat16 in its ``[out, in]`` shape."""
        t = self.tensors[name]
        out = np.empty(t.nbytes // 2, np.uint16)
        n_chunks = (out.size + CHUNK - 1) // CHUNK

        def fill(j: int) -> None:
            out[j * CHUNK:(j + 1) * CHUNK] = self._chunk(t, j)

        with ThreadPoolExecutor(os.cpu_count() or 4) as pool:
            list(pool.map(fill, range(n_chunks)))
        return out.view(BF16).reshape(t.shape)

    def digests(self) -> dict[str, str]:
        """sha256 of every file, the shards hashed in parallel (a real hub
        has them in its metadata; the program verifies what it pulls)."""
        names = list(self.files)

        def one(name: str) -> str:
            f = self.files[name]
            return f.sha256() if isinstance(f, VirtualFile) \
                else hashlib.sha256(f).hexdigest()

        with ThreadPoolExecutor(os.cpu_count() or 4) as pool:
            return dict(zip(names, pool.map(one, names)))
