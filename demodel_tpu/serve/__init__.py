"""Token-serving plane: continuous batching over a paged KV cache.

The workload the distribution stack exists for (ROADMAP item 1): a
model pulled through the swarm/tiered store starts SERVING tokens —
``load_model`` makes a cold boot literally a swarm pull
(:func:`demodel_tpu.delivery.pull_to_hbm` → HBM placement →
:class:`~demodel_tpu.serve.scheduler.GenEngine`), and the engine runs
the vLLM-style loop: paged KV blocks under a tier budget
(:mod:`~demodel_tpu.serve.kvcache`), admit → prefill → interleaved
decode with join-between-steps (:mod:`~demodel_tpu.serve.scheduler`),
503 + Retry-After past the waiting room.

Dep-light contract: this package imports jax (via the model step
functions) and must therefore NEVER be imported by the restore
server/statusz/proxy planes directly — they peek
``sys.modules["demodel_tpu.serve"]`` and mount ``/generate`` (or the
``generation`` statusz section) only when something already booted an
engine, the same discipline the swarm routes use.
"""

from __future__ import annotations

import threading

from demodel_tpu.serve.kvcache import (BlockLease, KVBlockPool,
                                       PoolExhausted)
from demodel_tpu.serve.scheduler import (AdmissionQueue, AdmissionTicket,
                                         GenEngine, QueueOverflow, Request)
from demodel_tpu.utils import compile_cache, trace

__all__ = [
    "AdmissionQueue", "AdmissionTicket", "BlockLease", "GenEngine",
    "KVBlockPool", "PoolExhausted", "QueueOverflow", "Request",
    "boot", "current", "install", "load_model",
]

#: the process-wide engine the HTTP surface serves from (one model per
#: process for now — the restore server's /generate and the statusz
#: ``generation`` section both read this through sys.modules)
_current: GenEngine | None = None
_current_lock = threading.Lock()


def install(engine: GenEngine | None) -> None:
    """Make ``engine`` the process-wide serving engine (None clears);
    a replaced engine keeps running — stopping it is the caller's call."""
    global _current
    with _current_lock:
        _current = engine


def current() -> GenEngine | None:
    with _current_lock:
        return _current


def boot(params, cfg, mesh=None, **engine_kw) -> GenEngine:
    """Start an engine over in-memory params and install it — the
    short path for tests/benches and pre-delivered weights. The engine's
    birth (the pool's arrays, the ids and ``_set_id``'s compilation, the
    thread) is the span ``serve.engine-start``."""
    with trace.span("serve.engine-start") as born:
        engine = GenEngine(params, cfg, mesh=mesh, **engine_kw).start()
        born.set_attr("max_batch", engine.max_batch)
        born.set_attr("kv_mb", engine.pool.budget.max_bytes >> 20)
        born.set_attr("pool_bytes",
                      sum(a.nbytes for a in engine.pool.arrays))
    install(engine)
    return engine


def load_model(model: str, cfg, *, source: str = "hf",
               revision: str = "main", endpoint: str | None = None,
               mesh=None, peers: list[str] | None = None,
               **engine_kw) -> GenEngine:
    """Cold model boot IS a swarm pull: fetch ``model`` through the
    tiered store / peer plane (:func:`delivery.pull_to_hbm` — cache
    hits serve from disk/RAM tiers, misses ride single-flight), place
    the weights, and start serving them. ``cfg`` is the
    :class:`~demodel_tpu.config.ProxyConfig` naming the store. ``mesh``
    defaults to every local device (``tp`` = device count); delivery,
    the loader and the engine all get the one resolved here."""
    import jax

    # before the loaders' layout programs: they are counted, and kept
    compile_cache.place()
    with trace.span("serve.load-model", model=model, source=source):
        from demodel_tpu import delivery
        from demodel_tpu.models import auto
        from demodel_tpu.parallel.mesh import make_mesh

        if mesh is None:
            mesh = make_mesh()
        report, placed = delivery.pull_to_hbm(
            model, cfg, source=source, revision=revision,
            endpoint=endpoint, mesh=mesh, peers=peers, deliver=True)
        store = delivery.open_store(cfg)
        try:
            with trace.span("serve.build-params") as built:
                _fn, params, mcfg = auto.model_from_pull(
                    store, report, mesh=mesh, placement=placed)
                leaves = jax.tree.leaves(params)
                built.set_attr("model_type",
                               type(mcfg).__module__.rpartition(".")[2])
                built.set_attr("tensors", len(leaves))
                built.set_attr("bytes", sum(a.nbytes for a in leaves))
        finally:
            store.close()
    return boot(params, mcfg, mesh=mesh, model=model, **engine_kw)
