"""Where XLA's persistent compilation cache lives.

Every chip-facing entry point (``GenEngine``, ``bench.py``,
``chip_smoke.py``, ``__graft_entry__``) calls :func:`place` before its
first compile. The engine compiles one multi-layer graph per prompt
length and per decode bucket; on a machine that starts with no compiled
code that is most of a cold run, and a second process should not pay it
again.

The directory must not move between runs (a moved cache never hits), so
it is never derived from ``tempfile``, a pid or the clock:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads the
variable itself — no path is set here), else ``.jax_cache`` next to
``pyproject.toml``.

Where the cache is placed is also where the process starts to account for
the programs it makes ready: :func:`place` hangs one set of listeners on
JAX's own events (``jax.monitoring``), which JAX calls on the thread that
traces, lowers and loads or compiles a program. They feed
``gen_programs_ready_total{stage, how}`` (a program: ``how`` is ``loaded``
from the persistent cache or ``compiled`` here) and
``gen_program_seconds_total{stage, phase}`` (``trace``, ``lower``, ``load``
or ``compile``: JAX reports a load as a compilation whose duration is the
retrieval), and write ``trace_s``, ``lower_s``, ``backend_s`` and ``how``
on the ambient ``serve.program-ready`` span, which the engine opens around
the first run of a shape. ``stage`` is what the thread says it is
dispatching (:data:`dispatching`), ``other`` where it says nothing: the
loaders' layout programs, the pool's birth.

jax is imported inside the function: the dep-light planes import
``demodel_tpu.utils`` freely and must not pay for it.
"""

from __future__ import annotations

import os
import threading
from pathlib import Path
from typing import Any

from demodel_tpu.utils import trace
from demodel_tpu.utils.metrics import HUB, labeled, parse_labels

#: the checkout root (the directory holding ``pyproject.toml``)
_CHECKOUT = Path(__file__).resolve().parents[2]

#: the span the engine opens around the first run of a shape
READY_SPAN = "serve.program-ready"

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


#: pre-register the families at import (house idiom)
for _stage in ("prefill", "decode", "other"):
    for _how in ("loaded", "compiled"):
        HUB.inc(labeled("gen_programs_ready_total", how=_how, stage=_stage), 0)
    for _phase in ("trace", "lower", "load", "compile"):
        HUB.inc(labeled("gen_program_seconds_total", phase=_phase,
                        stage=_stage), 0)


class _Thread(threading.local):
    """What one thread is making ready. ``shape`` is the thread's own to
    set, a plain assignment before a jitted call: ``(stage, *sizes)``, and
    it stays until the next one (the engine thread dispatches nothing but
    its two programs)."""

    shape: tuple = ("other",)
    depth = 0           # open traces: JAX times every nested jit's too
    hit = False         # the open backend compilation found the cache
    trace_s = 0.0       # the newest outermost trace and lowering: those of
    lower_s = 0.0       # the program whose backend compilation comes next


#: ``dispatching.shape = ("decode", bucket, width)``: see :class:`_Thread`
dispatching = _Thread()

_lock = threading.Lock()
_listening = False
#: the newest program made ready, for ``/debug/statusz``
_last: dict[str, Any] | None = None


def _phase(phase: str, secs: float):
    """``secs`` of one phase, to the counter and to the ambient span if it
    is the engine's around a first run, which is returned (or None)."""
    HUB.inc(labeled("gen_program_seconds_total", phase=phase,
                    stage=dispatching.shape[0]), secs)
    span = trace.current()
    if span is None or span.name != READY_SPAN:
        return None
    key = "backend_s" if phase in ("load", "compile") else f"{phase}_s"
    span.set_attr(key, round(span.attrs.get(key, 0.0) + secs, 6))
    return span


def _on_scalar(event: str, _value: float, **_kw: Any) -> None:
    if event == _TRACE:     # JAX records a timed stretch's start as a scalar
        dispatching.depth += 1


def _on_event(event: str, **_kw: Any) -> None:
    if event == _CACHE_HIT:
        dispatching.hit = True


def _on_duration(event: str, secs: float, **kw: Any) -> None:
    global _last
    t = dispatching
    if event == _TRACE:
        # the outermost trace holds the nested ones: counted once, a
        # program's phases sum to no more than the wall time of its call
        t.depth = max(0, t.depth - 1)
        if t.depth == 0:
            t.trace_s = secs
            _phase("trace", secs)
    elif event == _LOWER:
        t.lower_s = secs
        _phase("lower", secs)
    elif event == _BACKEND:
        how, t.hit = ("loaded" if t.hit else "compiled"), False
        span = _phase("load" if how == "loaded" else "compile", secs)
        if span is not None:
            span.set_attr("how", how)
        stage, *sizes = t.shape
        HUB.inc(labeled("gen_programs_ready_total", how=how, stage=stage))
        last = {"stage": stage, "shape": sizes, "how": how,
                "name": str(kw.get("fun_name", "")),
                "trace_s": round(t.trace_s, 6),
                "lower_s": round(t.lower_s, 6), "backend_s": round(secs, 6)}
        t.trace_s = t.lower_s = 0.0
        with _lock:
            _last = last


def _listen(jax) -> None:
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    jax.monitoring.register_scalar_listener(_on_scalar)
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def programs() -> dict[str, Any]:
    """The programs this process has made ready since :func:`place`:
    ``ready[stage][how]``, ``seconds[phase]`` over every stage, and the
    newest one (``/debug/statusz``, ``GenEngine.describe``)."""
    ready: dict[str, dict[str, int]] = {}
    seconds: dict[str, float] = {}
    for name, value in HUB.snapshot().items():
        family, got = parse_labels(name)
        if family == "gen_programs_ready_total":
            ready.setdefault(got["stage"], {})[got["how"]] = int(value)
        elif family == "gen_program_seconds_total":
            seconds[got["phase"]] = round(
                seconds.get(got["phase"], 0.0) + value, 6)
    with _lock:
        return {"ready": ready, "seconds": seconds, "last": _last}


def place() -> Path:
    """Point JAX's persistent compile cache at its fixed home (idempotent),
    start counting the programs made ready, and return the directory."""
    import jax

    _listen(jax)
    if jax.default_backend() == "tpu":
        # JAX persists only compiles of a second or more. The chip smoke
        # showed what that skips on a TPU: 135 of its 141 compilations,
        # 17.8 of the cold run's 48.0 s (v5e, PR 21). On the CPU the
        # default stays — a test run compiles thousands of sub-second
        # programs and would write every one.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if from_env:
        return Path(from_env)
    path = _CHECKOUT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path
