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

jax is imported inside the function: the dep-light planes import
``demodel_tpu.utils`` freely and must not pay for it.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the checkout root (the directory holding ``pyproject.toml``)
_CHECKOUT = Path(__file__).resolve().parents[2]


def place() -> Path:
    """Point JAX's persistent compile cache at its fixed home (idempotent)
    and return the directory."""
    import jax

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
