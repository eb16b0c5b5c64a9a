"""Degrade-not-crash env parsing.

The reference's env handling panics the whole server on config mistakes
(``mo.Result.MustGet``, ``start.go:170-173``); here a malformed value logs a
warning and yields the default — a proxy node must not die because someone
fat-fingered an integer.
"""

from __future__ import annotations

import os

from demodel_tpu.utils.logging import get_logger

log = get_logger("env")

_TRUE = ("1", "true", "yes", "on")
_FALSE = ("", "0", "false", "no", "off")


def env_int(name: str, default: int, minimum: int | None = None) -> int:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        val = int(raw)
    except ValueError:
        log.warning("%s=%r is not an integer; using default %d", name, raw,
                    default)
        return default
    if minimum is not None and val < minimum:
        log.warning("%s=%d below minimum %d; clamping", name, val, minimum)
        return minimum
    return val


def env_bool(name: str, default: bool = False) -> bool:
    raw = os.environ.get(name)
    if raw is None:
        return default
    low = raw.strip().lower()
    if low in _TRUE:
        return True
    if low in _FALSE:
        return False
    log.warning("%s=%r is not a boolean; using default %s", name, raw, default)
    return default


def env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return float(raw)
    except ValueError:
        log.warning("%s=%r is not a float; using default %s", name, raw,
                    default)
        return default


def available_cpus() -> int:
    """CPUs this process may actually run on — sched_getaffinity sees
    cgroup/affinity limits (a container pinned to 1 CPU on a 64-core
    host); cpu_count() is the fallback where affinity is unsupported.
    Concurrency defaults (peer streams, sink prefetch) clamp to this:
    extra threads/sockets only help when cores exist to drain them."""
    try:
        return len(os.sched_getaffinity(0)) or 1
    except (AttributeError, OSError):
        return os.cpu_count() or 1


# ---- pull/swarm-plane knob defaults ----------------------------------
#
# These resolve HERE (stdlib-only) rather than in their consuming
# modules because the statusz effective-config surface must report them
# dep-light: importing parallel.peer, parallel.placement, or sink.tuner
# runs those packages' __init__ and drags in jax — a statusz scrape must
# never do that. The consumers (peer._peer_streams, placement, tuner)
# delegate to these, so there is exactly one copy of each default.


def default_peer_streams() -> int:
    """``DEMODEL_PEER_STREAMS``: connections per large-object peer
    transfer. The unset default clamps to the core count — extra sockets
    on a 1-core host just contend (measured −18% at 1 core, 8 streams);
    an explicit env value always wins."""
    return env_int("DEMODEL_PEER_STREAMS",
                   max(1, min(8, available_cpus())), minimum=1)


def default_pull_window_mb() -> int:
    """``DEMODEL_PULL_WINDOW_MB``: fetch window granularity (default 32
    — large enough to amortize per-window overhead, small enough that
    one flaky window's retry cost stays bounded)."""
    return env_int("DEMODEL_PULL_WINDOW_MB", 32, minimum=1)


def tuner_enabled() -> bool:
    """``DEMODEL_TUNER``: the adaptive pull tuner switch — on unless
    explicitly disabled (=0 restores the fixed env defaults)."""
    return env_bool("DEMODEL_TUNER", True)


def default_swarm_chunk_mb() -> int:
    return env_int("DEMODEL_SWARM_CHUNK_MB", 8, minimum=1)


def default_swarm_fill_timeout() -> float:
    return float(env_int("DEMODEL_SWARM_FILL_TIMEOUT", 60, minimum=1))


def default_swarm_origin_streams() -> int:
    return env_int("DEMODEL_SWARM_ORIGIN_STREAMS", 1, minimum=1)


def swarm_reap_enabled() -> bool:
    """``DEMODEL_SWARM_REAP``=0 keeps the pre-reaper retain-until-
    close() board behavior (e.g. a warm standby that WANTS to keep
    serving)."""
    return env_bool("DEMODEL_SWARM_REAP", True)


def cache_max_gb() -> int:
    """``DEMODEL_CACHE_MAX_GB``: the disk tier's byte budget in GB
    (0 = unbounded). One resolver for every enforcement point — the
    native proxy's serving-loop gc, the pull plane's post-pull sweep,
    and the tier API's :func:`demodel_tpu.tier.enforce_disk_budget`."""
    return env_int("DEMODEL_CACHE_MAX_GB", 0, minimum=0)


def default_tier_ram_mb() -> int:
    """``DEMODEL_TIER_RAM_MB``: the host-RAM tier's byte budget in MB —
    mmap'd hot objects AND in-flight swarm chunk boards charge the same
    budget (chunk landings push hot objects out, never the reverse)."""
    return env_int("DEMODEL_TIER_RAM_MB", 256, minimum=1)


def telemetry_archive_dir() -> str:
    """``DEMODEL_TELEMETRY_ARCHIVE``: directory for the durable telemetry
    archive (:mod:`demodel_tpu.utils.retention`). Empty/unset disables
    the retention plane entirely — no import, no flusher thread."""
    return os.environ.get("DEMODEL_TELEMETRY_ARCHIVE", "").strip()


def telemetry_retain_mb() -> int:
    """``DEMODEL_TELEMETRY_RETAIN_MB``: byte budget for archived
    telemetry segments; oldest segments are evicted past it."""
    return env_int("DEMODEL_TELEMETRY_RETAIN_MB", 64, minimum=1)


def telemetry_retain_hours() -> int:
    """``DEMODEL_TELEMETRY_RETAIN_HOURS``: age budget for archived
    telemetry segments (default three days of history)."""
    return env_int("DEMODEL_TELEMETRY_RETAIN_HOURS", 72, minimum=1)


def profile_hz() -> int:
    """``DEMODEL_PROFILE_HZ``: sampling rate of the continuous profiler
    (default 19 — deliberately off the common 10/100 Hz beat so periodic
    work at round rates doesn't alias into or out of the profile)."""
    return env_int("DEMODEL_PROFILE_HZ", 19, minimum=1)


def profile_max_stacks() -> int:
    """``DEMODEL_PROFILE_MAX_STACKS``: bound on distinct folded stacks
    the profiler aggregates; past it new stacks fold into ``(other)`` and
    a drop counter — the aggregate must stay bounded on any workload."""
    return env_int("DEMODEL_PROFILE_MAX_STACKS", 2048, minimum=16)


def profile_window_s() -> int:
    """``DEMODEL_PROFILE_WINDOW_S``: seconds per profile window rolled
    into the telemetry archive (Python plane only — the native sampler
    exports cumulative aggregates and the restore server windows them)."""
    return env_int("DEMODEL_PROFILE_WINDOW_S", 60, minimum=5)


def proxy_write_timeout() -> int:
    """``DEMODEL_PROXY_WRITE_TIMEOUT``: per-connection deadline (seconds)
    for the reactor's EPOLLOUT writer to fully drain one response; a
    client still holding an undrained body past it is evicted."""
    return env_int("DEMODEL_PROXY_WRITE_TIMEOUT", 75, minimum=1)


def proxy_write_min_bps() -> int:
    """``DEMODEL_PROXY_WRITE_MIN_BPS``: low-watermark drain rate for the
    writer stall sweep — a connection draining slower than this (checked
    about once a second) is evicted early. 0 (the default) disables the
    watermark; only the write deadline then bounds a slow reader."""
    return env_int("DEMODEL_PROXY_WRITE_MIN_BPS", 0, minimum=0)


def proxy_ktls() -> bool:
    """``DEMODEL_PROXY_KTLS``: allow kernel-TLS ``SSL_sendfile`` for
    MITM'd cache hits (on by default; availability is runtime-probed and
    the chunked ``SSL_write`` pump is the automatic fallback)."""
    return env_bool("DEMODEL_PROXY_KTLS", True)


def store_reprobe_secs() -> int:
    """``DEMODEL_STORE_REPROBE_SECS``: how often a node in degraded
    read-through mode re-probes the store with a small real write; a
    successful probe exits the mode automatically. Shared with the
    native proxy's storage maintenance thread."""
    return env_int("DEMODEL_STORE_REPROBE_SECS", 10, minimum=1)


def scrub_interval_secs() -> int:
    """``DEMODEL_SCRUB_INTERVAL_SECS``: seconds between background
    scrubber slices re-digesting committed objects (0, the default,
    disables the scrubber on both planes)."""
    return env_int("DEMODEL_SCRUB_INTERVAL_SECS", 0, minimum=0)


def scrub_rate_mb_s() -> int:
    """``DEMODEL_SCRUB_RATE_MB_S``: the scrubber's re-digest budget in
    MB per second — each slice reads at most ``rate × interval`` bytes,
    so a cold cache is verified slowly enough to never contend with
    serving."""
    return env_int("DEMODEL_SCRUB_RATE_MB_S", 8, minimum=1)
