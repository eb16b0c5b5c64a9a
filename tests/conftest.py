"""Test harness: force an 8-virtual-device CPU platform BEFORE jax
initializes (SURVEY.md §4/§7 — NamedSharding placement without TPUs).

The tier-1 command also passes ``JAX_PLATFORMS=cpu``; the config update
below keeps a bare ``pytest`` on the CPU too, on a machine that has a
chip.
"""

from __future__ import annotations

import os
import re
import sys

# append (not clobber) the virtual device count to any existing XLA flags
_flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                os.environ.get("XLA_FLAGS", ""))
os.environ["XLA_FLAGS"] = (
    _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

try:
    jax.config.update("jax_platforms", "cpu")
except RuntimeError:  # backend already up (re-entrant runs) — best effort
    pass

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _no_profiler_left_running():
    """A restore server starts the process's sampling profiler
    (``RestoreServer.start``) and no test that starts one stops it: a later
    file of the same worker then found the windows it rolled in its own
    counts (``tests/test_retention.py``'s flush). Stopped where it was
    started, when the file that started it is done."""
    yield
    prof = sys.modules.get("demodel_tpu.utils.profiler")
    if prof is not None:
        prof.stop()


@pytest.fixture()
def mesh8():
    from demodel_tpu.parallel import make_mesh

    return make_mesh(8)


@pytest.fixture()
def tmp_dirs(tmp_path):
    """(data_dir, cache_dir) pair for config-dependent components."""
    data = tmp_path / "data"
    cache = tmp_path / "cache"
    data.mkdir()
    cache.mkdir()
    return data, cache
