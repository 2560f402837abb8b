"""Backend/platform forcing helpers.

The multi-device distributed tier is validated on virtual CPU devices — the
JAX analog of the reference's oversubscribed single-machine MPI testing
(tests/cmake/KaTestrophe.cmake, SURVEY §4).  Forcing happens in-process with
``jax.config.update``, which works even after jax is imported because
backends initialize lazily on first use, not on import.  Nothing here picks
a backend on its own: CPU runs come from ``JAX_PLATFORMS=cpu`` or an explicit
:func:`force_cpu_devices` call.
"""

from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def force_cpu_devices(n_devices: int) -> list:
    """Force the CPU platform with at least ``n_devices`` virtual devices.

    Must be called before the CPU backend is first used.  Any pre-existing
    ``xla_force_host_platform_device_count`` flag is replaced (a smaller
    inherited count would otherwise win and starve the mesh).  Returns the
    first ``n_devices`` CPU devices.
    """
    import jax

    flags = os.environ.get("XLA_FLAGS", "")
    existing = re.search(rf"{_COUNT_FLAG}=(\d+)", flags)
    count = max(n_devices, int(existing.group(1)) if existing else 0)
    flags = re.sub(rf"{_COUNT_FLAG}=\d+", "", flags).strip()
    os.environ["XLA_FLAGS"] = f"{flags} {_COUNT_FLAG}={count}".strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
    devs = jax.devices("cpu")
    if len(devs) < n_devices:
        raise RuntimeError(
            f"requested {n_devices} virtual CPU devices but the backend "
            f"initialized with {len(devs)}; the CPU backend was already "
            "live before force_cpu_devices was called"
        )
    return devs[:n_devices]


def host_pool_workers(jobs: int) -> int:
    """Thread-pool sizing for independent host-side subproblems (per-block
    extension in partitioning/deep.py, per-lane serve stages in
    serve/lanestack.py — the reference's TBB-arena analogs): one worker
    per job, capped by the machine and a 16-thread ceiling.  ONE policy so
    the pools cannot drift apart."""
    return min(max(int(jobs), 1), max(os.cpu_count() or 1, 1), 16)
