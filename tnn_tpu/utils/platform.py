"""Virtual CPU devices for the test suite and the multi-chip dry run.

Which platform a process runs on is plain JAX: ``JAX_PLATFORMS=cpu`` (or
``tpu``) on its environment. The one thing that needs code is asking the CPU
backend for N virtual devices — tests/conftest.py and
``__graft_entry__.dryrun_multichip`` exercise the sharded programs on an
8-device CPU mesh — so that lives here, in one place.
"""
from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def force_platform(platform: str = "cpu", n_devices: int | None = None):
    """Pin the JAX platform (and, for cpu, the virtual device count). Returns jax.

    Must run before the backend is initialized; after initialization use
    :func:`ensure_cpu_devices`, which also resets an already-created backend.
    """
    os.environ["JAX_PLATFORMS"] = platform
    if platform == "cpu" and n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        flag = f"{_COUNT_FLAG}={n_devices}"
        if _COUNT_FLAG in flags:
            # Replace an existing count rather than appending a duplicate: XLA honors
            # the first occurrence, so append-only would silently keep the old count.
            flags = re.sub(rf"{_COUNT_FLAG}=\d+", flag, flags)
        else:
            flags = (flags + " " + flag).strip()
        # inherited by subprocesses the tests spawn
        os.environ["XLA_FLAGS"] = flags

    import jax

    jax.config.update("jax_platforms", platform)
    if platform == "cpu" and n_devices is not None:
        jax.config.update("jax_num_cpu_devices", n_devices)
    return jax


def ensure_cpu_devices(n_devices: int):
    """Force the virtual n-device CPU platform, resetting a live backend if needed.

    clear_backends runs BEFORE the config updates: once a backend exists, the
    jax_num_cpu_devices update raises (and XLA_FLAGS was already parsed), so
    clearing afterwards would re-create a 1-device CPU client. Clearing when no
    backend exists yet is a no-op, so the unconditional order is always safe.
    """
    from jax.extend import backend as _jeb

    _jeb.clear_backends()
    jax = force_platform("cpu", n_devices)
    devs = jax.devices()
    assert devs[0].platform == "cpu" and len(devs) >= n_devices, (
        f"could not force {n_devices} CPU devices: got {len(devs)} x {devs[0].platform}"
    )
    return jax
