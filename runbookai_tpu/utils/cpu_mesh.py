"""Force the jax CPU platform with a virtual multi-device host mesh.

Sharding/parallelism code is validated without TPU hardware on a virtual
CPU mesh (``--xla_force_host_platform_device_count``, SURVEY.md §4). The
device count is an XLA flag and the platform a jax config value, and both
must be set before the jax backend initializes. Shared by
``tests/conftest.py`` and ``__graft_entry__.dryrun_multichip``.
"""

from __future__ import annotations

import os
import re

_FLAG = "--xla_force_host_platform_device_count"


def force_cpu_platform(n_devices: int) -> None:
    """Make jax run on CPU with at least ``n_devices`` virtual devices.

    Must be called before the jax backend initializes; raises RuntimeError
    if jax already came up on another platform or with too few devices
    (env-var and config overrides are no-ops after initialization).
    """
    flags = os.environ.get("XLA_FLAGS", "")
    match = re.search(rf"{_FLAG}=(\d+)", flags)
    if match is None:
        os.environ["XLA_FLAGS"] = (flags + f" {_FLAG}={n_devices}").strip()
    elif int(match.group(1)) < n_devices:
        os.environ["XLA_FLAGS"] = (
            flags[: match.start()] + f"{_FLAG}={n_devices}" + flags[match.end():]
        )
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")

    devices = jax.devices()
    if devices[0].platform != "cpu" or len(devices) < n_devices:
        raise RuntimeError(
            f"force_cpu_platform: jax initialized before the override could "
            f"take effect (platform={devices[0].platform}, "
            f"{len(devices)} devices, need >= {n_devices} cpu). "
            f"Run in a fresh process."
        )
