"""Platform selection and the compile cache for entry points.

JAX uses the TPU by default and fails at start-up if it cannot.  Tests,
rehearsals and the control-plane roles run on the CPU instead: that is
`JAX_PLATFORMS=cpu` in the environment before the first backend touch
(plus `--xla_force_host_platform_device_count=N` in `XLA_FLAGS` for a
virtual mesh), which is all `force_cpu` does.  A chip belongs to one
process at a time, so a process that must stay off it calls `force_cpu`
before anything touches a backend.
"""

from __future__ import annotations

import os
import re
from pathlib import Path

#: where the persistent compile cache lives unless the environment
#: places it: a fixed path inside the checkout (the path is part of the
#: cache key, so a directory that moves never hits)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def force_cpu(n_devices: int | None = None):
    """Hold this process to the CPU platform, optionally with
    `n_devices` virtual devices.  Must run before the first backend
    touch: raises if a backend is already up on another platform or
    with fewer devices.  Returns the jax module."""
    if n_devices is not None:
        flag = f"--xla_force_host_platform_device_count={n_devices}"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" in flags:
            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+", flag, flags
            )
        else:
            flags = (flags + " " + flag).strip()
        os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    # jax reads JAX_PLATFORMS when it is imported; a caller that
    # imported it earlier needs the config value set as well
    jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    if devs[0].platform != "cpu" or len(devs) < (n_devices or 1):
        raise RuntimeError(
            f"force_cpu({n_devices}) ran after a backend was initialised "
            f"({len(devs)} x {devs[0].platform}); call it before the "
            "first backend touch"
        )
    return jax


def require_tpu():
    """The measurement paths' device check: return `jax.devices()` or
    raise when the default backend is anything but a TPU.  There is no
    fallback — a number from another device is a different metric."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(
            f"no TPU: jax.devices()[0] is {devs[0].platform!r} "
            f"({devs[0].device_kind}); pass --platform cpu for a rehearsal"
        )
    return devs


def init_compile_cache() -> str:
    """Turn on jax's persistent compilation cache and return its
    directory: `$JAX_COMPILATION_CACHE_DIR` when the environment sets
    it, else `DEFAULT_CACHE_DIR`.  The only place in the repo that sets
    `jax_compilation_cache_dir`; entry points call it before their
    first trace."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        DEFAULT_CACHE_DIR)
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
