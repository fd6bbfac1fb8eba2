import os

# Tests run on a virtual 8-device CPU mesh so sharding paths are exercised
# without TPU hardware.  JAX_PLATFORMS must be in the environment before
# jax is imported; the device count is read when the backend starts.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

assert jax.devices()[0].platform == "cpu", "tests must run on the CPU mesh"

# Tests compile fresh: on the CPU backend a deserialized executable is
# not bit-identical to the freshly compiled one, which breaks the
# bit-exactness contracts the suite asserts (replay digests, gameday
# fault-free controls) and can abort the process outright.  So the
# persistent compile cache is switched off here, whatever
# utils.platform.init_compile_cache() is later asked to do in-process.
jax.config.update("jax_enable_compilation_cache", False)


def pytest_configure(config):
    # tier-1 runs with -m 'not slow'; the long soaks opt out via this mark
    config.addinivalue_line(
        "markers", "slow: long soak tests excluded from the tier-1 run"
    )
