"""Import-first helper for ad-hoc scripts: hold jax to the CPU with the
8 virtual devices the tests use.  `import scripts.cpu_env` before
anything that touches jax.  Mirrors tests/conftest.py."""

from noahgameframe_tpu.utils.platform import force_cpu

force_cpu(8)
