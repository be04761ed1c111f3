"""Test configuration.

Force JAX onto a virtual 8-device CPU platform so multi-chip sharding paths
(Mesh / shard_map / collectives) are exercised without TPU hardware.

Tests and rehearsals run on the CPU (the driver also sets
``JAX_PLATFORMS=cpu``).  jax is imported before this file runs, so the
platform override goes through ``jax.config`` (backends initialize lazily,
so this still wins as long as no computation has run); ``XLA_FLAGS`` is
read at backend init and can still be set here.  The chip is reached
through ``python chip_smoke.py`` on a chip host, never from pytest.

Every test runs under a time limit of its own (tests/time_limit.py).
"""

from time_limit import pytest_runtest_protocol  # noqa: F401  (the hook)

from bitcoin_miner_tpu.utils.platform import (
    enable_compile_cache,
    force_virtual_cpu,
)

force_virtual_cpu(8)
# XLA:CPU compiles of the sweep kernels take seconds each; cache them across
# pytest runs so only the first invocation pays.
enable_compile_cache()
