"""Accelerator detection and the persistent compile cache.

Detection probes the device object itself: its platform name *and*
``device_kind`` (which reads e.g. "TPU v5 lite" whatever name a PJRT
plugin registers under).
"""

from __future__ import annotations

import os
from functools import lru_cache
from pathlib import Path
from typing import Optional

import jax

# The PJRT platform name that fronts real TPU hardware.
_TPU_PLATFORMS = frozenset({"tpu"})

# The compile cache's home when JAX_COMPILATION_CACHE_DIR is unset: fixed
# and inside the checkout (git ignores it), because the path is part of the
# cache key — a directory that moves never hits.
REPO_COMPILE_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"

# Known PJRT platform names that front real GPU hardware (jax registers
# CUDA devices as "gpu" or "cuda" depending on plugin vintage; ROCm as
# "rocm").
_GPU_PLATFORMS = frozenset({"gpu", "cuda", "rocm"})


def is_tpu_device(dev) -> bool:
    """True if ``dev`` (a jax Device) is a TPU chip, whatever its plugin's
    registered platform name."""
    if (dev.platform or "").lower() in _TPU_PLATFORMS:
        return True
    kind = (getattr(dev, "device_kind", "") or "").lower()
    return "tpu" in kind


def is_gpu_device(dev) -> bool:
    """True if ``dev`` (a jax Device) is a GPU, whatever its plugin's
    registered platform name (same probe shape as :func:`is_tpu_device`:
    platform name first, ``device_kind`` as the fallback)."""
    if (dev.platform or "").lower() in _GPU_PLATFORMS:
        return True
    kind = (getattr(dev, "device_kind", "") or "").lower()
    return any(t in kind for t in ("nvidia", "radeon", "amd instinct"))


@lru_cache(maxsize=1)
def is_tpu() -> bool:
    """True if the default JAX backend fronts TPU hardware (initializes the
    backend on first call; cached per process)."""
    return is_tpu_device(jax.devices()[0])


@lru_cache(maxsize=1)
def pallas_platform() -> Optional[str]:
    """Which Pallas lowering the default backend's devices would take:
    ``"mosaic"`` on TPU, ``"triton"`` on GPU, ``None`` on CPU (no
    lowering — the interpreter is a test rig, not a tier).

    This is the probe the sweep drivers' rung resolution and the bench
    stamps consult (ISSUE 20): rung *defaults* stay conservative — the
    pallas tier is ON by default only under the Mosaic lowering, where
    its wins are measured; a Triton host resolves to the xla tier until
    a GPU bench prices the rung (ROADMAP follow-on) — but the probe
    result rides every bench JSON line so off-host analysis can tell a
    "pallas off: no lowering" host from a "pallas off: unpriced Triton"
    one."""
    dev = jax.devices()[0]
    if is_tpu_device(dev):
        return "mosaic"
    if is_gpu_device(dev):
        return "triton"
    return None


def device_desc(dev) -> str:
    """Human-readable one-liner for logs: platform + device_kind."""
    kind = getattr(dev, "device_kind", None) or "?"
    return f"{dev.platform}:{kind}"


def force_virtual_cpu(n_devices: int) -> None:
    """Force this process onto ``n_devices`` virtual CPU devices.

    Must run before any backend initializes.  ``JAX_PLATFORMS`` is read
    when jax is imported, so a caller that has already imported jax needs
    the override through ``jax.config``; ``XLA_FLAGS`` is still read at
    backend init.  Used by the test conftest and the driver's multichip
    dryrun.
    """
    xla_flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xla_flags:
        os.environ["XLA_FLAGS"] = (
            xla_flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        pass  # a backend already initialized; leave the caller's setup alone


def enable_compile_cache() -> str:
    """Turn on the persistent XLA compilation cache and return its directory.

    Kernel shape classes take 10-40 s to compile for the TPU (seconds on
    the CPU); a restarted miner or a repeat run loads them instead.  When
    ``JAX_COMPILATION_CACHE_DIR`` is set, jax has already read it and this
    sets no path; otherwise the cache is :data:`REPO_COMPILE_CACHE`."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_COMPILE_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
