"""Accelerator detection and the compile cache's home (utils/platform.py).

A ``jax.default_backend() == "tpu"`` string compare is not the detector:
the device object's platform name and ``device_kind`` are.
"""

import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from bitcoin_miner_tpu.utils.platform import (
    REPO_COMPILE_CACHE,
    device_desc,
    is_tpu,
    is_tpu_device,
)

REPO = Path(__file__).resolve().parents[1]


def dev(platform, kind=""):
    return SimpleNamespace(platform=platform, device_kind=kind)


@pytest.mark.parametrize(
    "platform,kind",
    [
        ("tpu", "TPU v5e"),
        ("TPU", ""),
        ("tpu", "TPU v5 lite"),
        ("tpu", ""),  # even with no device_kind
    ],
)
def test_tpu_platform_is_tpu(platform, kind):
    assert is_tpu_device(dev(platform, kind))


def test_unknown_plugin_detected_via_device_kind():
    assert is_tpu_device(dev("someplugin", "TPU v6e"))


def test_cpu_and_gpu_are_not_tpu():
    assert not is_tpu_device(dev("cpu", "cpu"))
    assert not is_tpu_device(dev("cuda", "NVIDIA H100"))
    assert not is_tpu_device(dev("cpu", None))


def test_is_tpu_under_forced_cpu_platform():
    # conftest forces the virtual-CPU platform for the whole test process.
    assert is_tpu() is False


def test_device_desc():
    assert device_desc(dev("tpu", "TPU v5e")) == "tpu:TPU v5e"
    assert device_desc(dev("cpu", None)) == "cpu:?"


# A fresh process turns the cache on and compiles one program named for its
# tag, with the tag as a constant: a key, and entry names, that no other
# test or case writes, so cases running side by side cannot see each
# other's entries in the shared in-checkout cache.
_PROBE = """
import sys
import jax, jax.numpy as jnp
from bitcoin_miner_tpu.utils.platform import enable_compile_cache
print(enable_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
tag = int(sys.argv[1])


def cache_probe(x):
    return x * tag + 7


cache_probe.__name__ = cache_probe.__qualname__ = f"cache_probe_{tag}"
jax.jit(cache_probe).lower(jnp.zeros(3, jnp.int32)).compile()
"""


def _tag(tmp_path) -> str:
    return str(abs(hash(tmp_path)) % 10**9)


def _entries(d: Path, tag: str, before=frozenset()):
    if not d.is_dir():
        return set()
    return {p.name for p in d.glob(f"jit_cache_probe_{tag}-*")} - set(before)


def _probe(tag, env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    p = subprocess.run(
        [sys.executable, "-c", _PROBE, tag],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=env,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    return p.stdout.strip().splitlines()[-1]


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_dir(tmp_path, env_set):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, entries land there and not
    in the checkout; unset, they land in the fixed in-checkout directory,
    which git ignores."""
    outside = tmp_path / "cache"
    tag = _tag(tmp_path)
    in_repo_before = _entries(REPO_COMPILE_CACHE, tag)
    said = _probe(tag, outside if env_set else None)
    new_in_repo = _entries(REPO_COMPILE_CACHE, tag, in_repo_before)
    if env_set:
        assert said == str(outside)
        new = _entries(outside, tag)
        assert new, "nothing was cached in JAX_COMPILATION_CACHE_DIR"
        assert not new_in_repo, "an entry landed in the checkout too"
    else:
        assert said == str(REPO_COMPILE_CACHE) == str(REPO / ".jax_cache")
        assert not outside.exists()
        assert new_in_repo, "nothing was cached in the checkout's .jax_cache"
        assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
