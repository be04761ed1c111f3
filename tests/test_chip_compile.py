"""Compile the main path's kernels for a described TPU v5e, with no chip.

The TPU compiler is installed here and compiles for a ``v5e:2x2``
topology that is described, not attached (``jax.experimental.topologies``).
What Mosaic or the SPMD partitioner would refuse on the chip, they refuse
here, at no chip time.  Nothing runs, so these tests say nothing about
results or speed: ``chip_smoke.py`` on the chip does that.

The topology is described inside a module fixture and nowhere else: only
one process at a time may load the TPU library, and under pytest-xdist
every worker imports this file.  Keep these tests in this one file.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from bitcoin_miner_tpu.ops.pallas_sha256 import dyn_params, make_pallas_minhash_dyn
from bitcoin_miner_tpu.ops.sha256 import build_layout
from bitcoin_miner_tpu.ops.sweep import auto_tune, decompose_range
from bitcoin_miner_tpu.parallel.sweep import _make_sharded_kernel_dyn

# The flagship job shape (BASELINE.json configs 2, 3 and 5): data
# 'cmu440', the d=10 class a long job spends nearly all its lanes in.
DATA = b"cmu440"


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(autouse=True)
def no_compile_cache():
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _production_shape():
    """The pallas tier's kernel as ``auto_tune`` resolves it, for d=10."""
    backend, batch, max_k, sieve, factored, _hot = auto_tune("pallas", None, None)
    assert (backend, factored) == ("pallas", False), "the dyn kernel is the default"
    group = next(decompose_range(10**9, 10**9 + 10**8, max_k=max_k))
    assert group.d == 10
    layout = build_layout(DATA, group.d)
    w_lo, w_hi = dyn_params(layout, group.k)
    return batch, sieve, group, layout, w_lo, w_hi


def test_single_chip_dyn_kernel_compiles_for_v5e(topo):
    batch, sieve, group, layout, w_lo, w_hi = _production_shape()
    assert sieve, "auto_tune turns the sieve on for pallas"
    fn, n_pad = make_pallas_minhash_dyn(
        layout.n_tail_blocks, w_lo, w_hi, group.k, batch, sieve=sieve
    )
    one = SingleDeviceSharding(topo.devices[0])
    nw = len(layout.tail_template)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    compiled = fn.lower(
        s((8,), jnp.uint32),
        s((batch, nw + 2), jnp.uint32),
        s((1,), jnp.int32),
        *(s((n_pad // 128, 128), jnp.uint32) for _ in range(w_hi - w_lo + 1)),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_dyn_kernel_compiles_for_v5e_mesh(topo):
    batch, sieve, group, layout, w_lo, w_hi = _production_shape()
    n_dev = len(topo.devices)
    assert n_dev == 4
    mesh = Mesh(np.array(topo.devices).reshape(n_dev), ("miners",))
    kern, n_pad = _make_sharded_kernel_dyn(
        layout.n_tail_blocks, w_lo, w_hi, group.k, batch, mesh, "miners",
        False, sieve=sieve,
    )
    row = NamedSharding(mesh, P("miners", None))
    rep = NamedSharding(mesh, P())
    rep2 = NamedSharding(mesh, P(None, None))
    nw = len(layout.tail_template)
    rows = n_dev * batch
    compiled = kern.lower(
        jax.ShapeDtypeStruct((8,), jnp.uint32, sharding=rep),
        jax.ShapeDtypeStruct((rows, nw), jnp.uint32, sharding=row),
        jax.ShapeDtypeStruct((rows, 2), jnp.int32, sharding=row),
        *((jax.ShapeDtypeStruct((), jnp.uint32, sharding=rep),) if sieve else ()),
        *(
            jax.ShapeDtypeStruct((n_pad // 128, 128), jnp.uint32, sharding=rep2)
            for _ in range(w_hi - w_lo + 1)
        ),
    ).compile()
    txt = compiled.as_text()
    assert "all-reduce" in txt, "the collective min did not become a collective"
    assert "tpu_custom_call" in txt
