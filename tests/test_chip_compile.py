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

import base64
import os
import re

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


def _production_shape(n_devices=1):
    """The pallas tier's kernel as ``auto_tune`` resolves it, for d=10, with
    ``batch`` the rows of each of ``n_devices`` devices."""
    backend, batch, max_k, sieve, factored = auto_tune(
        "pallas", None, None, n_devices=n_devices
    )
    assert (backend, factored) == ("pallas", False), "the dyn kernel is the default"
    group = next(decompose_range(10**9, 10**9 + 10**8, max_k=max_k))
    assert group.d == 10
    layout = build_layout(DATA, group.d)
    w_lo, w_hi = dyn_params(layout, group.k)
    return batch, sieve, group, layout, w_lo, w_hi


def _single_chip_dyn(topo):
    """The production dyn kernel and its operands on one described chip."""
    batch, sieve, group, layout, w_lo, w_hi = _production_shape()
    assert sieve, "auto_tune turns the sieve on for pallas"
    fn, n_pad = make_pallas_minhash_dyn(
        layout.n_tail_blocks, w_lo, w_hi, group.k, batch, sieve=sieve
    )
    one = SingleDeviceSharding(topo.devices[0])
    nw = len(layout.tail_template)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    specs = [
        s((8,), jnp.uint32),
        s((batch, nw + 2), jnp.uint32),
        s((), jnp.int32),  # the grid's row-group extent
        s((1,), jnp.int32),
        *(s((n_pad // 128, 128), jnp.uint32) for _ in range(w_hi - w_lo + 1)),
    ]
    return fn, specs


def _mosaic_kernels(hlo_text):
    """The Mosaic module of every ``tpu_custom_call``, as MLIR text with no
    locations and its serialization version masked: an export writes the
    forward-compatible version, a kernel traced in the process the newest,
    and the compiler upgrades the former as it reads it."""
    from jax._src.lib.mlir import ir

    out = []
    for body in re.findall(
        r'custom_call_target="tpu_custom_call".*?"body":"([^"]*)"', hlo_text
    ):
        with ir.Context() as ctx:
            ctx.allow_unregistered_dialects = True  # the serialized form
            module = ir.Module.parse(base64.b64decode(body))
            asm = module.operation.get_asm(enable_debug_info=False)
        out.append(re.sub(r"stable_mosaic\.version = \d+", "", asm))
    return out


def test_single_chip_dyn_kernel_compiles_for_v5e(topo):
    fn, specs = _single_chip_dyn(topo)
    compiled = fn.lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_stored_dyn_kernel_export_compiles_for_v5e(topo):
    """What a fresh miner process runs on a store hit: the production dyn
    kernel exported for the TPU, serialized, deserialized, and its
    ``call`` compiled for the chip.  It holds the same Mosaic kernel as
    the kernel traced in the process."""
    from jax import export

    fn, specs = _single_chip_dyn(topo)
    plain = [jax.ShapeDtypeStruct(s.shape, s.dtype) for s in specs]
    blob = export.export(fn, platforms=["tpu"])(*plain).serialize()
    exp = export.deserialize(blob)
    assert exp.platforms == ("tpu",)
    stored = jax.jit(exp.call).lower(*specs).compile().as_text()
    traced = fn.lower(*specs).compile().as_text()
    kernels = _mosaic_kernels(stored)
    assert kernels, "no tpu_custom_call in the stored kernel's executable"
    assert kernels == _mosaic_kernels(traced)


def _sharded_dyn(topo):
    """The production sharded dyn kernel and its operands, placed as
    ``parallel.sweep.shard_operands`` places them, on the described mesh."""
    n_dev = len(topo.devices)
    assert n_dev == 4
    batch, sieve, group, layout, w_lo, w_hi = _production_shape(n_dev)
    assert n_dev * batch == 1024, "a mesh dispatch holds 1024 slots in all"
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
    specs = [
        jax.ShapeDtypeStruct((8,), jnp.uint32, sharding=rep),
        jax.ShapeDtypeStruct((rows, nw), jnp.uint32, sharding=row),
        jax.ShapeDtypeStruct((rows, 2), jnp.int32, sharding=row),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),  # row groups
        *((jax.ShapeDtypeStruct((), jnp.uint32, sharding=rep),) if sieve else ()),
        *(
            jax.ShapeDtypeStruct((n_pad // 128, 128), jnp.uint32, sharding=rep2)
            for _ in range(w_hi - w_lo + 1)
        ),
    ]
    return kern, specs


def test_sharded_dyn_kernel_compiles_for_v5e_mesh(topo):
    kern, specs = _sharded_dyn(topo)
    txt = kern.lower(*specs).compile().as_text()
    assert "all-reduce" in txt, "the collective min did not become a collective"
    assert "tpu_custom_call" in txt


def test_stored_sharded_dyn_kernel_export_compiles_for_v5e_mesh(topo):
    """What a fresh four-chip miner runs on a store hit: the production
    sharded dyn kernel exported for the TPU with its operands' shardings,
    serialized, deserialized, and its ``call`` compiled for the mesh.  It
    keeps the collective cascade and holds the same Mosaic kernel as the
    sharded kernel traced in the process."""
    from jax import export

    kern, specs = _sharded_dyn(topo)
    blob = export.export(kern, platforms=["tpu"])(*specs).serialize()
    exp = export.deserialize(blob)
    assert exp.platforms == ("tpu",)
    assert exp.nr_devices == 4
    stored = jax.jit(exp.call).lower(*specs).compile().as_text()
    traced = kern.lower(*specs).compile().as_text()
    assert "all-reduce" in stored, "the stored kernel lost the collective min"
    kernels = _mosaic_kernels(stored)
    assert kernels, "no tpu_custom_call in the stored kernel's executable"
    assert kernels == _mosaic_kernels(traced)
