"""The store of exported pallas dyn kernels (ops/kernel_store.py).

On the CPU the pallas kernels run in interpret mode, where the sweep drivers
never engage the store (it serves the Mosaic lowering only).  So these tests
call the store directly on an interpret-mode dyn kernel, single-device or
sharded over a mesh of virtual CPU devices, or steer the drivers' pallas dyn
branch onto it by patching the platform probe and the kernel factory, with
every export stored under ``tmp_path``.
"""

import hashlib

import json
import os
import subprocess
import sys
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bitcoin_miner_tpu.bitcoin import min_hash_range
from bitcoin_miner_tpu.ops import kernel_store, pallas_sha256
from bitcoin_miner_tpu.ops.kernel_store import (
    MESH_SOURCES,
    StoredKernel,
    stored_kernel,
)
from bitcoin_miner_tpu.ops.pallas_sha256 import (
    DEFAULT_TILE,
    dyn_params,
    make_pallas_minhash_dyn,
    resolve_cpb,
    window_contribs_np,
)
from bitcoin_miner_tpu.ops.sha256 import build_layout
from bitcoin_miner_tpu.ops.sweep import (
    U32_MAX,
    MeshRows,
    SweepPipeline,
    _fill_templates,
    decompose_range,
    sweep_min_hash,
)
from bitcoin_miner_tpu.parallel import sweep as psweep
from bitcoin_miner_tpu.utils.metrics import METRICS

REPO = Path(__file__).resolve().parents[1]
DATA = "cmu440"
# One d=4 class at k=2: 4 chunks of 100 nonces.
LO, HI = 1000, 1399


def _kernel(batch=2, sieve=True, groups=1, cpb=None):
    """A small interpret-mode dyn kernel (d=4, k=2), its factory's
    parameters, and one dispatch's operands: ``batch`` rows, the grid over
    ``groups`` row groups of ``cpb``."""
    group = next(decompose_range(LO, LO + 100 * batch - 1, max_k=2))
    layout = build_layout(DATA.encode(), group.d)
    w_lo, w_hi = dyn_params(layout, group.k)
    params = dict(
        n_tail_blocks=layout.n_tail_blocks, w_lo=w_lo, w_hi=w_hi, k=group.k,
        batch=batch, tile=DEFAULT_TILE, cpb=cpb, sieve=sieve,
    )
    fn, n_pad = make_pallas_minhash_dyn(**params, interpret=True)
    tail_const, bounds = _fill_templates(layout, group, group.chunks, batch)
    tailcb = np.concatenate([tail_const, bounds.astype(np.uint32)], axis=1)
    # The sieve threshold U32_MAX, sign-flipped as the kernel wants it.
    th = (np.array([0x7FFFFFFF], dtype=np.int32),) if sieve else ()
    low_pos = layout.digit_pos[layout.digit_count - group.k :]
    args = [
        np.array(layout.midstate, dtype=np.uint32), tailcb, np.int32(groups),
        *th,
        *window_contribs_np(group.k, low_pos, w_lo, w_hi, n_pad),
    ]
    return fn, params, [jnp.asarray(a) for a in args]


def _ints(out):
    return [int(np.asarray(x)) for x in out]


def _counts():
    return (
        METRICS.get("sweep.kernel_export_hits"),
        METRICS.get("sweep.kernel_export_misses"),
    )


def _delta(before):
    now = _counts()
    return now[0] - before[0], now[1] - before[1]


def _exports(directory):
    return sorted(Path(directory).glob("*.jaxexport"))


@pytest.fixture
def production_store(tmp_path, monkeypatch):
    """The sweep drivers' pallas dyn branch as it runs under Mosaic, on an
    interpret-mode kernel, storing under ``tmp_path``."""
    from bitcoin_miner_tpu.ops import sweep

    real = pallas_sha256.make_pallas_minhash_dyn

    def interpreted(*a, interpret, **kw):
        return real(*a, interpret=True, **kw)

    monkeypatch.setattr(sweep, "pallas_platform", lambda: "mosaic")
    monkeypatch.setattr(pallas_sha256, "make_pallas_minhash_dyn", interpreted)
    monkeypatch.setattr(kernel_store, "store_dir", lambda: tmp_path)
    stored_kernel.cache_clear()
    yield tmp_path
    stored_kernel.cache_clear()


def _sweep():
    return sweep_min_hash(
        DATA, LO, HI, backend="pallas", interpret=False, batch=2, max_k=2
    )


_CHILD = """
import json, sys
sys.path[:0] = [{repo!r}, {tests!r}]
from bitcoin_miner_tpu.utils.platform import force_virtual_cpu
force_virtual_cpu(1)
from test_kernel_export import _counts, _ints, _kernel
from bitcoin_miner_tpu.ops.kernel_store import StoredKernel
_fn, params, args = _kernel()
# No kernel to trace: only the stored export can serve this call.
out = StoredKernel(None, params, sys.argv[1])(*args)
print(json.dumps({{"out": _ints(out), "counts": _counts()}}))
"""


def test_export_loads_in_fresh_process_bit_identical(tmp_path):
    fn, params, args = _kernel()
    traced = _ints(fn(*args))
    before = _counts()
    assert _ints(StoredKernel(fn, params, tmp_path)(*args)) == traced
    assert _delta(before) == (0, 1)
    assert len(_exports(tmp_path)) == 1
    child = subprocess.run(
        [sys.executable, "-c",
         _CHILD.format(repo=str(REPO), tests=str(REPO / "tests")),
         str(tmp_path)],
        capture_output=True, text=True, timeout=180,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert child.returncode == 0, child.stderr[-3000:]
    got = json.loads(child.stdout.strip().splitlines()[-1])
    assert got == {"out": traced, "counts": [1, 0]}


@pytest.mark.parametrize(
    "part", ["same", "batch", "sieve", "source_digest", "jax_version"]
)
def test_each_key_part_misses_when_changed(part, tmp_path, monkeypatch):
    fn, params, args = _kernel()
    StoredKernel(fn, params, tmp_path)(*args)
    if part == "batch":
        fn, params, args = _kernel(batch=4)
    elif part == "sieve":
        fn, params, args = _kernel(sieve=False)
    elif part == "source_digest":
        digest = kernel_store.source_digest()
        monkeypatch.setattr(
            kernel_store, "source_digest", lambda extra=(): "0" * len(digest)
        )
    elif part == "jax_version":
        versions = kernel_store.runtime_versions()
        monkeypatch.setattr(
            kernel_store, "runtime_versions",
            lambda: {**versions, "jax": versions["jax"] + ".post1"},
        )
    before = _counts()
    out = _ints(StoredKernel(fn, params, tmp_path)(*args))
    assert out == _ints(fn(*args))
    if part == "same":
        assert _delta(before) == (1, 0)
        assert len(_exports(tmp_path)) == 1
    else:
        assert _delta(before) == (0, 1)
        assert len(_exports(tmp_path)) == 2


@pytest.mark.parametrize(
    "module", ["pallas_sha256.py", "sha256.py", "parallel/sweep.py"]
)
def test_source_digest_follows_each_traced_module(module, tmp_path, monkeypatch):
    """An edit to any module traced into the kernel changes the digest,
    and so the key (see the ``source_digest`` case above): the kernel's
    own modules for every kernel, the shard_map and cascade around it for
    the sharded one."""
    ops = Path(kernel_store.__file__).parent
    own = module in kernel_store._SOURCES
    assert own or module in MESH_SOURCES
    extra = () if own else MESH_SOURCES
    for name in kernel_store._SOURCES:
        (tmp_path / "ops").mkdir(exist_ok=True)
        (tmp_path / "ops" / name).write_bytes((ops / name).read_bytes())
    for name in MESH_SOURCES:
        (tmp_path / name).parent.mkdir(parents=True, exist_ok=True)
        (tmp_path / name).write_bytes((ops.parent / name).read_bytes())
    monkeypatch.setattr(
        kernel_store, "__file__", str(tmp_path / "ops" / "kernel_store.py")
    )
    digest = kernel_store.source_digest.__wrapped__
    assert digest(extra) == kernel_store.source_digest(extra)
    with open(tmp_path / ("ops" if own else "") / module, "a") as f:
        f.write("\n# an edit\n")
    assert digest(extra) != kernel_store.source_digest(extra)


def test_one_export_serves_every_group_count(tmp_path):
    """The grid's row-group extent is an operand of the stored kernel, not
    part of its key: exported once, and loaded where no kernel can be
    traced, it answers at one group and at two as the jitted kernel does."""
    runs = {g: _kernel(batch=4, cpb=2, groups=g) for g in (1, 2)}
    fn, params, _ = runs[1]
    traced = {g: _ints(fn(*args)) for g, (_f, _p, args) in runs.items()}
    h0, h1, idx = traced[2]
    assert ((h0 << 32) | h1, LO + idx) == min_hash_range(DATA, LO, LO + 399)

    before = _counts()
    stored = StoredKernel(fn, params, tmp_path)
    assert {g: _ints(stored(*a)) for g, (_f, _p, a) in runs.items()} == traced
    assert _delta(before) == (0, 1)
    before = _counts()
    loaded = StoredKernel(None, params, tmp_path)
    assert {g: _ints(loaded(*a)) for g, (_f, _p, a) in runs.items()} == traced
    assert _delta(before) == (1, 0)
    assert len(_exports(tmp_path)) == 1


def test_grid_group_counters_add_up_to_the_full_grid(production_store):
    """Each dispatch of the stored dyn kernel counts the row groups its
    grid ran and those of the full ``batch // cpb`` grid it skipped: 9
    rows at batch 6, cpb 2 are a full dispatch (3 groups) and one of 3
    rows (2 groups, 1 skipped)."""
    names = ("sweep.grid_groups", "sweep.grid_groups_skipped")
    before = [METRICS.get(n) for n in names]
    r = sweep_min_hash(
        DATA, LO, LO + 899, backend="pallas", interpret=False, batch=6,
        cpb=2, max_k=2,
    )
    assert (r.hash, r.nonce) == min_hash_range(DATA, LO, LO + 899)
    ran, skipped = (METRICS.get(n) - b for n, b in zip(names, before))
    assert (ran, skipped) == (5, 1)
    assert ran + skipped == 2 * (6 // 2)  # two dispatches of the full grid


def test_store_sits_beside_the_compile_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert kernel_store.store_dir() == tmp_path / "kernel_exports"


def test_truncated_export_is_a_counted_miss_and_rewritten(production_store):
    want = min_hash_range(DATA, LO, HI)
    before = _counts()
    r = _sweep()
    assert (r.hash, r.nonce) == want
    assert _delta(before) == (0, 1)
    (path,) = _exports(production_store)
    whole = path.read_bytes()
    path.write_bytes(whole[: len(whole) // 2])

    stored_kernel.cache_clear()  # what a fresh process starts from
    before = _counts()
    r = _sweep()
    assert (r.hash, r.nonce) == want
    assert _delta(before) == (0, 1)
    assert _exports(production_store) == [path]
    assert path.stat().st_size == len(whole)

    stored_kernel.cache_clear()
    before = _counts()
    r = _sweep()
    assert (r.hash, r.nonce) == want
    assert _delta(before) == (1, 0)


def test_prewarm_racing_dispatch_writes_one_export(production_store, monkeypatch):
    # Widen the race: the export takes a beat longer than the dispatcher
    # needs to reach the same cold class.
    exported = StoredKernel._exported

    def slow(self, args):
        threading.Event().wait(0.5)
        return exported(self, args)

    monkeypatch.setattr(StoredKernel, "_exported", slow)
    p = SweepPipeline(
        backend="pallas", interpret=False, batch=2, max_k=2, host_lane_budget=0
    )
    before = _counts()
    try:
        assert p.prewarm_async(DATA, len(str(LO)))
        r = p.submit(DATA, LO, HI).result(timeout=180)
    finally:
        p.close()
    assert (r.hash, r.nonce) == min_hash_range(DATA, LO, HI)
    assert _delta(before) == (0, 1)
    assert len(_exports(production_store)) == 1


def test_concurrent_first_calls_export_once(tmp_path):
    fn, params, args = _kernel()
    traced = _ints(fn(*args))
    kern = StoredKernel(fn, params, tmp_path)
    n = 16
    start = threading.Barrier(n)
    outs, errors = [], []

    def first_call():
        try:
            start.wait(timeout=60)
            outs.append(_ints(kern(*args)))
        except Exception as e:  # reported below
            errors.append(e)

    before = _counts()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=first_call) for _ in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors
    assert outs == [traced] * n
    assert _delta(before) == (0, 1)
    assert len(_exports(tmp_path)) == 1


# --------------------------------------------------------------------------
# The sharded dyn kernel (parallel/sweep.py) on a mesh of virtual CPU devices
# --------------------------------------------------------------------------


def _mesh(n=4, shape=None, axes=("miners",)):
    devices = np.array(jax.devices()[:n])
    return Mesh(devices.reshape(shape or (n,)), axes)


def _sharded_kernel(mesh, axis="miners", batch=2):
    """A small interpret-mode sharded dyn kernel on ``mesh`` (d=4, k=2, the
    sieve on), the parameters the drivers' pallas dyn branch keys it by,
    one dispatch's operands placed as the pipeline places them, and the
    map from a winning ``(dev, flat)`` back to its nonce."""
    group = next(decompose_range(LO, HI, max_k=2))
    layout = build_layout(DATA.encode(), group.d)
    w_lo, w_hi = dyn_params(layout, group.k)
    fn, n_pad = psweep._make_sharded_kernel_dyn(
        layout.n_tail_blocks, w_lo, w_hi, group.k, batch, mesh, axis, True,
        sieve=True,
    )
    rows = MeshRows(len(group.chunks), mesh.size)
    tail_const, bounds = _fill_templates(
        layout, group, group.chunks, mesh.size * batch, rows.slots(batch)
    )
    ops = psweep.shard_operands(
        np.array(layout.midstate, dtype=np.uint32), tail_const, bounds,
        mesh, axis,
    )
    rep = NamedSharding(mesh, P())
    groups = jax.device_put(
        np.int32(-(-max(rows.counts()) // resolve_cpb(batch))), rep
    )
    thresh = jax.device_put(np.uint32(U32_MAX), rep)
    low_pos = layout.digit_pos[layout.digit_count - group.k :]
    contribs = psweep._mesh_contribs(group.k, low_pos, w_lo, w_hi, n_pad, mesh)
    params = dict(
        n_tail_blocks=layout.n_tail_blocks, w_lo=w_lo, w_hi=w_hi, k=group.k,
        per_dev_batch=batch, sieve=True, n_devices=mesh.size,
        mesh_shape=tuple(mesh.devices.shape),
        axis_names=tuple(mesh.axis_names), axis_name=axis,
    )

    def nonce(dev, flat):
        local, lane = divmod(flat, 10**group.k)
        return group.chunks[rows.row(dev, local)].base + lane

    return fn, params, [*ops, groups, thresh, *contribs], nonce


def _answer(out, nonce):
    """``(hash, nonce)`` of a sharded kernel's ``(h0, h1, dev, flat)``."""
    h0, h1, dev, flat = _ints(out)
    return (h0 << 32) | h1, nonce(dev, flat)


_SHARDED_CHILD = """
import json, sys
sys.path[:0] = [{repo!r}, {tests!r}]
from bitcoin_miner_tpu.utils.platform import force_virtual_cpu
force_virtual_cpu(4)
import jax
from test_kernel_export import _counts, _ints, _mesh, _sharded_kernel
from bitcoin_miner_tpu.ops.kernel_store import MESH_SOURCES, StoredKernel
assert jax.device_count() == 4
_fn, params, args, _nonce = _sharded_kernel(_mesh(4))
# No kernel to trace: only the stored export can serve this call.
out = StoredKernel(None, params, sys.argv[1], MESH_SOURCES)(*args)
print(json.dumps({{"out": _ints(out), "counts": _counts()}}))
"""


def test_sharded_export_loads_in_fresh_process_bit_identical(tmp_path):
    fn, params, args, nonce = _sharded_kernel(_mesh(4))
    traced = _ints(fn(*args))
    assert _answer(traced, nonce) == min_hash_range(DATA, LO, HI)
    before = _counts()
    kern = StoredKernel(fn, params, tmp_path, MESH_SOURCES)
    assert _ints(kern(*args)) == traced
    assert _delta(before) == (0, 1)
    (path,) = _exports(tmp_path)
    from jax import export as jax_export

    exp = jax_export.deserialize(bytearray(path.read_bytes()[32:]))
    assert exp.nr_devices == 4
    assert all(s is not None for s in exp.in_shardings_hlo)
    child = subprocess.run(
        [sys.executable, "-c",
         _SHARDED_CHILD.format(repo=str(REPO), tests=str(REPO / "tests")),
         str(tmp_path)],
        capture_output=True, text=True, timeout=180,
        env={
            **os.environ, "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        },
    )
    assert child.returncode == 0, child.stderr[-3000:]
    got = json.loads(child.stdout.strip().splitlines()[-1])
    assert got == {"out": traced, "counts": [1, 0]}


@pytest.fixture
def production_mesh_store(tmp_path, monkeypatch):
    """The sharded pallas dyn branch as it runs under Mosaic, on an
    interpret-mode kernel, storing under ``tmp_path``."""
    real = pallas_sha256.make_pallas_minhash_dyn

    def interpreted(*a, interpret, **kw):
        return real(*a, interpret=True, **kw)

    monkeypatch.setattr(psweep, "pallas_platform", lambda: "mosaic")
    monkeypatch.setattr(pallas_sha256, "make_pallas_minhash_dyn", interpreted)
    monkeypatch.setattr(kernel_store, "store_dir", lambda: tmp_path)
    stored_kernel.cache_clear()
    psweep._make_sharded_kernel_dyn.cache_clear()
    yield tmp_path
    stored_kernel.cache_clear()
    psweep._make_sharded_kernel_dyn.cache_clear()


def _sharded_sweep(mesh, axis="miners"):
    return psweep.sweep_min_hash_sharded(
        DATA, LO, HI, mesh=mesh, axis_name=axis, backend="pallas",
        interpret=False, batch_per_device=2, max_k=2,
    )


#: Meshes that differ from ``_mesh(4)`` in one part of the store's key.
_MESHES = {
    "same": lambda: (_mesh(4), "miners"),
    "n_devices": lambda: (_mesh(2), "miners"),
    "mesh_shape": lambda: (_mesh(4, (4, 1), ("miners", "spare")), "miners"),
    "axis_name": lambda: (_mesh(4, axes=("chips",)), "chips"),
}


@pytest.mark.parametrize("part", sorted(_MESHES))
def test_each_mesh_key_part_misses_when_changed(part, production_mesh_store):
    want = min_hash_range(DATA, LO, HI)
    before = _counts()
    r = _sharded_sweep(_mesh(4))
    assert (r.hash, r.nonce) == want
    assert _delta(before) == (0, 1)

    # What a fresh process starts from: no kernel built yet.
    stored_kernel.cache_clear()
    psweep._make_sharded_kernel_dyn.cache_clear()
    mesh, axis = _MESHES[part]()
    before = _counts()
    r = _sharded_sweep(mesh, axis)
    assert (r.hash, r.nonce) == want
    if part == "same":
        assert _delta(before) == (1, 0)
        assert len(_exports(production_mesh_store)) == 1
    else:
        assert _delta(before) == (0, 1)
        assert len(_exports(production_mesh_store)) == 2


def test_multiprocess_mesh_keeps_the_plain_jit(production_mesh_store, monkeypatch):
    """A mesh that spans processes builds the sharded kernel with the
    plain jit, and the store is neither read nor written."""
    monkeypatch.setattr(psweep.jax, "process_count", lambda: 2)
    group = next(decompose_range(LO, HI, max_k=2))
    layout = build_layout(DATA.encode(), group.d)
    mesh = _mesh(4)
    kern = psweep.sharded_kernel_for(
        layout, group, 2, mesh, "miners", "pallas", False, False, sieve=True
    )
    assert not isinstance(kern.class_key, StoredKernel)
    before = _counts()
    r = _sharded_sweep(mesh)
    assert (r.hash, r.nonce) == min_hash_range(DATA, LO, HI)
    assert _delta(before) == (0, 0)
    assert _exports(production_mesh_store) == []


def test_single_device_key_is_unchanged(monkeypatch):
    """The stores that one chip has written still hit: for fixed inputs
    the key of single-device operands, with or without their sharding, is
    the digest it has always been, and the source digest is the same
    sha256 over the same two modules."""
    ops = Path(kernel_store.__file__).parent
    h = hashlib.sha256()
    for name in ("pallas_sha256.py", "sha256.py"):
        h.update(name.encode())
        h.update((ops / name).read_bytes())
    assert kernel_store.source_digest() == h.hexdigest()

    monkeypatch.setattr(
        kernel_store, "runtime_versions",
        lambda: {"jax": "0.0.0", "jaxlib": "0.0.0"},
    )
    monkeypatch.setattr(kernel_store, "source_digest", lambda extra=(): "0" * 64)
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    params = dict(
        n_tail_blocks=1, w_lo=2, w_hi=3, k=2, batch=16, tile=8, cpb=None,
        sieve=True,
    )
    shapes = [((8,), jnp.uint32), ((16, 18), jnp.uint32), ((1,), jnp.int32)]
    for sharding in (None, one):
        specs = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
        assert kernel_store.export_key(params, specs) == (
            "29e1b789e8f6969ac3e0df109bdad306ac95fa77ef4cf804ccaa0db98128490e"
        )
