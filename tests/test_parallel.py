"""Multi-chip sharding: shard_map sweep + collective min on the virtual
8-device CPU mesh (SURVEY §2.3 — the ICI plane).

The sharded path is validated three ways:
- the xla tier (identical sharding structure + collective cascade) on the
  CPU mesh,
- the *Pallas* tier in interpret mode on the same mesh (the round-4 claim
  that interpret mode deadlocks XLA:CPU's collective rendezvous does not
  reproduce on jax 0.9.0 — both a minimal shard_map+pallas+pmin repro and
  the full kernel run clean, so the flagship tier is now oracle-checked
  sharded),
- AOT: the production config (Pallas under shard_map + pmin cascade)
  lowered and Mosaic-compiled against a described 4-device v5e:2x2 TPU
  topology (no chips needed) in test_chip_compile.py.
The driver's dryrun_multichip runs the first two.
"""

import jax
import pytest

from bitcoin_miner_tpu.bitcoin.hash import min_hash_range
from bitcoin_miner_tpu.parallel import default_mesh, sweep_min_hash_sharded


def test_mesh_has_8_devices():
    assert len(jax.devices()) == 8
    mesh = default_mesh()
    assert mesh.devices.size == 8


def test_sharded_matches_oracle_single_group():
    # One digit group (d=4, k=2) -> one kernel compile; 13 chunks pad across
    # 8 devices x batch 2, exercising padded-row masking.
    r = sweep_min_hash_sharded(
        "cmu440", 1000, 2234, backend="xla", max_k=2, batch_per_device=2
    )
    assert (r.hash, r.nonce) == min_hash_range("cmu440", 1000, 2234)
    assert r.lanes_swept == 2234 - 1000 + 1


def test_sharded_matches_oracle_digit_boundary():
    r = sweep_min_hash_sharded(
        "x", 95, 305, backend="xla", max_k=1, batch_per_device=2
    )
    assert (r.hash, r.nonce) == min_hash_range("x", 95, 305)


def test_sharded_subset_mesh():
    mesh = default_mesh(2)
    r = sweep_min_hash_sharded(
        "cmu440", 1000, 1999, mesh=mesh, backend="xla", max_k=2, batch_per_device=2
    )
    assert (r.hash, r.nonce) == min_hash_range("cmu440", 1000, 1999)


def test_sharded_pallas_interpret_matches_oracle():
    # The flagship tier, sharded: Pallas kernel (interpret mode — Mosaic
    # itself needs a TPU) under shard_map + the pmin cascade, 8 devices.
    # Bit-exactness proves the kernel's in-VMEM running-min composes with
    # the cross-device collective min, including lowest-nonce tie-break.
    r = sweep_min_hash_sharded(
        "cmu440", 1000, 2234, backend="pallas", interpret=True,
        max_k=2, batch_per_device=2,
    )
    assert (r.hash, r.nonce) == min_hash_range("cmu440", 1000, 2234)
    assert r.lanes_swept == 2234 - 1000 + 1


def test_sharded_pallas_interpret_digit_boundary():
    # Crosses a digit-count boundary -> two kernel shapes, both sharded.
    r = sweep_min_hash_sharded(
        "x", 95, 305, backend="pallas", interpret=True,
        max_k=1, batch_per_device=2,
    )
    assert (r.hash, r.nonce) == min_hash_range("x", 95, 305)


def test_sharded_per_shard_sieve_matches_oracle():
    # Per-shard sieve (ISSUE 14 satellite): the sharded tier no longer
    # forces the baseline kernel — each shard's pass 1 seeds from the
    # replicated dispatch threshold ahead of the collective argmin
    # cascade, and survivor-less shards contribute the sentinel the
    # cascade orders last.  batch_per_device=2 over 8 devices with a
    # digit-boundary range: later dispatches carry a tightened running
    # min, so most shards prune to the sentinel and the fold must STILL
    # be bit-exact, lowest-nonce ties included.
    r = sweep_min_hash_sharded(
        "cmu440", 1000, 2234, backend="xla", max_k=2, batch_per_device=2,
        sieve=True,
    )
    assert (r.hash, r.nonce) == min_hash_range("cmu440", 1000, 2234)
    assert r.lanes_swept == 2234 - 1000 + 1


def test_sharded_per_shard_sieve_digit_boundary():
    r = sweep_min_hash_sharded(
        "x", 95, 305, backend="xla", max_k=1, batch_per_device=2, sieve=True
    )
    assert (r.hash, r.nonce) == min_hash_range("x", 95, 305)


def test_sharded_pallas_interpret_per_shard_sieve():
    # The flagship sharded composition: the dyn pallas SIEVE kernel under
    # shard_map — each shard tightens its own local running min in SMEM
    # scratch (the "per-shard local running-min") before the pmin cascade.
    r = sweep_min_hash_sharded(
        "cmu440", 1000, 2234, backend="pallas", interpret=True,
        max_k=2, batch_per_device=2, sieve=True,
    )
    assert (r.hash, r.nonce) == min_hash_range("cmu440", 1000, 2234)


def test_mesh_pipeline_per_shard_sieve_matches_oracle():
    # SweepPipeline mesh mode threads the enqueue-time running-min into
    # every sharded dispatch (sieve no longer pinned off in mesh mode).
    from bitcoin_miner_tpu.ops.sweep import SweepPipeline

    p = SweepPipeline(
        backend="xla", mesh=default_mesh(8), max_k=2, batch=2,
        host_lane_budget=0, sieve=True,
    )
    try:
        futs = [
            p.submit("cmu440", 1000, 2234),
            p.submit("cmu440", 2235, 3499),
        ]
        wants = [("cmu440", 1000, 2234), ("cmu440", 2235, 3499)]
        for f, (d, lo, hi) in zip(futs, wants):
            r = f.result(timeout=300)
            assert (r.hash, r.nonce) == min_hash_range(d, lo, hi), (d, lo, hi)
    finally:
        p.close()


def test_sharded_factored_matches_oracle():
    # Factored sharded tier (ISSUE 16 satellite): the outer/inner digit
    # split now threads through _make_sharded_kernel, so mesh xla miners
    # get the per-group schedule-buffer shrink that won 2.76x on the
    # single-device tier.  Same shard_map + collective cascade, with the
    # factored kernel's remapped global flat index feeding the per-device
    # argmin — bit-exact, lowest-nonce ties included.
    r = sweep_min_hash_sharded(
        "cmu440", 1000, 2234, backend="xla", max_k=2, batch_per_device=2,
        factored=True,
    )
    assert (r.hash, r.nonce) == min_hash_range("cmu440", 1000, 2234)
    assert r.lanes_swept == 2234 - 1000 + 1


def test_sharded_factored_digit_boundary():
    # k=1 leaves nothing to factor (k_in=0 -> baseline fallback) on one
    # side of the boundary; the d=3 class factors.  Both shapes sharded.
    r = sweep_min_hash_sharded(
        "x", 95, 305, backend="xla", max_k=1, batch_per_device=2,
        factored=True,
    )
    assert (r.hash, r.nonce) == min_hash_range("x", 95, 305)


def test_sharded_factored_sieve_composition():
    # Factored + per-shard sieve, sharded: pass 1 and pass 2 resume from
    # ONE shared group prefix inside each shard, the dispatch threshold
    # replicated ahead of the cascade.
    r = sweep_min_hash_sharded(
        "cmu440", 1000, 2234, backend="xla", max_k=2, batch_per_device=2,
        factored=True, sieve=True,
    )
    assert (r.hash, r.nonce) == min_hash_range("cmu440", 1000, 2234)


def test_sharded_matches_single_device_tier():
    from bitcoin_miner_tpu.ops.sweep import sweep_min_hash

    # Same data/digit-count as the single-group test -> reuses its compile.
    data, lo, hi = "cmu440", 1100, 3333
    rs = sweep_min_hash_sharded(
        data, lo, hi, backend="xla", max_k=2, batch_per_device=2
    )
    r1 = sweep_min_hash(data, lo, hi, backend="xla", max_k=2)
    assert (rs.hash, rs.nonce) == (r1.hash, r1.nonce)


def test_mesh_pipeline_matches_oracle():
    # The cross-request SweepPipeline in mesh mode: back-to-back sharded
    # jobs over the 8-device mesh, each bit-exact vs the oracle — the
    # multi-chip miner's production search path (apps/miner.py
    # make_async_search with --devices N).
    from bitcoin_miner_tpu.ops.sweep import SweepPipeline

    p = SweepPipeline(
        backend="xla", mesh=default_mesh(8), max_k=2, batch=2,
        host_lane_budget=0,
    )
    try:
        futs = [
            p.submit("cmu440", 1000, 2234),
            p.submit("cmu440", 2235, 3499),
            p.submit("x", 95, 305),  # different data + digit boundary
        ]
        wants = [("cmu440", 1000, 2234), ("cmu440", 2235, 3499), ("x", 95, 305)]
        for f, (d, lo, hi) in zip(futs, wants):
            r = f.result(timeout=300)
            assert (r.hash, r.nonce) == min_hash_range(d, lo, hi), (d, lo, hi)
            assert r.lanes_swept == hi - lo + 1
    finally:
        p.close()


def test_make_async_search_routes_mesh_to_pipeline():
    from bitcoin_miner_tpu.apps.miner import _PipelineSearch, make_async_search

    s = make_async_search("auto", devices=8)
    try:
        assert isinstance(s, _PipelineSearch)
        h, n = s.submit("cmu440", 1000, 1999).result(timeout=300)
        assert (h, n) == min_hash_range("cmu440", 1000, 1999)
    finally:
        s.close()


# -- Even row placement over a 4-device mesh ------------------------------
#
# A mesh dispatch of R valid rows spreads them over the devices' blocks of
# slots (ops.sweep.MeshRows): each device holds a contiguous run at the
# front of its block, no two devices differ by more than one row, and
# (device, slot) order stays nonce order.  batch_per_device 5 over 4
# devices gives 20 slots a dispatch; k=2 rows of 100 nonces from 1000 make
# a range of R rows one dispatch of R rows.

N_DEV, PER_DEV = 4, 5


def _dispatch_rows(lo, hi, max_k):
    """Valid rows of each dispatch the sharded sweeps make of [lo, hi]."""
    from bitcoin_miner_tpu.ops.sweep import decompose_range

    batch = N_DEV * PER_DEV
    return [
        min(batch, len(g.chunks) - s)
        for g in decompose_range(lo, hi, max_k=max_k)
        for s in range(0, len(g.chunks), batch)
    ]


def _mesh_sweep(form, backend, data, lo, hi, max_k, n_dev=N_DEV, **kw):
    from bitcoin_miner_tpu.ops.sweep import SweepPipeline

    mesh = default_mesh(n_dev)
    interpret = backend == "pallas"
    if form == "sharded":
        return sweep_min_hash_sharded(
            data, lo, hi, mesh=mesh, backend=backend, interpret=interpret,
            max_k=max_k, batch_per_device=PER_DEV, **kw,
        )
    p = SweepPipeline(
        backend=backend, interpret=interpret, mesh=mesh, max_k=max_k,
        batch=PER_DEV, host_lane_budget=0, **kw,
    )
    try:
        return p.submit(data, lo, hi).result(timeout=200)
    finally:
        p.close()


@pytest.mark.parametrize("form,backend", [
    ("sharded", "xla"),
    ("sharded", "pallas"),
    ("pipeline", "xla"),
    ("pipeline", "pallas"),  # the form and tier of the four-chip cell
])
@pytest.mark.parametrize("data,lo,hi,max_k", [
    ("cmu440", 1000, 1099, 2),  # R = 1: device 0 alone
    ("cmu440", 1000, 1299, 2),  # R = 3 = n - 1: one device idle
    ("cmu440", 1000, 1499, 2),  # R = 5 = n + 1
    ("cmu440", 1050, 1549, 2),  # R = 6 = one device's batch + 1, runt ends
    ("cmu440", 1000, 2999, 2),  # R = 20 = n x batch: every slot full
    ("x", 95, 305, 1),  # crosses d=2 -> d=3: dispatches of 1, 20 and 1 rows
])
def test_mesh_even_placement_matches_oracle(
    form, backend, data, lo, hi, max_k, monkeypatch
):
    import numpy as np

    from bitcoin_miner_tpu.ops.sweep import MeshRows
    from bitcoin_miner_tpu.parallel import sweep as psweep
    from bitcoin_miner_tpu.utils import trace
    from bitcoin_miner_tpu.utils.metrics import METRICS

    shipped = []  # each dispatch's bounds, as placed on the mesh
    place = psweep.shard_operands  # every mesh dispatch places through it

    def spy(midstate, tail_const, bounds, *a, **kw):
        shipped.append(np.array(bounds))
        return place(midstate, tail_const, bounds, *a, **kw)

    monkeypatch.setattr(psweep, "shard_operands", spy)
    names = (
        "sweep.mesh_rows", "sweep.mesh_row_slots", "sweep.mesh_dispatches",
        "sweep.mesh_dispatch_slots",
    )
    before = [METRICS.get(n) for n in names]
    with trace.tracing() as tr:
        r = _mesh_sweep(form, backend, data, lo, hi, max_k)
        events = [e for e in tr.drain() if e["event"] == "mesh_dispatch"]
    assert (r.hash, r.nonce) == min_hash_range(data, lo, hi)
    assert r.lanes_swept == hi - lo + 1
    want = _dispatch_rows(lo, hi, max_k)
    # Where the rows went: each device's valid rows sit at the front of
    # its block, and no two devices differ by more than one row.
    assert len(shipped) == len(want)
    for bounds, rows in zip(shipped, want):
        valid = (bounds[:, 1] > bounds[:, 0]).reshape(N_DEV, PER_DEV)
        per_dev = valid.sum(axis=1).tolist()
        assert per_dev == list(MeshRows(rows, N_DEV).counts())
        assert max(per_dev) - min(per_dev) <= 1
        assert all(valid[d, :c].all() for d, c in enumerate(per_dev))
    # The counters and the trace event say the same.
    got_rows, slots, dispatches, carried = (
        METRICS.get(n) - b for n, b in zip(names, before)
    )
    assert got_rows == sum(want)
    assert dispatches == len(want)
    assert slots == sum(N_DEV * -(-rows // N_DEV) for rows in want)
    # Every dispatch carries all N_DEV x PER_DEV slots, however few it fills.
    assert carried == len(want) * N_DEV * PER_DEV
    assert [e["attrs"]["rows"] for e in events] == want
    for e, rows in zip(events, want):
        assert e["attrs"]["per_device"] == list(MeshRows(rows, N_DEV).counts())
        assert e["attrs"]["slots"] == N_DEV * PER_DEV


# -- The per-device batch a mesh dispatch defaults to ----------------------
#
# The pallas tier's default holds DEFAULT_BATCH (1024) slots per dispatch in
# all, so a scheduler chunk of ~1000 rows fills a mesh dispatch as it fills
# one chip's: each device gets ceil(1024 / n) rounded up to DEFAULT_CPB (8).


@pytest.mark.parametrize("backend,batch,family,n_devices,want", [
    ("pallas", None, "sha256", 1, 1024),
    ("pallas", None, "sha256", 2, 512),
    ("pallas", None, "sha256", 3, 344),
    ("pallas", None, "sha256", 4, 256),
    ("pallas", None, "sha256", 8, 128),
    ("xla", None, "sha256", 1, 4),  # the xla tier's default is per device
    ("xla", None, "sha256", 4, 4),
    (None, None, "blake2b", 1, 8),  # so is the blake2b family's
    (None, None, "blake2b", 4, 8),
    ("pallas", 5, "sha256", 4, 5),  # an explicit batch is per device
    ("xla", 2, "sha256", 8, 2),
])
def test_auto_tune_per_device_batch(backend, batch, family, n_devices, want):
    from bitcoin_miner_tpu.ops.pallas_sha256 import DEFAULT_BATCH, DEFAULT_CPB
    from bitcoin_miner_tpu.ops.sweep import auto_tune

    got = auto_tune(backend, batch, None, family=family, n_devices=n_devices)[1]
    assert got == want
    if backend == "pallas" and batch is None:
        # A multiple of the rows per grid program, and the least one whose
        # n_devices blocks hold DEFAULT_BATCH slots.
        assert got % DEFAULT_CPB == 0
        assert n_devices * got >= DEFAULT_BATCH
        assert n_devices * (got - DEFAULT_CPB) < DEFAULT_BATCH


def test_mesh_pipeline_pallas_default_dispatch_holds_1024_slots(monkeypatch):
    # A four-device pallas pipeline with no batch given builds its sharded
    # kernel for 256 rows a device and ships 1024-row operands, however few
    # rows a dispatch has.  Three rows of 10 nonces (d=3, k=1) keep the
    # interpret-mode kernel cheap.
    import numpy as np

    from bitcoin_miner_tpu.ops.sweep import SweepPipeline
    from bitcoin_miner_tpu.parallel import sweep as psweep

    shipped, built = [], []
    place, build = psweep.shard_operands, psweep.sharded_kernel_for

    def spy_place(midstate, tail_const, bounds, *a, **kw):
        shipped.append(np.array(bounds).shape)
        return place(midstate, tail_const, bounds, *a, **kw)

    def spy_build(layout, group, per_dev_batch, *a, **kw):
        built.append(per_dev_batch)
        return build(layout, group, per_dev_batch, *a, **kw)

    monkeypatch.setattr(psweep, "shard_operands", spy_place)
    monkeypatch.setattr(psweep, "sharded_kernel_for", spy_build)
    p = SweepPipeline(
        backend="pallas", interpret=True, mesh=default_mesh(4), max_k=1,
        host_lane_budget=0,
    )
    try:
        r = p.submit("x", 100, 129).result(timeout=200)
    finally:
        p.close()
    assert (r.hash, r.nonce) == min_hash_range("x", 100, 129)
    assert built and set(built) == {256}
    assert shipped == [(1024, 2)]


def test_mesh_dyn_grid_runs_the_fullest_devices_groups():
    """Every shard's grid runs the fullest device's rows in whole groups of
    cpb.  35 rows over 4 devices of 24 slots (cpb 8) place 9, 9, 9 and 8,
    so each grid runs 2 of its 3 groups and the short device's second
    group is empty.  The window's minimum sits in a ninth row, which a
    grid one group short would miss."""
    from bitcoin_miner_tpu.ops.sweep import MeshRows
    from bitcoin_miner_tpu.utils.metrics import METRICS

    data, n_dev, per_dev, n_rows = "cmu440", 4, 24, 35
    place = MeshRows(n_rows, n_dev)
    assert place.counts() == (9, 9, 9, 8)
    ninth = {place.row(dev, 8) for dev in range(3)}
    mins = [min_hash_range(data, b, b + 99) for b in range(10000, 30000, 100)]
    start = next(
        s for s in range(len(mins) - n_rows)
        if mins.index(min(mins[s : s + n_rows]), s) - s in ninth
    )
    lo = 10000 + 100 * start
    hi = lo + 100 * n_rows - 1
    names = ("sweep.grid_groups", "sweep.grid_groups_skipped")
    before = [METRICS.get(n) for n in names]
    r = sweep_min_hash_sharded(
        data, lo, hi, mesh=default_mesh(n_dev), backend="pallas",
        interpret=True, max_k=2, batch_per_device=per_dev,
    )
    assert (r.hash, r.nonce) == min_hash_range(data, lo, hi)
    ran, skipped = (METRICS.get(n) - b for n, b in zip(names, before))
    assert (ran, skipped) == (n_dev * 2, n_dev * 1)


def test_mesh_rows_slot_map_is_nonce_ordered():
    from bitcoin_miner_tpu.ops.sweep import MeshRows

    for r in range(0, 4 * PER_DEV + 1):
        place = MeshRows(r, N_DEV)
        slots = place.slots(PER_DEV)
        assert slots == sorted(slots) and len(set(slots)) == r
        assert all(s // PER_DEV < N_DEV and s % PER_DEV < PER_DEV for s in slots)
        # row() inverts slots(): (device, local slot) -> row, in order.
        assert [place.row(s // PER_DEV, s % PER_DEV) for s in slots] == list(range(r))


@pytest.mark.parametrize("form", ["sharded", "pipeline"])
@pytest.mark.parametrize("n_dev", [2, N_DEV])
def test_mesh_cross_device_tie_lowest_nonce_wins(form, n_dev, monkeypatch):
    # A stand-in kernel hashes nonce n to (7, 3) if n >= tie, else
    # (7, 9), reading n from the chunk templates and lanes as the real
    # kernel sees them.  [1050, 1699] is 7 rows, 1000..1600, of 100
    # nonces: 2, 2, 2 and 1 rows per device on 4 devices, 4 and 3 on 2.
    # The tie starts at the last row of the next-to-last device (1500 on
    # 4 devices, 1300 on 2), so the minimum (7, 3) ties on both digest
    # words across that device and the last one, whose local slot 0 is
    # the lower flat index: the cascade must pick the lower device, and
    # the fold must map it back to the tie's first nonce.  A placement
    # the fold does not mirror names another nonce.
    import jax.numpy as jnp

    from bitcoin_miner_tpu.ops.sweep import I32_MAX, U32_MAX, MeshRows
    from bitcoin_miner_tpu.parallel import sweep as psweep

    tie = 1000 + 100 * (sum(MeshRows(7, n_dev).counts()[:-1]) - 1)

    def tie_kernel(layout, group, per_dev_batch, mesh, axis_name, *a, **kw):
        n_lanes = 10**group.k
        n_high = layout.digit_count - group.k

        def local(midstate, tail_const, bounds):
            high = jnp.zeros(tail_const.shape[0], jnp.int32)
            for dp in layout.digit_pos[:n_high]:
                byte = (tail_const[:, dp.word] >> dp.shift) & 0xFF
                high = high * 10 + byte.astype(jnp.int32) - 48
            i = jnp.arange(n_lanes, dtype=jnp.int32)[None, :]
            nonce = high[:, None] * n_lanes + i
            valid = (i >= bounds[:, :1]) & (i < bounds[:, 1:2])
            h1 = jnp.where(nonce >= tie, jnp.uint32(3), jnp.uint32(9))
            h1 = jnp.where(valid, h1, jnp.uint32(U32_MAX))
            min_h1 = jnp.min(h1)
            flat = jnp.arange(h1.size, dtype=jnp.int32).reshape(h1.shape)
            first = jnp.min(
                jnp.where(valid & (h1 == min_h1), flat, jnp.int32(I32_MAX))
            )
            h0 = jnp.where(first != I32_MAX, jnp.uint32(7), jnp.uint32(U32_MAX))
            return h0, min_h1, first

        return psweep._shard_and_jit(local, mesh, axis_name, False)

    monkeypatch.setattr(psweep, "sharded_kernel_for", tie_kernel)
    r = _mesh_sweep(
        form, "xla", "cmu440", 1050, 1699, 2, n_dev=n_dev, sieve=False
    )
    assert (r.hash, r.nonce) == ((7 << 32) | 3, tie)


def test_single_device_templates_unchanged():
    # The single-device template fill, byte for byte as before mesh
    # placement existed: a digest of its output over three ranges (one
    # digit class, a digit boundary, an 11-digit class at k=6).
    import hashlib

    from bitcoin_miner_tpu.ops.sweep import (
        _fill_templates,
        _layout_cache,
        decompose_range,
    )

    out = []
    for data, lo, hi, k, batch in [
        ("cmu440", 1234567, 1534566, 5, 8),
        ("x", 95, 305, 1, 32),
        ("cmu440", 99_000_000_000, 99_004_999_999, 6, 8),
    ]:
        for g in decompose_range(lo, hi, max_k=k):
            layout = _layout_cache(data.encode(), g.d)
            for s in range(0, len(g.chunks), batch):
                rows = g.chunks[s : s + batch]
                t, b = _fill_templates(layout, g, rows, batch)
                t2, b2 = _fill_templates(layout, g, rows, batch, range(len(rows)))
                assert t.tobytes() == t2.tobytes() and b.tobytes() == b2.tobytes()
                out.append(t.tobytes() + b.tobytes())
    assert hashlib.sha256(b"".join(out)).hexdigest() == (
        "b861cd02c106261fb58f7e6bd7a148c98768bf294754dd8eb93de2fb9c5ead0c"
    )


# -- The mesh pipeline's adversarial matrix --------------------------------
#
# SweepPipeline over the 4-device mesh is the path the four-chip miner
# runs; each case is bit-exact against the hashlib oracle.


class TestMeshPipeline:
    BACKENDS = ["xla", "pallas"]  # pallas in interpret mode (_mesh_sweep)

    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize(
        "lo,hi",
        [
            (5, 15),       # 9→10: d=1 (static pallas kernel) + d=2
            (93, 107),     # 99→100 digit-class boundary
            (985, 1040),   # 999→1000 (the dyn-kernel window shift)
        ],
    )
    @pytest.mark.parametrize("sieve", [False, True], ids=["plain", "sieve"])
    def test_digit_class_boundaries(self, backend, lo, hi, sieve):
        r = _mesh_sweep("pipeline", backend, "cmu440", lo, hi, 2, sieve=sieve)
        assert (r.hash, r.nonce) == min_hash_range("cmu440", lo, hi)
        assert r.lanes_swept == hi - lo + 1

    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("sieve", [False, True], ids=["plain", "sieve"])
    def test_u64_upper_edge(self, backend, sieve):
        top = (1 << 64) - 1
        r = _mesh_sweep("pipeline", backend, "big", top - 50, top, 1, sieve=sieve)
        assert (r.hash, r.nonce) == min_hash_range("big", top - 50, top)
        assert r.lanes_swept == 51

    @staticmethod
    def _fold_before_enqueue(monkeypatch):
        """Hold each mesh dispatch until every earlier one has been fetched
        and folded, so the threshold it carries is the running minimum
        through the dispatch before it (the pipeline otherwise enqueues
        ahead of its fetches).  Returns the ``(thresh, outputs)`` of each
        dispatch, in order."""
        import functools
        import time

        from bitcoin_miner_tpu.ops import sweep as sweep_mod
        from bitcoin_miner_tpu.ops.sweep import MeshRows
        from bitcoin_miner_tpu.parallel import sweep as psweep

        folds, shipped = [], []
        row, count = MeshRows.row, sweep_mod._count_mesh_dispatch
        invoke = psweep.sharded_invoke

        def counting_row(self, dev, local):  # the fetcher maps every result
            folds.append(dev)
            return row(self, dev, local)

        def held_count(place, per_dev_batch):
            deadline = time.monotonic() + 120
            while len(folds) < len(shipped) and time.monotonic() < deadline:
                time.sleep(0.005)
            time.sleep(0.05)  # the fetcher's fold follows its row lookup
            return count(place, per_dev_batch)

        def spy_invoke(kern, *a, thresh=None, **kw):
            out = invoke(kern, *a, thresh=thresh, **kw)
            shipped.append((thresh, out))
            return out

        # Each dispatch goes to the fetcher as soon as it is enqueued.
        monkeypatch.setattr(
            sweep_mod, "run_sweep_dispatches",
            functools.partial(sweep_mod.run_sweep_dispatches, max_inflight=0),
        )
        monkeypatch.setattr(MeshRows, "row", counting_row)
        monkeypatch.setattr(sweep_mod, "_count_mesh_dispatch", held_count)
        monkeypatch.setattr(psweep, "sharded_invoke", spy_invoke)
        return shipped

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_threshold_tie_across_shards_survives(self, backend, monkeypatch):
        # A stand-in kernel hashes every nonce of [1000, 3699] to (T, 9),
        # but those >= 3500 to (T, 3), honouring the sieve threshold as the
        # real kernels do (``h0 <= thresh``; in the sign-flipped int32
        # domain on pallas, through the real _flip_thresh).  Dispatch 1
        # (rows 1000..2999) sets the running minimum's h0 to T; dispatch 2
        # (rows 3000..3600: 2, 2, 2 and 1 per device) carries threshold T
        # exactly, and its (T, 3) ties across device 2 (row 3500, local
        # slot 1) and device 3 (row 3600, slot 0, the lower flat index).
        # The tie must survive the threshold, and the lower nonce must win.
        import jax.numpy as jnp
        from jax import lax

        from bitcoin_miner_tpu.ops.sweep import I32_MAX, U32_MAX
        from bitcoin_miner_tpu.parallel import sweep as psweep

        T = 0x80000007  # past 2^31: the flipped domain orders it

        def tie_kernel(
            layout, group, per_dev_batch, mesh, axis_name, *a, sieve=False, **kw
        ):
            n_lanes = 10**group.k
            n_high = layout.digit_count - group.k

            def flip(x):
                return lax.bitcast_convert_type(x ^ jnp.uint32(0x80000000), jnp.int32)

            def local(midstate, tail_const, bounds, *th):
                high = jnp.zeros(tail_const.shape[0], jnp.int32)
                for dp in layout.digit_pos[:n_high]:
                    byte = (tail_const[:, dp.word] >> dp.shift) & 0xFF
                    high = high * 10 + byte.astype(jnp.int32) - 48
                i = jnp.arange(n_lanes, dtype=jnp.int32)[None, :]
                nonce = high[:, None] * n_lanes + i
                valid = (i >= bounds[:, :1]) & (i < bounds[:, 1:2])
                h0 = jnp.full(nonce.shape, T, jnp.uint32)
                if sieve and backend == "pallas":
                    valid = valid & (flip(h0) <= psweep._flip_thresh(th[0])[0])
                elif sieve:
                    valid = valid & (h0 <= th[0])
                h1 = jnp.where(nonce >= 3500, jnp.uint32(3), jnp.uint32(9))
                h1 = jnp.where(valid, h1, jnp.uint32(U32_MAX))
                min_h1 = jnp.min(h1)
                flat = jnp.arange(h1.size, dtype=jnp.int32).reshape(h1.shape)
                first = jnp.min(
                    jnp.where(valid & (h1 == min_h1), flat, jnp.int32(I32_MAX))
                )
                h0 = jnp.where(first != I32_MAX, jnp.uint32(T), jnp.uint32(U32_MAX))
                return h0, min_h1, first

            return psweep._shard_and_jit(local, mesh, axis_name, sieve)

        monkeypatch.setattr(psweep, "sharded_kernel_for", tie_kernel)
        shipped = self._fold_before_enqueue(monkeypatch)
        r = _mesh_sweep("pipeline", backend, "cmu440", 1000, 3699, 2, sieve=True)
        assert [th for th, _ in shipped] == [U32_MAX, T]
        h0, h1, dev, flat = (int(x) for x in shipped[1][1])
        assert (h0, h1, dev, flat) == (T, 3, 2, 100)
        assert (r.hash, r.nonce) == ((T << 32) | 3, 3500)

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_threshold_below_every_shard_keeps_running_min(
        self, backend, monkeypatch
    ):
        # [1000, 4999] is two dispatches of 20 rows, and the job's minimum
        # (nonce 1081) lies in the first.  The second carries that minimum's
        # h0 as its threshold, below every lane of every shard: each shard
        # contributes the sentinel, the cascade returns it, and the running
        # minimum stands.
        from bitcoin_miner_tpu.ops.sweep import I32_MAX, U32_MAX

        want = min_hash_range("cmu440", 1000, 4999)
        assert want[1] < 3000 < min_hash_range("cmu440", 3000, 4999)[1]
        shipped = self._fold_before_enqueue(monkeypatch)
        r = _mesh_sweep("pipeline", backend, "cmu440", 1000, 4999, 2, sieve=True)
        assert [th for th, _ in shipped] == [U32_MAX, want[0] >> 32]
        assert int(shipped[1][1][3]) == I32_MAX
        assert (r.hash, r.nonce) == want

    def test_wedge_dispatch_hangs_until_close(self, monkeypatch):
        # BMT_WEDGE_DISPATCH=1 hangs the mesh pipeline's first fetch, as a
        # stuck device future would: the job's future stays open until
        # close() releases the fetch loop.
        import time

        from bitcoin_miner_tpu.ops import sweep as sweep_mod
        from bitcoin_miner_tpu.ops.sweep import SweepPipeline

        monkeypatch.setenv("BMT_WEDGE_DISPATCH", "1")
        monkeypatch.setitem(sweep_mod._WEDGE_STATE, "fired", False)
        p = SweepPipeline(
            backend="xla", mesh=default_mesh(N_DEV), max_k=2, batch=PER_DEV,
            host_lane_budget=0,
        )
        try:
            fut = p.submit("wedgemesh", 1000, 1299)
            deadline = time.monotonic() + 120
            while not sweep_mod._WEDGE_STATE["fired"] and time.monotonic() < deadline:
                time.sleep(0.02)
            assert sweep_mod._WEDGE_STATE["fired"]  # the hang was real
            time.sleep(0.5)
            assert not fut.done()
        finally:
            p.close()
        assert not p._fetcher.is_alive()
        # The dropped fetch never yields a result.
        assert fut.exception(timeout=10) is not None


@pytest.mark.parametrize("form", ["single", "sharded"])
def test_sync_sweep_leaves_no_pipeline_thread(form):
    # The synchronous sweeps run one job through a pipeline of their own
    # and close it before they return.
    import threading

    from bitcoin_miner_tpu.ops.sweep import sweep_min_hash

    names = ("sweep-dispatch", "sweep-fetch")
    before = {t for t in threading.enumerate() if t.name in names}
    if form == "single":
        r = sweep_min_hash("cmu440", 1000, 1299, backend="xla", max_k=2)
    else:
        r = sweep_min_hash_sharded(
            "cmu440", 1000, 1299, mesh=default_mesh(N_DEV), backend="xla",
            max_k=2, batch_per_device=PER_DEV,
        )
    assert (r.hash, r.nonce) == min_hash_range("cmu440", 1000, 1299)
    left = [
        t for t in threading.enumerate()
        if t.name in names and t not in before and t.is_alive()
    ]
    assert not left, left


def test_mesh_pallas_pipeline_sweeps_d_above_max_k_on_the_mesh():
    # The auto host budget on the pallas tier keeps only d <= max_k on
    # the host; the next class goes through the sharded dyn kernel.
    from bitcoin_miner_tpu.ops.sweep import SweepPipeline
    from bitcoin_miner_tpu.utils.metrics import METRICS

    p = SweepPipeline(
        backend="pallas", interpret=True, mesh=default_mesh(4), max_k=2,
        batch=2,
    )
    try:
        assert p._host_lane_budget == 10**2
        dev0 = METRICS.get("sweep.device_lanes")
        r = p.submit("cmu440", 5, 1234).result(timeout=300)
    finally:
        p.close()
    assert (r.hash, r.nonce) == min_hash_range("cmu440", 5, 1234)
    assert METRICS.get("sweep.device_lanes") - dev0 == 1234 - 100 + 1
