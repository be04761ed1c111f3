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


def test_sharded_hot_matches_oracle():
    # The always-hot plane over the mesh (ISSUE 16): donated replicated
    # carry merged AFTER the collective cascade, the carried best_dev
    # scaling the row exactly like the per-chunk sharded fold.  The xla
    # leg rides the factored sharded default.
    r = sweep_min_hash_sharded(
        "cmu440", 1000, 2234, backend="xla", max_k=2, batch_per_device=2,
        sieve=True, hot=True,
    )
    assert (r.hash, r.nonce) == min_hash_range("cmu440", 1000, 2234)
    assert r.lanes_swept == 2234 - 1000 + 1


def test_sharded_hot_digit_boundary():
    r = sweep_min_hash_sharded(
        "x", 95, 305, backend="xla", max_k=1, batch_per_device=2, hot=True
    )
    assert (r.hash, r.nonce) == min_hash_range("x", 95, 305)


def test_mesh_pipeline_hot_matches_oracle():
    # SweepPipeline mesh mode with the hot plane on: back-to-back jobs,
    # one donated carry per job, tokens through the same fetch queue.
    from bitcoin_miner_tpu.ops.sweep import SweepPipeline

    p = SweepPipeline(
        backend="xla", mesh=default_mesh(8), max_k=2, batch=2,
        host_lane_budget=0, sieve=True, hot=True,
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
