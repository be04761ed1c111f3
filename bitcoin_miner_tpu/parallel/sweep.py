"""Multi-chip nonce sweep: shard_map over a device mesh + collective min.

This is the ICI plane of the comms design (SURVEY §2.3/§5): chunk batches are
sharded across the mesh's ``miners`` axis, each device runs the single-chip
min-hash kernel on its shard, and a psum-style collective cascade reduces the
lexicographic ``(h0, h1, nonce-order)`` minimum across chips — the TPU-native
analogue of the reference's server-side min-fold over miner Results
(``bitcoin/message.go:38-44``), and the ``lax.pmin`` reduction named in the
BASELINE north star.

Tie-break: a dispatch's rows spread evenly over the devices' blocks of
slots, each block holding a contiguous run of them in ascending-nonce order
(``ops.sweep.MeshRows``), and the blocks are sharded *contiguously* along the
mesh axis, so ``(device, flat_idx)`` lexicographic order equals nonce order
and the collective cascade preserves lowest-nonce-wins.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.sha256 import DigitPos
from ..ops.sweep import (
    I32_MAX,
    U32_MAX,
    SweepPipeline,
    SweepResult,
    default_factor_k_in,
    make_kernel_body,
)
from ..utils.platform import pallas_platform
from .mesh import MINER_AXIS, default_mesh


def _collective_min(h0, h1, flat, axis: str):
    """Reduce per-device (h0, h1, flat_idx) scalars to the replicated global
    lexicographic min, lowest-(device, flat) — i.e. lowest-nonce — ties.

    Three chained ``lax.pmin``s: min h0, then min h1 among h0-winners, then
    min (device, flat) among (h0, h1)-winners.  All collectives ride the mesh
    axis (ICI on real hardware).
    """
    g_h0 = lax.pmin(h0, axis)
    h1m = jnp.where(h0 == g_h0, h1, jnp.uint32(U32_MAX))
    g_h1 = lax.pmin(h1m, axis)
    mine = (h0 == g_h0) & (h1m == g_h1) & (flat != jnp.int32(I32_MAX))
    dev = lax.axis_index(axis).astype(jnp.int32)
    g_dev = lax.pmin(jnp.where(mine, dev, jnp.int32(I32_MAX)), axis)
    g_flat = lax.pmin(
        jnp.where(mine & (dev == g_dev), flat, jnp.int32(I32_MAX)), axis
    )
    return g_h0, g_h1, g_dev, g_flat


def _flip_thresh(thresh):
    """uint32 scalar threshold → the (1,) sign-flipped int32 operand the
    pallas sieve kernels compare in (same domain as _invoke_kernel's
    host-side conversion, but traced — the sharded thresh operand rides
    the dispatch replicated as plain uint32)."""
    return lax.bitcast_convert_type(
        thresh ^ jnp.uint32(0x80000000), jnp.int32
    ).reshape(1)


@lru_cache(maxsize=256)
def _make_sharded_kernel(
    n_tail_blocks: int,
    low_pos: Tuple[DigitPos, ...],
    k: int,
    per_dev_batch: int,
    mesh: Mesh,
    axis_name: str,
    backend: str,
    interpret: bool,
    rolled: bool,
    sieve: bool = False,
    factored: int = 0,
):
    """Compile the sharded kernel for one (layout, k, batch) shape class
    (the xla tier, and the pallas static fallback for the d == k class).

    Returned jitted fn: ``(midstate (8,), tail_const (B, nw), bounds (B, 2))
    -> (g_h0, g_h1, g_dev, g_flat)`` replicated scalars, where
    ``B = n_devices * per_dev_batch`` and the slot blocks are sharded
    contiguously along ``axis_name``.

    ``factored`` (ISSUE 16 satellite, xla only — the pallas branch
    ignores it, see :func:`sharded_kernel_for`): the inner digit count
    ``k_in`` of the outer/inner split, 0 = the baseline lane axis.  Each
    SHARD runs the factored body locally — the outer-group scalar round
    prefix and the per-group cache-resident schedule buffer are per-shard
    properties, so the 2.76× single-device xla win (BENCH_pr14.json)
    carries straight through the collective cascade, which is shape-
    agnostic over the local ``(h0, h1, flat)`` it reduces.

    ``sieve=True`` is the PER-SHARD sieve (ISSUE 14 satellite): the fn
    takes an extra replicated uint32 ``thresh`` scalar; each shard runs
    the two-stage kernel locally — seeding pass 1 from the dispatch
    threshold and (pallas) tightening its own running min in SMEM
    scratch — AHEAD of the collective argmin cascade.  A shard with no
    survivor contributes the ``(U32_MAX, U32_MAX, I32_MAX)`` sentinel,
    which is correct under the cascade: no survivor means every lane on
    that shard exceeds the threshold, and any OTHER shard's survivor is
    <= the threshold, so the sentinel never outranks a real minimum
    (ties conservatively survive shard-locally, same as single-device).
    """
    if backend == "pallas":
        from ..ops.pallas_sha256 import make_pallas_minhash

        pallas_fn = make_pallas_minhash(
            n_tail_blocks, low_pos, k, per_dev_batch, interpret=interpret,
            sieve=sieve,
        )

        def local(midstate, tail_const, bounds, *th):
            tailcb = jnp.concatenate(
                [tail_const, bounds.astype(jnp.uint32)], axis=1
            )
            if sieve:
                return pallas_fn(midstate, tailcb, _flip_thresh(th[0]))
            return pallas_fn(midstate, tailcb)

    else:
        local = make_kernel_body(
            n_tail_blocks, low_pos, k, per_dev_batch, rolled, sieve=sieve,
            factored=factored,
        )

    return _shard_and_jit(local, mesh, axis_name, sieve)


def _shard_and_jit(
    local, mesh: Mesh, axis_name: str, sieve: bool, compiler_options=None
):
    """shard_map + collective cascade + jit around one local kernel body
    — shared by the sha256 and blake2b sharded factories (the cascade is
    shape-agnostic over the local ``(h0, h1, flat)`` scalars)."""

    def shard_fn(midstate, tail_const, bounds, *th):
        h0, h1, flat = local(midstate, tail_const, bounds, *th)
        return _collective_min(h0, h1, flat, axis_name)

    mapped = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(), P(axis_name, None), P(axis_name, None))
        + ((P(),) if sieve else ()),
        out_specs=(P(), P(), P(), P()),
        # pallas_call's out_shape carries no varying-mesh-axes annotation, so
        # the vma checker can't see through it; the collective cascade above
        # makes every output genuinely replicated.
        check_vma=False,
    )
    return jax.jit(mapped, compiler_options=compiler_options)


@lru_cache(maxsize=256)
def _make_sharded_blake2b_kernel(
    msg_len: int,
    tail_off: int,
    n_tail_blocks: int,
    live_words: Tuple[int, ...],
    low_pos: Tuple[DigitPos, ...],
    k: int,
    per_dev_batch: int,
    mesh: Mesh,
    axis_name: str,
    sieve: bool = False,
    factored: int = 0,
):
    """The blake2b family's sharded kernel (ISSUE 20): each shard runs
    the grouped-unrolled u32-pair kernel (ops/blake2b.py) locally —
    zero-word elision, per-group cache-resident tiles and all — ahead of
    the same collective argmin cascade, so mesh miners serve the family
    with the single-device tier's full kernel win.  xla only (the family
    has no pallas lowering); the shape-class key carries the layout's
    static fields the sha256 key doesn't need (msg_len / tail_off /
    live-word set are compiled into the DAG)."""
    from ..ops.blake2b import compiler_options, make_blake2b_kernel_body

    local = make_blake2b_kernel_body(
        msg_len, tail_off, n_tail_blocks, live_words, low_pos, k,
        per_dev_batch, sieve=sieve, factored=factored,
    )
    return _shard_and_jit(
        local, mesh, axis_name, sieve, compiler_options=compiler_options()
    )


@lru_cache(maxsize=8)
def _zero_tile_mesh(n_pad: int, mesh: Mesh):
    from ..ops.pallas_sha256 import zero_tile_np

    return jax.device_put(
        zero_tile_np(n_pad), NamedSharding(mesh, P(None, None))
    )


@lru_cache(maxsize=64)
def _mesh_contribs(k, low_pos, w_lo, w_hi, n_pad, mesh):
    """Window contribution tiles replicated over the mesh, cached per
    digit class so sweeps don't re-transfer them; untouched words share
    one replicated zero tile."""
    from ..ops.pallas_sha256 import window_contribs_np, zero_tile_np

    rep = NamedSharding(mesh, P(None, None))
    zero = zero_tile_np(n_pad)
    return tuple(
        _zero_tile_mesh(n_pad, mesh) if c is zero else jax.device_put(c, rep)
        for c in window_contribs_np(k, low_pos, w_lo, w_hi, n_pad)
    )


@lru_cache(maxsize=64)
def _make_sharded_kernel_dyn(
    n_tail_blocks: int,
    w_lo: int,
    w_hi: int,
    k: int,
    per_dev_batch: int,
    mesh: Mesh,
    axis_name: str,
    interpret: bool,
    sieve: bool = False,
):
    """Sharded form of the digit-position-DYNAMIC pallas kernel: ONE
    compiled SPMD executable serves every digit class d in [k+1, 20] of a
    data length, same as the single-device production path (ops/sweep.py
    `_build_kernel`) — a multi-chip sweep crossing a decimal digit
    boundary never re-traces or re-loads.

    Returned jitted fn: ``(midstate, tail_const, bounds, n_groups,
    [thresh,] *contribs)`` with contribs replicated (one (n_pad/128, 128)
    u32 tile per window word); ``sieve=True`` adds the replicated uint32
    thresh scalar of the per-shard sieve (see :func:`_make_sharded_kernel`).
    ``n_groups`` is the replicated int32 row-group extent of every shard's
    grid: the fullest device's rows in whole groups of ``cpb``, so every
    device steps through as many groups as that one.
    """
    from ..ops.pallas_sha256 import make_pallas_minhash_dyn

    pallas_fn, n_pad = make_pallas_minhash_dyn(
        n_tail_blocks, w_lo, w_hi, k, per_dev_batch, interpret=interpret,
        sieve=sieve,
    )
    n_window = w_hi - w_lo + 1

    def shard_fn(midstate, tail_const, bounds, n_groups, *rest):
        tailcb = jnp.concatenate(
            [tail_const, bounds.astype(jnp.uint32)], axis=1
        )
        if sieve:
            h0, h1, flat = pallas_fn(
                midstate, tailcb, n_groups, _flip_thresh(rest[0]), *rest[1:]
            )
        else:
            h0, h1, flat = pallas_fn(midstate, tailcb, n_groups, *rest)
        return _collective_min(h0, h1, flat, axis_name)

    mapped = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(), P(axis_name, None), P(axis_name, None), P())
        + ((P(),) if sieve else ())
        + (P(None, None),) * n_window,
        out_specs=(P(), P(), P(), P()),
        check_vma=False,  # same rationale as the static form above
    )
    return jax.jit(mapped), n_pad


def sharded_kernel_for(
    layout,
    group,
    batch_per_device: int,
    mesh: Mesh,
    axis_name: str,
    backend: str,
    interpret: bool,
    rolled: bool,
    sieve: bool = False,
    factored: bool = False,
):
    """Build (or fetch cached) the sharded kernel closure for one digit
    class: ``kern(midstate, tail_const, bounds, *th) -> (g_h0, g_h1,
    g_dev, g_flat)`` (``*th`` is the one replicated uint32 threshold
    operand when ``sieve=True``, empty otherwise), for the mesh mode of
    ``ops.sweep.SweepPipeline``; dyn-kernel closures carry ``class_key``
    for the pipeline's single-flight build locks.  Under Mosaic, in a
    single-process mesh, the dyn kernel is served from its stored export
    (``ops/kernel_store.py``), as on one device.

    ``factored`` threads the outer/inner digit split into the xla
    branch (classes with ``k >= 2``; a 1-digit lane axis has nothing to
    factor).  The pallas branch IGNORES it: the sharded pallas tier
    keeps the dyn kernels — the factored pallas kernel is per-class
    static, giving back the digit-boundary compile amortization, and its
    cost model can only be arbitrated on real TPU (the same follow-on as
    the single-device pallas factored rung)."""
    low_pos = layout.digit_pos[layout.digit_count - group.k :]
    if getattr(layout, "family", "sha256") == "blake2b":
        if backend != "xla":
            raise ValueError(
                f"blake2b kernel family has no {backend!r} tier (xla only)"
            )
        return _make_sharded_blake2b_kernel(
            layout.msg_len,
            layout.tail_off,
            layout.n_tail_blocks,
            layout.live_words,
            low_pos,
            group.k,
            batch_per_device,
            mesh,
            axis_name,
            sieve=sieve,
            factored=(
                default_factor_k_in(group.k) if factored and group.k >= 2
                else 0
            ),
        )
    if backend == "pallas":
        from ..ops.pallas_sha256 import dyn_params, resolve_cpb

        window = dyn_params(layout, group.k)
        if window is not None:
            w_lo, w_hi = window
            fn, n_pad = _make_sharded_kernel_dyn(
                layout.n_tail_blocks,
                w_lo,
                w_hi,
                group.k,
                batch_per_device,
                mesh,
                axis_name,
                interpret,
                sieve=sieve,
            )
            if (
                not interpret
                and pallas_platform() == "mosaic"
                and jax.process_count() == 1
            ):
                # A fresh process loads the kernel's stored export instead
                # of tracing and lowering it again (ops/kernel_store.py).
                # Not across processes: each must enqueue the collectives
                # of one program, and that is unchecked for an export.
                from ..ops.kernel_store import MESH_SOURCES, stored_kernel

                fn = stored_kernel(
                    fn,
                    sources=MESH_SOURCES,
                    n_tail_blocks=layout.n_tail_blocks,
                    w_lo=w_lo,
                    w_hi=w_hi,
                    k=group.k,
                    per_dev_batch=batch_per_device,
                    sieve=sieve,
                    n_devices=mesh.size,
                    mesh_shape=tuple(mesh.devices.shape),
                    axis_names=tuple(mesh.axis_names),
                    axis_name=axis_name,
                )
            contribs = _mesh_contribs(
                group.k, low_pos, w_lo, w_hi, n_pad, mesh
            )

            def kern(
                midstate, tail_const, bounds, n_groups, *th, _fn=fn,
                _c=contribs,
            ):
                return _fn(midstate, tail_const, bounds, n_groups, *th, *_c)

            kern.class_key = fn
            kern.cpb = resolve_cpb(batch_per_device)  # the factory's cpb
            return kern
        # d == k (the d=1 class): outside the dyn window domain; one
        # class, so per-class compilation costs nothing extra.
    return _make_sharded_kernel(
        layout.n_tail_blocks,
        low_pos,
        group.k,
        batch_per_device,
        mesh,
        axis_name,
        backend,
        interpret,
        rolled,
        sieve=sieve,
        factored=(
            default_factor_k_in(group.k)
            if factored and group.k >= 2 and backend != "pallas"
            else 0
        ),
    )


def shard_operands(midstate, tail_const, bounds, mesh: Mesh, axis_name: str):
    """Place one dispatch's chunk descriptor on the mesh, asynchronously:
    slot blocks sharded contiguously along ``axis_name`` (block ``d`` on
    device ``d``, filled by ``ops.sweep.MeshRows``), midstate replicated."""
    row = NamedSharding(mesh, P(axis_name, None))
    rep = NamedSharding(mesh, P())
    return (
        jax.device_put(midstate, rep),
        jax.device_put(tail_const, row),
        jax.device_put(bounds, row),
    )


def sharded_invoke(
    kern, midstate, tail_const, bounds, mesh: Mesh, axis_name: str,
    thresh=None, groups=None,
):
    """Queue one sharded dispatch (see :func:`shard_operands`).
    ``thresh`` (per-shard sieve kernels only): the host's running-min h0
    as a plain int — replicated to every shard as a uint32 scalar.
    ``groups`` (the sharded dyn kernel only): the row-group extent of
    every shard's grid, replicated as an int32 scalar ahead of it."""
    rep = NamedSharding(mesh, P())
    extra = []
    if groups is not None:
        extra.append(jax.device_put(np.int32(groups), rep))
    if thresh is not None:
        extra.append(jax.device_put(np.uint32(thresh), rep))
    ops = shard_operands(midstate, tail_const, bounds, mesh, axis_name)
    return kern(*ops, *extra)


def sweep_min_hash_sharded(
    data: str,
    lower: int,
    upper: int,
    *,
    mesh: Optional[Mesh] = None,
    axis_name: str = MINER_AXIS,
    max_k: Optional[int] = None,
    batch_per_device: Optional[int] = None,
    backend: Optional[str] = None,
    interpret: bool = False,
    workload=None,
    sieve: Optional[bool] = None,
    factored: Optional[bool] = None,
) -> SweepResult:
    """Multi-chip ``(min Hash(data, n), argmin n)`` over inclusive
    ``[lower, upper]``; bit-exact vs the hashlib oracle, lowest-nonce ties.

    A dispatch has ``n_devices * batch_per_device`` slots (by default
    ``auto_tune``'s 1024 in all on the pallas tier, 256 a device on four);
    its rows spread evenly over the devices (``ops.sweep.MeshRows``) and
    the padding slots have empty lane bounds, masked in-kernel.

    ``sieve`` (ISSUE 14 satellite, None = the ``auto_tune`` rung for
    this backend): the PER-SHARD two-stage sieve — each dispatch carries
    the host's running-min h0 replicated to every shard, each shard's
    pass 1 seeds from it (and, on pallas, tightens its own local running
    min in SMEM scratch) ahead of the collective argmin cascade, and a
    survivor-less shard contributes the sentinel the cascade orders
    last.  Bit-exact either way.

    ``factored`` (ISSUE 16 satellite, None = the ``auto_tune`` rung):
    the outer/inner digit split, threaded per-shard through the xla
    sharded kernels — a mesh miner gets the single-device tier's 2.76×
    win.  Ignored by the sharded pallas branch (dyn kernels; real-TPU
    arbitration follow-on).

    The synchronous form of ``ops.sweep.SweepPipeline`` in mesh mode: one
    job through a pipeline of its own, closed on return.  Its one
    dispatcher thread enqueues every collective in job order, which the
    multi-host miner needs: each process must enqueue the same
    collectives in the same order.
    """
    if mesh is None:
        mesh = default_mesh(axis_name=axis_name)
    p = SweepPipeline(
        mesh=mesh, axis_name=axis_name, max_k=max_k, batch=batch_per_device,
        backend=backend, interpret=interpret, host_lane_budget=0,
        workload=workload, sieve=sieve, factored=factored,
    )
    try:
        return p.submit(data, lower, upper).result()
    finally:
        p.close()
