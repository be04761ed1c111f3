"""Pallas tier of the SHA-256 min-hash sweep (SURVEY §7 B6).

Why Pallas: the jnp tier's unrolled 64-round graph does not stay fused on
TPU — XLA materialises (B, N) uint32 intermediates to HBM between fusions,
capping throughput at ~2e7 nonce/s.  Here each grid program hashes a tile of
lanes entirely in VMEM/vector registers: inputs are a handful of scalar
template words (SMEM, table flattened to dodge 512B row padding) plus the
precomputed low-digit ASCII contribution tiles (VMEM, ~12 B/nonce
streamed).  Each program folds a *lane-wise* lexicographic running min
into VMEM scratch — pure compare/select, no cross-lane reduction (those
cost ~2 us/program and were ~35% of kernel time) — and the final program
does one cross-lane argmin into three SMEM output scalars.  TPU grid
programs run sequentially per core, so cross-program read-modify-write of
scratch is well-defined.  The hot loop never touches HBM.

Dispatch-count matters as much as kernel speed: every dispatch + result
fetch costs a fixed latency, so a call processes a *super-batch*
of up to ``batch`` chunks (grid axis 0) × ``10^k`` lanes each (grid axis 1
tiles) — about 10^9 nonces per dispatch at batch=1024, k=6 — and returns
just ``(min_h0, min_h1, argmin_flat)``.

Work decomposition matches ops/sweep.py: chunks are 10^k-aligned so high
digits are per-chunk template constants (host-folded); the k low digits'
ASCII contribution (pre-shifted into word positions) is a per-class device
constant computed once with plain XLA ops — identical for every chunk.
In-kernel div/mod-10 is avoided entirely (Mosaic lowers integer division
poorly).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .sha256 import (
    DigitPos,
    compress,
    compress_rolled,
    factor_low_pos,
    outer_patch_table,
)

U32_MAX = 0xFFFFFFFF
I32_MAX = 0x7FFFFFFF

# Lanes per grid program: (tile/128, 128) uint32 vectors.  4096 measured
# best on v5e with the lane-wise accumulator (r4 on-TPU autotune:
# 1.64e9 n/s at 4096 vs 1.58e9 at 8192 vs regressions at 16384+ from
# vector-register spills; see BASELINE.md).
DEFAULT_TILE = 4096
# Chunks per dispatch (grid axis 0). 1024 chunks x 10^6 lanes ~ 1e9 nonces
# per dispatch; SMEM footprint = batch * (n_words + 2) * 4 B.
DEFAULT_BATCH = 1024
# Chunk rows processed per grid program: amortises the per-program fixed
# cost (launch, window bookkeeping, iota, the accumulator read-modify-
# write) across cpb compressions without growing peak vector state (rows
# process sequentially, reusing registers).  r4 on-TPU scan: 1.73e9 n/s at
# cpb=1 -> 1.85e9 at cpb=8 (tile 4096); cpb=16+ regresses.
DEFAULT_CPB = 8


def _contrib_words(low_pos: Sequence[DigitPos]) -> Tuple[int, ...]:
    """Distinct tail-word indices touched by the k low (in-kernel) digits."""
    return tuple(sorted({dp.word for dp in low_pos}))


@functools.lru_cache(maxsize=64)
def _digit_contrib_np(
    k: int, low_pos: Tuple[DigitPos, ...], n_pad: int
) -> Tuple[np.ndarray, ...]:
    """(n_pad/128, 128) uint32 per touched word: OR-able ASCII contribution
    of lane i's k low decimal digits.  Host numpy (converted to an on-device
    constant inside each jit trace — caching device arrays here would leak
    tracers)."""
    i = np.arange(n_pad, dtype=np.int64)
    per_word: Dict[int, np.ndarray] = {}
    for j, dp in enumerate(low_pos):
        p = 10 ** (k - 1 - j)
        dig = ((i // p) % 10 + 48).astype(np.uint32) << np.uint32(dp.shift)
        per_word[dp.word] = per_word.get(dp.word, np.uint32(0)) | dig
    return tuple(
        per_word[w].reshape(n_pad // 128, 128) for w in _contrib_words(low_pos)
    )


def _build_call(
    n_tail_blocks: int,
    cwords: Tuple[int, ...],
    k: int,
    batch: int,
    tile: int,
    interpret: bool,
    cpb: Optional[int],
    sieve: bool = False,
):
    """Build the pallas_call shared by the static and dynamic factories.

    ``cwords``: the tail-word indices that receive a VMEM contribution
    input (in input order).  The kernel body is identical either way —
    contributions are pallas_call *inputs*; whether they are jit-trace
    constants (static factory, one kernel per digit class) or runtime
    arguments (dynamic factory, one kernel for every k=6 class) is decided
    by the jit wrapper around the returned call.

    ``sieve=True`` builds the TWO-STAGE variant (ISSUE 13): **pass 1**
    hashes every lane in ``h0``-only output-mask form and reduces it to a
    survivor predicate — ``h0 <= threshold`` in the sign-flipped int32
    domain, against a device-carried running minimum seeded from the extra
    ``thresh`` SMEM operand and tightened in SMEM scratch as the
    sequential grid folds new minima (no host round-trip); **pass 2**
    (the full ``(h0, h1)`` compression + lane-wise lexicographic fold +
    accumulator read-modify-write — the per-lane bookkeeping the sieve
    exists to skip) runs under ``pl.when`` only for groups containing a
    survivor.  Ties (``h0 == threshold``) conservatively survive, so a
    later lane equal on ``h0`` but smaller on ``(h1, nonce)`` is never
    lost — bit-exactness vs the hashlib oracle holds by construction.
    After the first dispatches the running min's ``h0`` falls like
    ``U32_MAX / nonces_swept`` and survivor groups become a vanishing
    fraction; steady state pays pass 1 only (see tools/roofline.py for
    the per-pass op accounting).

    Returns ``(call, n_pad, groups)``: ``call(n_groups, *operands)``
    runs the grid over ``n_groups`` of the ``groups = batch // cpb`` row
    groups.
    """
    n_lanes = 10**k
    if batch * n_lanes > I32_MAX:
        # The flat argmin index b * 10^k + i must fit int32 (Mosaic has no
        # cheap i64); past this the kernel would return silently WRONG
        # nonces — measured at k=7/batch=1024 before this guard existed.
        raise ValueError(
            f"batch ({batch}) * 10^k ({n_lanes}) lanes overflow the int32 "
            "argmin index; lower batch or max_k"
        )
    # Small chunks (k <= 3) fit one sub-tile; clamp tile to the padded lane
    # count so we never build a grid of empty programs.
    tile = max(1024, min(tile, math.ceil(n_lanes / 1024) * 1024))
    n_tiles = math.ceil(n_lanes / tile)
    n_pad = n_tiles * tile
    sub = tile // 128
    word_to_cidx = {w: m for m, w in enumerate(cwords)}

    n_words = n_tail_blocks * 16

    row_w = n_words + 2  # words per chunk row: template + lo_off + hi_off
    cpb = resolve_cpb(batch, cpb)
    groups = batch // cpb

    def kernel(midstate_ref, tailc_ref, *rest):
        # tailc_ref is the chunk table FLATTENED to 1-D, logical row layout
        # [word_0 .. word_{nw-1}, lo_off, hi_off]: SMEM pads every row of a
        # 2-D window to 512 B — (1024, 18) ate 512 KiB of the 1 MiB budget
        # and (2048, 18) overflowed it outright — while the 1-D form is
        # ~4 B/word (147 KiB at batch 2048).
        thresh_ref = None
        if sieve:  # extra SMEM operand: the host's running-min h0
            thresh_ref, rest = rest[0], rest[1:]
        contrib_refs = rest[: len(cwords)]
        th_ref = None
        if sieve:
            (
                h0_ref, h1_ref, idx_ref, a0_ref, a1_ref, ai_ref, th_ref,
            ) = rest[len(cwords) :]
        else:
            h0_ref, h1_ref, idx_ref, a0_ref, a1_ref, ai_ref = rest[len(cwords) :]
        g = pl.program_id(0)
        t = pl.program_id(1)
        rows = [g * cpb + j for j in range(cpb)]
        offs = [r * row_w for r in rows]
        los = [tailc_ref[o + n_words].astype(jnp.int32) for o in offs]
        his = [tailc_ref[o + n_words + 1].astype(jnp.int32) for o in offs]

        # First program initialises the lane-wise accumulators (VMEM
        # scratch persists across the sequential grid) to "no result".
        @pl.when((g == 0) & (t == 0))
        def _init():
            empty = jnp.full((sub, 128), I32_MAX, dtype=jnp.int32)
            a0_ref[...] = empty
            a1_ref[...] = empty
            ai_ref[...] = empty
            if sieve:
                # Seed the device-carried threshold from the dispatch
                # operand; later programs only TIGHTEN it (pass 2 below),
                # so the sieve sharpens across the sequential grid with
                # no host round-trip.
                th_ref[0] = thresh_ref[0]

        # Padding rows of a partial super-batch carry bounds (0, 0): a
        # fully-padded group (one a grid of ``n_groups`` still reaches)
        # skips all vector work with one scalar branch; a mixed group
        # wastes at most cpb-1 masked compressions, and at most one group
        # per dispatch is mixed.
        any_work = his[0] > los[0]
        for j in range(1, cpb):
            any_work = any_work | (his[j] > los[j])

        @pl.when(any_work)
        def _work():
            row = jax.lax.broadcasted_iota(jnp.int32, (sub, 128), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (sub, 128), 1)
            i = t * tile + row * 128 + col  # lane index within each chunk
            sbit = jnp.uint32(0x80000000)
            if interpret:
                from .sha256 import K

                # Stacked from inline scalars: pallas forbids closure-
                # captured array constants.
                k_table = jnp.stack([jnp.uint32(int(v)) for v in K])

            def _row_state(j, final_form):
                """Hash chunk row ``j``'s tile of lanes; the last block
                compresses in ``final_form`` output-mask form (True →
                ``(h0, h1)``, ``"h0"`` → pass 1's ``(h0,)``)."""
                state = tuple(midstate_ref[s] for s in range(8))
                for blk in range(n_tail_blocks):
                    w = []
                    for widx in range(blk * 16, (blk + 1) * 16):
                        base = tailc_ref[offs[j] + widx]
                        if widx in word_to_cidx:
                            w.append(
                                contrib_refs[word_to_cidx[widx]][...] | base
                            )
                        else:
                            # Constant word: keep the SMEM *scalar* —
                            # compress's lazy-broadcast grouping then runs
                            # every const-only chain (leading rounds,
                            # K-folds, σ of const schedule words) on the
                            # scalar unit instead of the VPU (a fully-
                            # constant tail block costs ~4x less than a
                            # vector one, measured on v5e).
                            w.append(base)
                    # The reduction reads only (h0, h1): the last block's
                    # compression drops the work feeding the dead digest
                    # words (final_only / its "h0" output-mask form).
                    last = blk == n_tail_blocks - 1
                    fo = final_form if last else False
                    # Mosaic wants the unrolled straight-line rounds
                    # (registers, software pipelining); interpret mode
                    # traces the kernel as plain XLA ops, where the
                    # unrolled DAG (x grid programs) sends XLA:CPU into
                    # minutes-long LLVM compiles — roll it.
                    if interpret:
                        state = compress_rolled(
                            state, w, k_table=k_table, final_only=fo
                        )
                    else:
                        state = compress(state, w, final_only=fo)
                return state

            def _full_fold():
                """The full (h0, h1) lexicographic min-fold + accumulator
                read-modify-write — the baseline kernel's whole body, and
                the sieve kernel's survivor-only pass 2."""
                l0 = l1 = li = None  # the group's lane-wise running min
                for j in range(cpb):
                    state = _row_state(j, True)
                    valid = (i >= los[j]) & (i < his[j])
                    h0 = jnp.where(valid, state[0], jnp.uint32(U32_MAX))
                    h1 = jnp.where(valid, state[1], jnp.uint32(U32_MAX))
                    # Mosaic has no unsigned reductions: compare in the
                    # sign-flipped int32 domain, where u32 order == s32
                    # order (x ^ 0x8000_0000).
                    h0b = jax.lax.bitcast_convert_type(h0 ^ sbit, jnp.int32)
                    h1b = jax.lax.bitcast_convert_type(h1 ^ sbit, jnp.int32)
                    idx = jnp.where(
                        valid, rows[j] * n_lanes + i, jnp.int32(I32_MAX)
                    )
                    if l0 is None:
                        l0, l1, li = h0b, h1b, idx
                    else:
                        better = (h0b < l0) | (
                            (h0b == l0)
                            & ((h1b < l1) | ((h1b == l1) & (idx < li)))
                        )
                        l0 = jnp.where(better, h0b, l0)
                        l1 = jnp.where(better, h1b, l1)
                        li = jnp.where(better, idx, li)

                # Lane-wise lexicographic running min: pure compare/select,
                # no cross-lane reduction — those cost ~2 us/program and
                # were ~35% of kernel time (measured v5e); they run once
                # per DISPATCH in _final below.  One scratch read-modify-
                # write per group (grid programs execute sequentially per
                # core, so this is safe).
                p0 = a0_ref[...]
                p1 = a1_ref[...]
                pi = ai_ref[...]
                better = (l0 < p0) | (
                    (l0 == p0) & ((l1 < p1) | ((l1 == p1) & (li < pi)))
                )
                a0_ref[...] = jnp.where(better, l0, p0)
                a1_ref[...] = jnp.where(better, l1, p1)
                ai_ref[...] = jnp.where(better, li, pi)

            if not sieve:
                _full_fold()
            else:
                # ---- pass 1: h0-only hash → survivor predicate.  The
                # epilogue per row is mask + select + flip + compare + OR
                # (~8 vector ops/lane/group) instead of the full fold's
                # ~22 (tools/roofline.py) — and NO h1 chain.
                th = th_ref[0]
                surv = None
                for j in range(cpb):
                    (h0,) = _row_state(j, "h0")
                    h0 = jnp.where(
                        (i >= los[j]) & (i < his[j]), h0, jnp.uint32(U32_MAX)
                    )
                    h0b = jax.lax.bitcast_convert_type(h0 ^ sbit, jnp.int32)
                    # <= not <: a tie on h0 may still win on (h1, nonce)
                    # — conservative tie survival keeps bit-exactness.
                    # Masked lanes (I32_MAX) survive only the degenerate
                    # U32_MAX threshold, where pass 2 masks them anyway.
                    s = h0b <= th
                    surv = s if surv is None else (surv | s)

                # ---- pass 2: survivor groups only — after the first few
                # dispatches a vanishing fraction (the running min's h0
                # falls like U32_MAX / nonces_swept).
                @pl.when(jnp.any(surv))
                def _survivors():
                    _full_fold()
                    # Tighten the device-carried threshold to the new
                    # accumulator minimum: later groups in this dispatch
                    # sieve against the freshest bound.
                    th_ref[0] = jnp.minimum(th_ref[0], jnp.min(a0_ref[...]))

        # Last program: one cross-lane lexicographic argmin over the
        # accumulator tile -> the three SMEM output scalars.  The row-group
        # extent may be a runtime operand (see ``call`` below), so the last
        # group is the grid's, not ``groups - 1``.
        @pl.when((g == pl.num_programs(0) - 1) & (t == n_tiles - 1))
        def _final():
            v0 = a0_ref[...]
            v1 = a1_ref[...]
            vi = ai_ref[...]
            m0 = jnp.min(v0)
            e0 = v0 == m0
            m1 = jnp.min(jnp.where(e0, v1, jnp.int32(I32_MAX)))
            e1 = e0 & (v1 == m1)
            mi = jnp.min(jnp.where(e1, vi, jnp.int32(I32_MAX)))
            h0_ref[0] = m0
            h1_ref[0] = m1
            idx_ref[0] = mi

    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),  # midstate (8,)
        pl.BlockSpec(memory_space=pltpu.SMEM),  # tail_const+bounds, flat (B*(nw+2),)
    ]
    if sieve:
        # The running-min threshold operand (1,), sign-flipped int32.
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    in_specs += [
        pl.BlockSpec((sub, 128), lambda g, t: (t, 0), memory_space=pltpu.VMEM)
        for _ in cwords
    ]
    out_specs = [pl.BlockSpec(memory_space=pltpu.SMEM) for _ in range(3)]
    out_shape = [
        jax.ShapeDtypeStruct((1,), jnp.int32),  # sign-flipped h0
        jax.ShapeDtypeStruct((1,), jnp.int32),  # sign-flipped h1
        jax.ShapeDtypeStruct((1,), jnp.int32),
    ]
    scratch = [pltpu.VMEM((sub, 128), jnp.int32) for _ in range(3)]
    if sieve:
        # The device-carried threshold: persists across the sequential
        # grid like the accumulators (SMEM — it is one scalar).
        scratch.append(pltpu.SMEM((1,), jnp.int32))

    def call(n_groups, *operands):
        """Run the grid over the first ``n_groups`` row groups: a static
        int, or a traced int32 scalar in ``[1, groups]``.  Rows fill the
        chunk table from slot 0, so the groups past a dispatch's last row
        hold none, and a grid that stops there skips their per-step cost
        (~0.33 us a grid step on a v5e, ~81 us an empty group) as well as
        their vector work."""
        return pl.pallas_call(
            kernel,
            grid=(n_groups, n_tiles),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch,
            interpret=interpret,
        )(*operands)

    return call, n_pad, groups


def resolve_cpb(batch: int, cpb: Optional[int] = None) -> int:
    """Chunk rows per grid program for a ``batch``-slot dispatch: ``cpb``
    when it divides ``batch``, else a ValueError; None gives the largest
    divisor of ``batch`` up to ``DEFAULT_CPB``, so small batches (tests,
    probes) still exercise the group-fold path."""
    if cpb is None:
        return next(
            c for c in range(min(DEFAULT_CPB, batch), 0, -1) if batch % c == 0
        )
    if cpb < 1 or batch % cpb:
        # An explicitly requested non-divisor would silently measure
        # something else; refuse.
        raise ValueError(f"cpb ({cpb}) must divide batch ({batch})")
    return cpb


def _unflip(h0b, h1b, idx):
    """SMEM outputs -> (u32 h0, u32 h1, i32 flat_idx) scalars."""
    sbit = jnp.uint32(0x80000000)
    min_h0 = jax.lax.bitcast_convert_type(h0b[0], jnp.uint32) ^ sbit
    min_h1 = jax.lax.bitcast_convert_type(h1b[0], jnp.uint32) ^ sbit
    return min_h0, min_h1, idx[0]


@functools.lru_cache(maxsize=256)
def make_pallas_minhash(
    n_tail_blocks: int,
    low_pos: Tuple[DigitPos, ...],
    k: int,
    batch: int = DEFAULT_BATCH,
    tile: int = DEFAULT_TILE,
    interpret: bool = False,
    cpb: Optional[int] = None,
    sieve: bool = False,
):
    """Build the jitted Pallas min-hash for one (layout, k, batch) class.

    Returned fn: ``(midstate (8,), tailc_bounds (B, nw+2))
    -> (min_h0, min_h1, flat_idx)`` — the global lexicographic min over the
    whole (B, 10^k) lane grid (hashes in the sign-flipped-int32 domain are
    compared; outputs are plain uint32), flat_idx = chunk_row * 10^k + lane,
    I32_MAX when every lane is masked out by bounds.

    ``sieve=True`` builds the two-stage variant (see :func:`_build_call`):
    the fn takes an extra ``thresh (1,) int32`` operand (the host's
    running-min h0, sign-flipped) and ``flat_idx == I32_MAX`` now also
    means "no lane survived the threshold" — the host keeps its best.
    """
    cwords = _contrib_words(low_pos)
    call, n_pad, groups = _build_call(
        n_tail_blocks, cwords, k, batch, tile, interpret, cpb, sieve=sieve
    )

    if sieve:

        @jax.jit
        def minhash(midstate, tailc_bounds, thresh):
            contribs = tuple(
                jnp.asarray(c) for c in _digit_contrib_np(k, low_pos, n_pad)
            )
            return _unflip(
                *call(
                    groups, midstate, tailc_bounds.reshape(-1), thresh,
                    *contribs,
                )
            )

        return minhash

    @jax.jit
    def minhash(midstate, tailc_bounds):
        contribs = tuple(
            jnp.asarray(c) for c in _digit_contrib_np(k, low_pos, n_pad)
        )
        return _unflip(
            *call(groups, midstate, tailc_bounds.reshape(-1), *contribs)
        )

    return minhash


def dyn_params(layout, k: int) -> Optional[Tuple[int, int]]:
    """``(w_lo, w_hi)`` of the dynamic kernel's word window for this
    layout's data length, or None when the (d, k) class lies outside the
    dyn domain (d == k — the d=1 class, whose lone digit byte sits one
    short of the d >= k+1 window).  The ONE eligibility predicate shared
    by the single-device driver, the sharded driver, and the AOT test —
    duplicating it risks the drivers silently diverging on kernel
    selection."""
    dp0 = layout.digit_pos[0]
    digit_off = dp0.word * 4 + (3 - dp0.shift // 8)
    w_lo, w_hi = dyn_window(digit_off, layout.n_tail_blocks * 16, k)
    low_pos = layout.digit_pos[layout.digit_count - k :]
    if all(w_lo <= dp.word <= w_hi for dp in low_pos):
        return w_lo, w_hi
    return None


def dyn_window(digit_off: int, n_words: int, k: int) -> Tuple[int, int]:
    """The static word window ``[w_lo, w_hi]`` that can carry the k low
    digits of ANY digit class d in [k+1, 20] (u64 max) for a message whose
    digits start at tail byte ``digit_off``: low digits of class d occupy
    bytes ``digit_off + d - k .. digit_off + d - 1``."""
    w_lo = (digit_off + (k + 1) - k) // 4
    w_hi = min((digit_off + 20 - 1) // 4, n_words - 1)
    return w_lo, w_hi


@functools.lru_cache(maxsize=64)
def make_pallas_minhash_dyn(
    n_tail_blocks: int,
    w_lo: int,
    w_hi: int,
    k: int,
    batch: int = DEFAULT_BATCH,
    tile: int = DEFAULT_TILE,
    interpret: bool = False,
    cpb: Optional[int] = None,
    sieve: bool = False,
):
    """Digit-position-DYNAMIC variant: one compiled kernel for every digit
    class whose k low digits land in tail words ``[w_lo, w_hi]`` — i.e. all
    d in [k+1, 20] sharing a tail-block count (see :func:`dyn_window`).

    Why it exists: each digit class is otherwise a distinct kernel whose
    first in-process use costs ~9 s of tracing + ~5 s of executable load
    (even on a persistent-cache hit) — a mid-job stall whenever a sweep
    crosses a decimal digit boundary (measured r5, fleet path in
    BASELINE.md).  Here the per-class digit contributions become RUNTIME
    inputs (one (n_pad/128, 128) u32 tile per window word, zero tiles for
    untouched words), so every class shares one trace + one executable.

    Cost vs the static kernel: window words are vector (OR with a zero
    tile) even when the class leaves them constant, so some const-only
    schedule chains move from the scalar unit to the VPU.

    Returned fn: ``(midstate, tailc_bounds, n_groups, *contribs)`` ->
    ``(min_h0, min_h1, flat_idx)``; contribs must have length
    ``w_hi - w_lo + 1`` (see :func:`window_contribs_np`).  With
    ``sieve=True`` the fn takes ``(midstate, tailc_bounds, n_groups,
    thresh, *contribs)`` — the two-stage variant of :func:`_build_call`.

    ``n_groups`` (an int32 scalar) is the grid's row-group extent: the
    dispatch's rows, which fill slots from 0, rounded up to whole groups
    of ``cpb`` (:func:`resolve_cpb`).  A dispatch of 9 rows steps through
    2 groups instead of ``batch // cpb``; it is clamped to ``[1, batch //
    cpb]``, so the grid never reads past the chunk table.
    """
    cwords = tuple(range(w_lo, w_hi + 1))
    call, n_pad, groups = _build_call(
        n_tail_blocks, cwords, k, batch, tile, interpret, cpb, sieve=sieve
    )

    # *rest is (thresh, *contribs) with the sieve, else contribs alone.
    @jax.jit
    def minhash(midstate, tailc_bounds, n_groups, *rest):
        return _unflip(
            *call(
                jnp.clip(n_groups, 1, groups), midstate,
                tailc_bounds.reshape(-1), *rest,
            )
        )

    return minhash, n_pad


def _build_factored_call(
    n_tail_blocks: int,
    owords: Tuple[int, ...],
    in_cwords: Tuple[int, ...],
    first_inner_word: int,
    k: int,
    k_in: int,
    batch: int,
    tile: int,
    interpret: bool,
    cpb: Optional[int],
    sieve: bool,
):
    """Build the pallas_call of the FACTORED kernel (ISSUE 14): the lane
    axis ``10^k`` split into ``10^(k - k_in)`` outer digit groups (a new
    sequential grid axis) × ``10^k_in`` inner lanes (the iota/tile axis).

    Per (chunk-row, outer-group) visit the kernel patches the group's
    outer-digit ASCII into the template with pure scalar ORs from the
    ``outer_tab`` SMEM operand, computes the **per-group scalar round
    prefix** — every tail block before ``first_inner_word`` plus that
    block's leading rounds, entirely on the scalar unit via ``compress``'s
    ``stop_round=`` entry point — and resumes the vector rounds from the
    carried ``group_state`` at the first inner-digit word.  Only the
    ``k_in`` inner digits ride VMEM contribution tiles, so every word the
    baseline dyn kernel streamed as a window vector (and every compress /
    σ-schedule chain it fed) stays on the scalar unit: 3002 → 2910 folded
    vector ops/lane on the flagship 1-block shape (tools/roofline.py
    ``--ops-only`` audits any shape).

    ``sieve=True`` composes the PR-13 two-stage sieve: pass 1 hashes
    h0-only **resuming from the same per-group prefix pass 2 uses** (the
    group-prefix reuse), the survivor predicate/threshold scratch
    semantics are unchanged, and the threshold now tightens across BOTH
    sequential axes (chunk-row groups and outer digit groups).

    Returns ``(call, n_pad)``; n_pad is the padded INNER lane count.
    """
    n_lanes = 10**k
    s_in = 10**k_in
    g_count = 10 ** (k - k_in)
    if batch * n_lanes > I32_MAX:
        # Same int32 flat-argmin guard as _build_call: the factored index
        # remaps to chunk_row * 10^k + og * 10^k_in + lane.
        raise ValueError(
            f"batch ({batch}) * 10^k ({n_lanes}) lanes overflow the int32 "
            "argmin index; lower batch or max_k"
        )
    tile = max(1024, min(tile, math.ceil(s_in / 1024) * 1024))
    n_tiles = math.ceil(s_in / tile)
    n_pad = n_tiles * tile
    sub = tile // 128
    word_to_cidx = {w: m for m, w in enumerate(in_cwords)}
    ow_idx = {w: m for m, w in enumerate(owords)}
    n_ow = len(owords)

    n_words = n_tail_blocks * 16
    row_w = n_words + 2
    if cpb is None:
        cpb = next(
            c for c in range(min(DEFAULT_CPB, batch), 0, -1) if batch % c == 0
        )
    elif cpb < 1 or batch % cpb:
        raise ValueError(f"cpb ({cpb}) must divide batch ({batch})")
    groups = batch // cpb
    fib, prefix_rounds = divmod(first_inner_word, 16)

    def kernel(midstate_ref, tailc_ref, *rest):
        thresh_ref = None
        if sieve:
            thresh_ref, rest = rest[0], rest[1:]
        otab_ref, rest = rest[0], rest[1:]
        contrib_refs = rest[: len(in_cwords)]
        th_ref = None
        if sieve:
            (
                h0_ref, h1_ref, idx_ref, a0_ref, a1_ref, ai_ref, th_ref,
            ) = rest[len(in_cwords) :]
        else:
            h0_ref, h1_ref, idx_ref, a0_ref, a1_ref, ai_ref = rest[
                len(in_cwords) :
            ]
        c = pl.program_id(0)  # chunk-row group (cpb rows each)
        og = pl.program_id(1)  # outer digit group — sequential, like c/t
        t = pl.program_id(2)  # inner lane tile
        rows = [c * cpb + j for j in range(cpb)]
        offs = [r * row_w for r in rows]
        los = [tailc_ref[o + n_words].astype(jnp.int32) for o in offs]
        his = [tailc_ref[o + n_words + 1].astype(jnp.int32) for o in offs]
        # Per-group lane bounds (scalar clips): clipping the chunk bounds
        # into [0, s_in) both rebases them onto the inner iota and masks
        # every lane of a group the chunk's [lo, hi) doesn't reach —
        # padding lanes i >= s_in are masked for free since ghi <= s_in.
        glo = [jnp.clip(lo - og * s_in, 0, s_in) for lo in los]
        ghi = [jnp.clip(hi - og * s_in, 0, s_in) for hi in his]

        @pl.when((c == 0) & (og == 0) & (t == 0))
        def _init():
            empty = jnp.full((sub, 128), I32_MAX, dtype=jnp.int32)
            a0_ref[...] = empty
            a1_ref[...] = empty
            ai_ref[...] = empty
            if sieve:
                th_ref[0] = thresh_ref[0]

        any_work = ghi[0] > glo[0]
        for j in range(1, cpb):
            any_work = any_work | (ghi[j] > glo[j])

        @pl.when(any_work)
        def _work():
            row = jax.lax.broadcasted_iota(jnp.int32, (sub, 128), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (sub, 128), 1)
            i = t * tile + row * 128 + col  # INNER lane index
            sbit = jnp.uint32(0x80000000)
            if interpret:
                from .sha256 import K

                k_table = jnp.stack([jnp.uint32(int(v)) for v in K])

            def comp(state, w, final_only=False, stop_round=None, group_state=None):
                # Mosaic wants the unrolled rounds; interpret mode rolls
                # them (same rationale as _build_call's _row_state).
                if interpret:
                    return compress_rolled(
                        state, w, k_table=k_table, final_only=final_only,
                        stop_round=stop_round, group_state=group_state,
                    )
                return compress(
                    state, w, final_only=final_only,
                    stop_round=stop_round, group_state=group_state,
                )

            def _row_blocks(j):
                """Row j's w words for outer group og: template scalars,
                outer digits OR-patched as per-group SMEM scalars, inner
                digits as VMEM contribution tiles."""
                blocks = []
                for blk in range(n_tail_blocks):
                    w = []
                    for widx in range(blk * 16, (blk + 1) * 16):
                        base = tailc_ref[offs[j] + widx]
                        if widx in ow_idx:
                            base = base | otab_ref[og * n_ow + ow_idx[widx]]
                        if widx in word_to_cidx:
                            w.append(
                                contrib_refs[word_to_cidx[widx]][...] | base
                            )
                        else:
                            w.append(base)
                    blocks.append(w)
                return blocks

            def _row_prefix(blocks):
                """The per-group scalar round prefix (computed once per
                row-group visit, shared by pass 1 AND pass 2): blocks
                before the first inner word run whole on the scalar unit,
                and that block's leading rounds stop at the carried
                group_state."""
                state = tuple(midstate_ref[s] for s in range(8))
                for b in range(fib):
                    state = comp(state, blocks[b])
                return state, comp(state, blocks[fib], stop_round=prefix_rounds)

            def _row_state(pre, final_form):
                """Vector rounds of one row: resume block fib from the
                carried group state, then any remaining blocks."""
                blocks, state_fib, gs = pre
                st = state_fib
                for b in range(fib, n_tail_blocks):
                    fo = final_form if b == n_tail_blocks - 1 else False
                    if b == fib:
                        st = comp(st, blocks[b], final_only=fo, group_state=gs)
                    else:
                        st = comp(st, blocks[b], final_only=fo)
                return st

            pres = []
            for j in range(cpb):
                blocks = _row_blocks(j)
                state_fib, gs = _row_prefix(blocks)
                pres.append((blocks, state_fib, gs))

            def _full_fold():
                """The full (h0, h1) lexicographic min-fold + accumulator
                read-modify-write — identical bookkeeping to the baseline
                kernel's, at the inner-lane tile shape."""
                l0 = l1 = li = None
                for j in range(cpb):
                    state = _row_state(pres[j], True)
                    valid = (i >= glo[j]) & (i < ghi[j])
                    h0 = jnp.where(valid, state[0], jnp.uint32(U32_MAX))
                    h1 = jnp.where(valid, state[1], jnp.uint32(U32_MAX))
                    h0b = jax.lax.bitcast_convert_type(h0 ^ sbit, jnp.int32)
                    h1b = jax.lax.bitcast_convert_type(h1 ^ sbit, jnp.int32)
                    # Global flat index: scalar base + inner lane (the
                    # scalar part folds off the VPU like the baseline's
                    # rows[j] * n_lanes term).
                    base_j = rows[j] * n_lanes + og * s_in
                    idx = jnp.where(valid, base_j + i, jnp.int32(I32_MAX))
                    if l0 is None:
                        l0, l1, li = h0b, h1b, idx
                    else:
                        better = (h0b < l0) | (
                            (h0b == l0)
                            & ((h1b < l1) | ((h1b == l1) & (idx < li)))
                        )
                        l0 = jnp.where(better, h0b, l0)
                        l1 = jnp.where(better, h1b, l1)
                        li = jnp.where(better, idx, li)

                p0 = a0_ref[...]
                p1 = a1_ref[...]
                pi = ai_ref[...]
                better = (l0 < p0) | (
                    (l0 == p0) & ((l1 < p1) | ((l1 == p1) & (li < pi)))
                )
                a0_ref[...] = jnp.where(better, l0, p0)
                a1_ref[...] = jnp.where(better, l1, p1)
                ai_ref[...] = jnp.where(better, li, pi)

            if not sieve:
                _full_fold()
            else:
                # Pass 1: h0-only, resuming from the SAME per-group
                # prefix pass 2 reuses below.
                th = th_ref[0]
                surv = None
                for j in range(cpb):
                    (h0,) = _row_state(pres[j], "h0")
                    h0 = jnp.where(
                        (i >= glo[j]) & (i < ghi[j]), h0, jnp.uint32(U32_MAX)
                    )
                    h0b = jax.lax.bitcast_convert_type(h0 ^ sbit, jnp.int32)
                    # <= not <: conservative tie survival (ISSUE 13).
                    s = h0b <= th
                    surv = s if surv is None else (surv | s)

                @pl.when(jnp.any(surv))
                def _survivors():
                    _full_fold()
                    th_ref[0] = jnp.minimum(th_ref[0], jnp.min(a0_ref[...]))

        @pl.when((c == groups - 1) & (og == g_count - 1) & (t == n_tiles - 1))
        def _final():
            v0 = a0_ref[...]
            v1 = a1_ref[...]
            vi = ai_ref[...]
            m0 = jnp.min(v0)
            e0 = v0 == m0
            m1 = jnp.min(jnp.where(e0, v1, jnp.int32(I32_MAX)))
            e1 = e0 & (v1 == m1)
            mi = jnp.min(jnp.where(e1, vi, jnp.int32(I32_MAX)))
            h0_ref[0] = m0
            h1_ref[0] = m1
            idx_ref[0] = mi

    grid = (groups, g_count, n_tiles)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),  # midstate (8,)
        pl.BlockSpec(memory_space=pltpu.SMEM),  # tail_const+bounds, flat
    ]
    if sieve:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))  # thresh (1,)
    # Per-group outer-digit patch table, flat (10^k_out * n_ow,): tiny
    # (<= ~8 KB at k_out=3) next to the chunk table's ~147 KB.
    in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    in_specs += [
        pl.BlockSpec(
            (sub, 128), lambda c, og, t: (t, 0), memory_space=pltpu.VMEM
        )
        for _ in in_cwords
    ]
    out_specs = [pl.BlockSpec(memory_space=pltpu.SMEM) for _ in range(3)]
    out_shape = [
        jax.ShapeDtypeStruct((1,), jnp.int32),  # sign-flipped h0
        jax.ShapeDtypeStruct((1,), jnp.int32),  # sign-flipped h1
        jax.ShapeDtypeStruct((1,), jnp.int32),
    ]
    scratch = [pltpu.VMEM((sub, 128), jnp.int32) for _ in range(3)]
    if sieve:
        scratch.append(pltpu.SMEM((1,), jnp.int32))

    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )
    return call, n_pad


@functools.lru_cache(maxsize=256)
def make_pallas_minhash_factored(
    n_tail_blocks: int,
    low_pos: Tuple[DigitPos, ...],
    k: int,
    k_in: int,
    batch: int = DEFAULT_BATCH,
    tile: int = DEFAULT_TILE,
    interpret: bool = False,
    cpb: Optional[int] = None,
    sieve: bool = False,
):
    """Build the jitted FACTORED Pallas min-hash for one (layout, k,
    batch) class (ISSUE 14) — per-class STATIC (see ops/sweep.py
    ``_build_kernel`` for why the dyn window can't factor).

    Same calling convention and output contract as
    :func:`make_pallas_minhash`: ``(midstate (8,), tailc_bounds (B,
    nw+2))`` — plus ``thresh (1,) int32`` first among the extras when
    ``sieve=True`` — returning ``(min_h0, min_h1, flat_idx)`` with
    ``flat_idx = chunk_row * 10^k + lane_in_chunk`` (the outer/inner
    remap happens in-kernel), I32_MAX when masked out or nothing
    survived the threshold.  The outer-digit patch table and the inner
    contribution tiles are trace constants of the jit wrapper.
    """
    split = factor_low_pos(low_pos, k_in)
    owords, otab_np = outer_patch_table(split.outer_pos)
    in_cwords = _contrib_words(split.inner_pos)
    call, n_pad = _build_factored_call(
        n_tail_blocks,
        owords,
        in_cwords,
        split.first_inner_word,
        k,
        k_in,
        batch,
        tile,
        interpret,
        cpb,
        sieve,
    )
    otab_flat = otab_np.reshape(-1)
    inner_pos = split.inner_pos

    if sieve:

        @jax.jit
        def minhash(midstate, tailc_bounds, thresh):
            contribs = tuple(
                jnp.asarray(c)
                for c in _digit_contrib_np(k_in, inner_pos, n_pad)
            )
            return _unflip(
                *call(
                    midstate, tailc_bounds.reshape(-1), thresh,
                    jnp.asarray(otab_flat), *contribs,
                )
            )

        return minhash

    @jax.jit
    def minhash(midstate, tailc_bounds):
        contribs = tuple(
            jnp.asarray(c) for c in _digit_contrib_np(k_in, inner_pos, n_pad)
        )
        return _unflip(
            *call(
                midstate, tailc_bounds.reshape(-1),
                jnp.asarray(otab_flat), *contribs,
            )
        )

    return minhash


@functools.lru_cache(maxsize=8)
def zero_tile_np(n_pad: int) -> np.ndarray:
    """One shared all-zero contribution tile per lane-pad size — untouched
    window words across every digit class alias it (and its single device
    copy) instead of pinning a fresh ~4 MB buffer each."""
    z = np.zeros((n_pad // 128, 128), dtype=np.uint32)
    z.setflags(write=False)
    return z


@functools.lru_cache(maxsize=64)
def window_contribs_np(
    k: int, low_pos: Tuple[DigitPos, ...], w_lo: int, w_hi: int, n_pad: int
) -> Tuple[np.ndarray, ...]:
    """Per-window-word contribution tiles for one digit class, the shared
    zero tile for window words this class's digits don't touch."""
    for dp in low_pos:
        if not w_lo <= dp.word <= w_hi:
            raise ValueError(
                f"digit word {dp.word} outside dyn window [{w_lo}, {w_hi}]"
            )
    per_word = dict(
        zip(_contrib_words(low_pos), _digit_contrib_np(k, low_pos, n_pad))
    )
    zero = zero_tile_np(n_pad)
    return tuple(per_word.get(w, zero) for w in range(w_lo, w_hi + 1))
