"""Blockwise nonce-range sweep: decomposition + jitted min-hash kernel.

This is the TPU-native replacement for the reference miner's scalar hot loop
(``bitcoin/miner/miner.go`` intended behavior: ``for n in [lo,hi]:
h = Hash(data, n); track min`` — SURVEY §3.6).  The "long dimension" here is
the nonce space (up to 2^64, ``bitcoin/message.go:21``), swept blockwise with
O(1) device state per chunk — the same pattern long-context frameworks use
for sequence parallelism, applied to the nonce axis.

Decomposition invariants:

- Nonces are grouped by decimal **digit count** ``d`` (the hashed string's
  length depends on it), then into **10^k-aligned chunks** so the high
  ``d-k`` digits are constant per chunk and can be folded into the message
  template host-side; only the low ``k`` digits vary in-kernel, generated
  from a lane iota by div/mod-10 (all < 2^31, safe in int32).
- A kernel call processes a batch of B chunks at once (shape ``(B, 10^k)``),
  returning the lexicographic min of the big-endian ``(h0, h1)`` hash pair
  and the flat argmin lane, lowest-nonce tie-break.  Batches are dispatched
  asynchronously so the device pipeline stays full while the host prepares
  the next templates.
"""

from __future__ import annotations

import collections
import os as _os
import time as _time
from dataclasses import dataclass
from functools import lru_cache
from typing import Deque, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import trace as _trace
from ..utils.metrics import METRICS
from ..utils.platform import is_tpu, pallas_platform
from .sha256 import (
    DigitPos,
    MsgLayout,
    build_layout,
    compress,
    compress_rolled,
    factor_low_pos,
    outer_patch_table,
)

U32_MAX = 0xFFFFFFFF
I32_MAX = 0x7FFFFFFF


# --------------------------------------------------------------------------
# Range decomposition (host)
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class Chunk:
    """A 10^k-aligned slice of a digit bucket: nonces ``base + [lo_off,
    hi_off)`` all share the same decimal digit count and high digits."""

    base: int
    lo_off: int
    hi_off: int  # exclusive


@dataclass(frozen=True)
class ChunkGroup:
    """Chunks sharing digit count ``d`` and low-digit count ``k`` (and hence
    one compiled kernel + one message layout)."""

    d: int
    k: int
    chunks: Tuple[Chunk, ...]


def decompose_range(lower: int, upper: int, max_k: int = 6) -> Iterator[ChunkGroup]:
    """Split inclusive ``[lower, upper]`` into digit-bucketed aligned chunks.

    ``max_k`` caps lanes-per-chunk at 10^max_k; larger buckets become many
    chunks.  Yields groups in ascending nonce order.
    """
    if lower > upper:
        raise ValueError(f"empty nonce range [{lower}, {upper}]")
    if lower < 0:
        raise ValueError(f"negative nonce {lower}")
    d_lo = len(str(lower))
    d_hi = len(str(upper))
    for d in range(d_lo, d_hi + 1):
        bucket_lo = 0 if d == 1 else 10 ** (d - 1)
        bucket_hi = 10**d - 1
        lo = max(lower, bucket_lo)
        hi = min(upper, bucket_hi)
        if lo > hi:
            continue
        k = 1 if d == 1 else min(d - 1, max_k)
        span = 10**k
        chunks = []
        for c in range(lo // span, hi // span + 1):
            base = c * span
            chunks.append(
                Chunk(base=base, lo_off=max(lo - base, 0), hi_off=min(hi - base + 1, span))
            )
        yield ChunkGroup(d=d, k=k, chunks=tuple(chunks))


# --------------------------------------------------------------------------
# The jitted kernel (jnp tier — B6 adds the Pallas tier)
# --------------------------------------------------------------------------


def default_factor_k_in(k: int) -> int:
    """The factored kernel's inner digit count for a ``k``-digit lane axis
    (ISSUE 14): keep the outer group count ``10^(k - k_in)`` at <= 1000
    (the sequential per-group loop / grid axis) while leaving the inner
    lane tile as wide as that allows.  k=6 → 3 (1000 groups × 1000
    lanes, the flagship pallas shape); k=5 → 3; k=2 → 1.  Shared by the
    kernel builders and tools/roofline.py so the op audit models exactly
    the split that runs."""
    return min(3, max(1, k - 2))


def make_kernel_body(
    n_tail_blocks: int,
    low_pos: Tuple[DigitPos, ...],
    k: int,
    batch: int,
    rolled: Optional[bool] = None,
    sieve: bool = False,
    factored: int = 0,
):
    """Build the pure (un-jitted) min-hash kernel body for one
    (layout, k, batch) shape class.

    Returned fn: ``(midstate (8,), tail_const (B, nw), bounds (B, 2))
    -> (min_h0, min_h1, flat_idx)`` where flat_idx indexes the (B, 10^k)
    lane grid row-major, or I32_MAX if every lane was masked out.  Pure so
    the multi-chip layer can re-trace it inside ``shard_map``
    (bitcoin_miner_tpu.parallel.sweep).

    ``rolled`` picks the compression form: the unrolled straight-line DAG
    (best on TPU — fused, register-resident) vs the fori_loop form (XLA:CPU
    chokes on the unrolled DAG's LLVM compile).  None = by platform.

    ``sieve=True`` is the two-stage variant (ISSUE 13): the fn takes an
    extra uint32 scalar ``thresh`` (the host's running-min h0); pass 1
    hashes every lane in h0-only output-mask form and reduces it to one
    ``any(h0 <= thresh)`` survivor bit (ties conservatively survive);
    the full ``(h0, h1)`` fold + argmin runs under ``lax.cond`` only
    when a survivor exists, else ``(U32_MAX, U32_MAX, I32_MAX)`` comes
    back and the host keeps its best.  Unfactored, this tier has no
    sequential dimension, so the threshold tightens only between
    dispatches (host-side); the pallas tier also tightens it across the
    grid in SMEM scratch.

    ``factored=k_in`` (ISSUE 14) factors the lane axis into ``10^(k -
    k_in)`` outer × ``10^k_in`` inner digit groups: the lane iota covers
    only the low ``k_in`` digits, the outer digits become an outer
    ``fori_loop`` whose ASCII bytes patch the template as per-group
    ``(B, 1)`` scalars, and every round before the first inner-digit
    word is computed once per group at the scalar column shape
    (``compress``'s ``stop_round=`` / ``group_state=`` entry points) —
    the per-group scalar round prefix is shared by the sieve's pass 1
    AND pass 2.  Composing with ``sieve=True``, the group loop IS a
    sequential dimension, so the threshold now also tightens across
    groups within one dispatch (``min(thresh, carried best h0)``) —
    the xla tier's analogue of the pallas SMEM tightening.
    """
    n_lanes = 10**k
    if rolled is None:
        rolled = not is_tpu()
    comp = compress_rolled if rolled else compress

    def _assemble(midstate, tail_const):
        """Shared w-word assembly: per-block word lists + initial state."""
        i = jnp.arange(n_lanes, dtype=jnp.int32)
        # ASCII of the k low decimal digits of each lane index.
        contrib = {}
        for j, dp in enumerate(low_pos):
            p = 10 ** (k - 1 - j)
            dig = ((i // p) % 10 + 48).astype(jnp.uint32) << jnp.uint32(dp.shift)
            contrib[dp.word] = contrib[dp.word] | dig if dp.word in contrib else dig

        state = tuple(midstate[s] for s in range(8))  # scalars, broadcast below
        blocks = []
        for b in range(n_tail_blocks):
            w = []
            for widx in range(b * 16, (b + 1) * 16):
                col = tail_const[:, widx][:, None]  # (B, 1)
                if widx in contrib:
                    w.append(col | contrib[widx][None, :])  # (B, N)
                else:
                    w.append(col)
            blocks.append(w)
        return i, state, blocks

    def _hash(state, blocks, final_form):
        """Run the blocks; the last compresses in ``final_form`` output-
        mask form (True → (h0, h1), "h0" → pass 1's (h0,))."""
        for b, w in enumerate(blocks):
            last = b == n_tail_blocks - 1
            state = comp(state, w, final_only=(final_form if last else False))
        return state

    def _fold(i, state, bounds, lanes=n_lanes):
        """The full lexicographic min + argmin reduction (both tiers'
        pass 2; the whole baseline kernel).  ``lanes`` is the fold's lane
        width — ``n_lanes`` for the baseline grid, ``10^k_in`` for one
        outer group of the factored kernel."""
        h0 = jnp.broadcast_to(state[0], (batch, lanes))
        h1 = jnp.broadcast_to(state[1], (batch, lanes))

        valid = (i[None, :] >= bounds[:, :1]) & (i[None, :] < bounds[:, 1:2])
        h0 = jnp.where(valid, h0, jnp.uint32(U32_MAX))
        h1 = jnp.where(valid, h1, jnp.uint32(U32_MAX))

        h0f = h0.reshape(-1)
        h1f = h1.reshape(-1)
        validf = valid.reshape(-1)
        flat = jnp.arange(batch * lanes, dtype=jnp.int32)

        min_h0 = jnp.min(h0f)
        e0 = h0f == min_h0
        h1m = jnp.where(e0, h1f, jnp.uint32(U32_MAX))
        min_h1 = jnp.min(h1m)
        e1 = e0 & (h1f == min_h1) & validf
        flat_idx = jnp.min(jnp.where(e1, flat, jnp.int32(I32_MAX)))
        return min_h0, min_h1, flat_idx

    if factored:
        from jax import lax

        split = factor_low_pos(low_pos, factored)
        s_in = 10**split.k_in
        g_count = 10**split.k_out
        owords, otab_np = outer_patch_table(split.outer_pos)
        owidx = {wd: m for m, wd in enumerate(owords)}
        fib, prefix_rounds = divmod(split.first_inner_word, 16)

        def _assemble_group(midstate, tail_const, og):
            """Per-outer-group w assembly: inner-digit contributions over
            the 10^k_in lane iota (vector), outer group ``og``'s digits
            OR-patched into the template as ``(B, 1)`` scalar columns."""
            i = jnp.arange(s_in, dtype=jnp.int32)
            contrib = {}
            for j, dp in enumerate(split.inner_pos):
                p = 10 ** (split.k_in - 1 - j)
                dig = ((i // p) % 10 + 48).astype(jnp.uint32) << jnp.uint32(dp.shift)
                contrib[dp.word] = (
                    contrib[dp.word] | dig if dp.word in contrib else dig
                )
            orow = lax.dynamic_index_in_dim(
                jnp.asarray(otab_np), og, 0, keepdims=False
            )
            state = tuple(midstate[s] for s in range(8))
            blocks = []
            for b in range(n_tail_blocks):
                wl = []
                for widx in range(b * 16, (b + 1) * 16):
                    col = tail_const[:, widx][:, None]  # (B, 1)
                    if widx in owidx:
                        col = col | orow[owidx[widx]]  # per-group scalar OR
                    if widx in contrib:
                        wl.append(col | contrib[widx][None, :])  # (B, s_in)
                    else:
                        wl.append(col)
                blocks.append(wl)
            return i, state, blocks

        def _group_prefix(state, blocks):
            """The per-group scalar round prefix: every block before the
            first inner-digit word, plus that block's leading rounds, all
            at the ``(B, 1)`` group-scalar shape — computed ONCE per
            group and shared by the sieve's pass 1 and pass 2.  Returns
            ``(state entering block fib, carried group_state)``."""
            for b in range(fib):
                state = comp(state, blocks[b])
            return state, comp(state, blocks[fib], stop_round=prefix_rounds)

        def _hash_resumed(state_fib, gs, blocks, final_form):
            """The vector rounds: resume block ``fib`` from the carried
            group state, then run any remaining blocks normally."""
            st = state_fib
            for b in range(fib, n_tail_blocks):
                fo = final_form if b == n_tail_blocks - 1 else False
                if b == fib:
                    st = comp(st, blocks[b], final_only=fo, group_state=gs)
                else:
                    st = comp(st, blocks[b], final_only=fo)
            return st

        def _combine(carry, h0, h1, fi, og):
            """Fold one group's result into the carried best.  Full
            lexicographic compare INCLUDING the remapped global flat
            index: ties across groups are NOT first-wins (a later
            group's row-0 lane is a lower flat index — and nonce — than
            an earlier group's row-3 lane)."""
            bh0, bh1, bidx = carry
            gidx = jnp.where(
                fi == jnp.int32(I32_MAX),
                jnp.int32(I32_MAX),
                (fi // s_in) * n_lanes + og * s_in + fi % s_in,
            )
            better = (h0 < bh0) | (
                (h0 == bh0) & ((h1 < bh1) | ((h1 == bh1) & (gidx < bidx)))
            )
            return (
                jnp.where(better, h0, bh0),
                jnp.where(better, h1, bh1),
                jnp.where(better, gidx, bidx),
            )

        _start = (
            jnp.uint32(U32_MAX), jnp.uint32(U32_MAX), jnp.int32(I32_MAX),
        )

        if not sieve:

            def kernel(midstate, tail_const, bounds):
                def body(og, carry):
                    i, state, blocks = _assemble_group(midstate, tail_const, og)
                    state_fib, gs = _group_prefix(state, blocks)
                    # Per-group lane bounds: clipping host bounds into
                    # [0, s_in) also masks every lane of a group the
                    # chunk's [lo, hi) doesn't reach.
                    gb = jnp.clip(bounds - og * s_in, 0, s_in)
                    st = _hash_resumed(state_fib, gs, blocks, True)
                    return _combine(
                        carry, *_fold(i, st, gb, lanes=s_in), og
                    )

                return lax.fori_loop(0, g_count, body, _start)

            return kernel

        def kernel(midstate, tail_const, bounds, thresh):
            def body(og, carry):
                i, state, blocks = _assemble_group(midstate, tail_const, og)
                state_fib, gs = _group_prefix(state, blocks)
                gb = jnp.clip(bounds - og * s_in, 0, s_in)
                # The group loop is a sequential dimension: tighten the
                # dispatch threshold with the best h0 carried so far, so
                # later groups sieve against the freshest bound (the xla
                # analogue of the pallas SMEM-scratch tightening).
                th = jnp.minimum(thresh, carry[0])
                # Pass 1: h0-only from the shared group prefix.
                (p1_h0,) = _hash_resumed(state_fib, gs, blocks, "h0")
                h0v = jnp.broadcast_to(p1_h0, (batch, s_in))
                valid = (i[None, :] >= gb[:, :1]) & (i[None, :] < gb[:, 1:2])
                h0v = jnp.where(valid, h0v, jnp.uint32(U32_MAX))
                # <= not <: ties conservatively survive (see above).
                surv = jnp.any(h0v <= th)

                def _pass2(_):
                    return _fold(
                        i, _hash_resumed(state_fib, gs, blocks, True), gb,
                        lanes=s_in,
                    )

                def _none(_):
                    return _start

                return _combine(carry, *lax.cond(surv, _pass2, _none, 0), og)

            return lax.fori_loop(0, g_count, body, _start)

        return kernel

    if not sieve:

        def kernel(midstate, tail_const, bounds):
            i, state, blocks = _assemble(midstate, tail_const)
            # Last block: only (h0, h1) survive into the reduction, so
            # skip the dead digest words (compress final_only).
            return _fold(i, _hash(state, blocks, True), bounds)

        return kernel

    def kernel(midstate, tail_const, bounds, thresh):
        from jax import lax

        i, state, blocks = _assemble(midstate, tail_const)
        # Pass 1: h0 only (output-mask form), one survivor bit.
        (p1_h0,) = _hash(state, blocks, "h0")
        h0 = jnp.broadcast_to(p1_h0, (batch, n_lanes))
        valid = (i[None, :] >= bounds[:, :1]) & (i[None, :] < bounds[:, 1:2])
        h0 = jnp.where(valid, h0, jnp.uint32(U32_MAX))
        # <= not <: an h0 tie may still win on (h1, nonce) — conservative
        # tie survival keeps bit-exactness vs the oracle.
        surv = jnp.any(h0 <= thresh)

        def _pass2(_):
            return _fold(i, _hash(state, blocks, True), bounds)

        def _none(_):
            return (
                jnp.uint32(U32_MAX), jnp.uint32(U32_MAX), jnp.int32(I32_MAX),
            )

        return lax.cond(surv, _pass2, _none, 0)

    return kernel


@lru_cache(maxsize=256)
def _make_kernel(
    n_tail_blocks: int,
    low_pos: Tuple[DigitPos, ...],
    k: int,
    batch: int,
    rolled: bool,
    sieve: bool = False,
    factored: int = 0,
):
    """Jitted single-device wrapper over :func:`make_kernel_body`."""
    return jax.jit(
        make_kernel_body(
            n_tail_blocks, low_pos, k, batch, rolled, sieve=sieve,
            factored=factored,
        )
    )


@lru_cache(maxsize=256)
def _layout_cache(data: bytes, d: int, sep: bytes = b" ", family: str = "sha256"):
    if family == "blake2b":
        from .blake2b import build_layout as build_blake2b_layout

        return build_blake2b_layout(data, d, sep=sep)
    return build_layout(data, d, sep=sep)


def _workload_knobs(workload) -> Tuple[bytes, object, bool, str]:
    """Resolve the (separator, host-min fn, native-allowed, kernel
    family) tuple a sweep driver needs from a workload object
    (duck-typed: ``.sep``, ``._cpu_search``, ``.native_ok``,
    ``.kernel_family`` — see workloads/base.py).  ``None`` means the
    frozen mining default, byte-identical to the pre-registry behavior.
    The kernel family picks which message-layout builder + device kernel
    the drivers compile ("sha256" or "blake2b"); a workload with neither
    template cannot run these drivers at all — that is a configuration
    error, not a silent wrong answer."""
    if workload is None:
        return b" ", _host_min, True, "sha256"
    if getattr(workload, "sep", None) is None:
        raise ValueError(
            f"workload {getattr(workload, 'name', workload)!r} has no "
            "device message template; its tier ladder has no device tier"
        )
    family = getattr(workload, "kernel_family", "sha256")
    if getattr(workload, "native_ok", False):
        # native == this workload's oracle
        return workload.sep, _host_min, True, family
    # The workload's cpu-tier loop (prefix-folded, one encode per call),
    # not its per-nonce min_range oracle: host lanes sit on the hot path.
    return workload.sep, workload._cpu_search(), False, family


@dataclass(frozen=True)
class MeshRows:
    """Where the ``n_rows`` valid rows of one mesh dispatch sit.

    The dispatch has one block of ``per_dev_batch`` slots per device (by
    default :func:`auto_tune`'s 1024 slots in all, split over the
    devices), and the operands are sharded contiguously along the mesh
    axis, so block ``dev`` runs on device ``dev``.  The rows split as evenly as they go:
    each device takes ``n_rows // n_devices`` rows and the first
    ``n_rows % n_devices`` take one more, at the front of their block, in
    ascending nonce order; padding fills the end of each block.  Blocks
    stay in device order, so ``(device, slot)`` order is still nonce
    order and the collective cascade's lowest-(device, flat) tie-break
    stays lowest-nonce.  The one owner of the slot <-> row map: the
    template fill places rows with :meth:`slots`, every fold resolves a
    winning ``(device, local row)`` with :meth:`row`."""

    n_rows: int
    n_devices: int

    def counts(self) -> Tuple[int, ...]:
        """Valid rows on each device (no two differ by more than one)."""
        q, r = divmod(self.n_rows, self.n_devices)
        return tuple(q + (d < r) for d in range(self.n_devices))

    def row(self, dev: int, local: int) -> int:
        """The row held by slot ``local`` of device ``dev``'s block."""
        q, r = divmod(self.n_rows, self.n_devices)
        return dev * q + min(dev, r) + local

    def slots(self, per_dev_batch: int) -> List[int]:
        """Each row's slot in the dispatch, in row order."""
        return [
            dev * per_dev_batch + i
            for dev, c in enumerate(self.counts())
            for i in range(c)
        ]


def _fill_templates(
    layout: MsgLayout,
    group: ChunkGroup,
    chunk_rows: Sequence[Chunk],
    batch: int,
    slots: Optional[Sequence[int]] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side: fold each chunk's constant high digits into the word
    template; build the (B, 2) lane-bound array, padding unused rows empty.
    Row ``r`` goes to slot ``r``, or to ``slots[r]`` when given (a mesh
    dispatch's placement, :class:`MeshRows`)."""
    tail_const = np.tile(
        np.array(layout.tail_template, dtype=np.uint64), (batch, 1)
    )  # u64 scratch to avoid overflow warnings, cast at the end
    bounds = np.zeros((batch, 2), dtype=np.int32)
    span = 10**group.k
    n_high = layout.digit_count - group.k
    for r, ch in enumerate(chunk_rows):
        s = r if slots is None else slots[r]
        if n_high > 0:
            high = str(ch.base // span)
            assert len(high) == n_high, (high, n_high, ch)
            for j, ch_digit in enumerate(high):
                dp = layout.digit_pos[j]
                tail_const[s, dp.word] |= ord(ch_digit) << dp.shift
        bounds[s] = (ch.lo_off, ch.hi_off)
    return tail_const.astype(np.uint32), bounds


# --------------------------------------------------------------------------
# Host sweep driver
# --------------------------------------------------------------------------


@dataclass
class SweepResult:
    hash: int  # the 64-bit big-endian hash value
    nonce: int
    lanes_swept: int  # valid nonces hashed (for throughput accounting)


def _default_backend() -> str:
    """The strongest tier this host's devices run by DEFAULT: pallas only
    under the Mosaic (TPU) lowering.  A GPU host *has* a pallas lowering
    (Triton — :func:`~bitcoin_miner_tpu.utils.platform.pallas_platform`
    reports it, and ``backend="pallas"`` is honored there), but the rung
    stays off by default until a GPU bench prices it: every pallas
    default in :func:`auto_tune` (sieve ON, batch 1024, max_k 6) was
    measured under Mosaic and none transfer sight-unseen to Triton's
    warp-level cost model (ROADMAP follow-on)."""
    return "pallas" if pallas_platform() == "mosaic" else "xla"


def auto_tune(
    backend: Optional[str],
    batch: Optional[int],
    max_k: Optional[int],
    sieve: Optional[bool] = None,
    factored: Optional[bool] = None,
    family: str = "sha256",
    n_devices: int = 1,
) -> Tuple[str, int, int, bool, bool]:
    """Resolve the (backend, rows-per-device, max_k, sieve, factored)
    defaults of :class:`SweepPipeline`, on one device or a mesh.
    max_k=5 bounds the xla tier's compress_rolled schedule buffer
    ((16, B, 10^k) u32) to ~50 MB at B=8.

    ``n_devices`` is the number of devices one dispatch spans.  The pallas
    default holds ``DEFAULT_BATCH`` slots per dispatch in all, so each
    device gets ``ceil(1024 / n_devices)`` rounded up to a multiple of
    ``DEFAULT_CPB``: 1024 on one chip, 256 on four.  A scheduler chunk of
    ~1000 rows then fills ~98% of a mesh dispatch's row groups instead of
    ~24%; an empty group in a fixed grid paid the per-step cost (on one
    v5e, 250 rows took 136.6 ms a dispatch at 1024 slots, 128.9 ms at
    256), and the dyn kernel's grid stops at the fullest device's last
    row group (:func:`_grid_groups`).  An
    explicit ``batch`` is per device and is kept; so are the xla and
    blake2b defaults.

    ``family`` resolves PER-WORKLOAD rung defaults (ISSUE 20) — the
    tuple was sha256-template-only before the BLAKE2b device tier
    landed.  The "blake2b" family has exactly one device rung, the
    grouped-unrolled xla kernel (ops/blake2b.py): no pallas lowering
    exists for it, so ``backend`` resolves to "xla" on every platform
    (requesting "pallas" is a configuration error, same contract as a
    workload without the tier); ``batch`` defaults to 8 (measured on
    this host: 5.28M n/s at batch 8 / k_in 3 vs 4.82M at batch 4 /
    k_in 4 — the BLAKE2b DAG is narrower than SHA-256's, so the
    cache-residency knee sits at a wider batch); ``factored`` defaults
    ON (the grouped form IS the kernel's production shape — the
    full-lane form exists for tiny classes and tests); ``sieve``
    defaults OFF (h0 and h1 fall out of one compression word, so there
    is no cheaper pass 1 and no two-stage win).

    The **sieve rung** (ISSUE 13, ``sieve=None`` = auto): the two-stage
    sieve kernel is ON for the pallas tier — pass 1's predicate epilogue
    is ~8 vector ops/lane against the ~22 of the per-lane argmin
    bookkeeping it replaces (tools/roofline.py prints both), and
    survivor groups vanish as the running min falls like
    ``U32_MAX / nonces_swept`` — and OFF for the xla tier, where the
    sieve measurably LOSES — originally 2x with the baseline kernel
    (BENCH_pr13.json: the full (16, B, 10^k) schedule buffer
    re-materialised per pass, no sequential dimension), and re-measured
    under the r14 FACTORED xla default, where both of those reasons are
    gone (per-group buffers, the group loop tightens the threshold), it
    still loses ~5% (factored 2.45M vs factored+sieve 2.33M n/s on this
    host: ``lax.cond`` still re-runs the inner rounds on survivor
    dispatches), so the rung stays OFF (``bench.py --sieve-compare``
    re-measures any shape).  A shape where
    the sieve loses therefore keeps the current kernel by default.

    The **factored rung** (ISSUE 14, ``factored=None`` = auto): the
    outer/inner digit factoring is ON for the xla tier, where the
    same-seed pair measured it winning **2.76×** (BENCH_pr14.json:
    baseline 905k vs factored 2.50M n/s on this CPU host — the rolled
    form's 16-word schedule buffer shrinks from the full
    ``(16, B, 10^k)`` tens-of-MB shape to a per-group ``(16, B,
    10^k_in)`` that stays cache-resident, on top of the per-group scalar
    round prefix), and OFF for the pallas tier BY DEFAULT despite the op
    model's win (flagship 1-block compression 3002 → 2910 folded vector
    ops/lane, h0-only pass 1 3001 → 2909; ``tools/roofline.py
    --ops-only`` audits any shape): the factored pallas kernel is
    per-class STATIC — giving back the dyn kernel's digit-boundary
    compile amortization — and its outer grid axis multiplies grid
    programs ~4× (1024-lane inner tiles vs 4096), neither of which this
    host can price; ``bench.py --factor-compare`` on real TPU is the
    arbiter (ROADMAP follow-on), and a shape where factoring loses keeps
    the current kernel by default."""
    if family == "blake2b":
        if backend is None:
            backend = "xla"
        elif backend == "pallas":
            raise ValueError(
                "the blake2b kernel family has no pallas lowering; its "
                "device rung is the xla grouped-unrolled kernel"
            )
        if batch is None:
            batch = 8
        if max_k is None:
            max_k = 5
        if sieve is None:
            sieve = False
        if factored is None:
            factored = True
        return backend, batch, max_k, sieve, factored
    if backend is None:
        backend = _default_backend()
    if batch is None:
        # pallas: the r5 on-TPU autotune of the dynamic kernel prefers
        # batch 2048 for FULL dispatches (1.907e9 vs 1.899e9 bench), but
        # the fleet's EWMA chunks (~0.95e9 at target_chunk_seconds=0.5)
        # half-fill a 2048-row batch and measured 1.79e9 delivered vs
        # 1.82e9 at 1024 — the scheduler-matched 1024 wins end-to-end, so
        # a mesh dispatch splits those 1024 slots over its devices.
        # xla default measured via bench.py --autotune on XLA:CPU: batch 4
        # beat 8/16/32 by 14-128% (smaller schedule buffer, better cache);
        # RE-MEASURED under the r14 factored default (ROADMAP PR-14
        # follow-on c, BENCH_pr15.json): per-group buffers narrowed the
        # gap but batch 4 still wins — 2.40M vs 2.37M (8), 1.49M (16),
        # 1.21M (32) n/s — so the default stands.
        if backend == "pallas":
            from .pallas_sha256 import DEFAULT_BATCH, DEFAULT_CPB

            per_dev = -(-DEFAULT_BATCH // n_devices)
            batch = -(-per_dev // DEFAULT_CPB) * DEFAULT_CPB
        else:
            batch = 4
    if max_k is None:
        max_k = 6 if backend == "pallas" else 5
    if sieve is None:
        sieve = backend == "pallas"
    if factored is None:
        factored = backend == "xla"
    return backend, batch, max_k, sieve, factored


@dataclass(frozen=True)
class HostFold:
    """A ``(hash, nonce)`` candidate computed on the host for a tiny digit
    class, passed through a driver's ``consume`` in place of a device
    output handle.  Routing these off-device means a one-off ``10^d``
    bucket never pays a 20-40 s Mosaic compile: measured r5, a fleet
    warm-up job over ``[0, 4e9)`` spent ~150 s compiling d=1..9 kernels
    whose combined lanes are <1% of one second of device work."""

    hash: int
    nonce: int


def _host_min(data: str, lo: int, hi: int) -> Tuple[int, int]:
    """Host-tier ``(min hash, argmin nonce)`` over inclusive ``[lo, hi]``:
    the C++ native tier when built (~1.5e8 n/s multithreaded), else the
    hashlib oracle (~1e6 n/s)."""
    try:
        from .. import native

        if native.available():
            return native.min_hash_range_native(data, lo, hi)
    except Exception:
        pass
    from ..bitcoin.hash import min_hash_range

    return min_hash_range(data, lo, hi)


def auto_host_lane_budget(
    native_ok: bool = True, dyn_k: Optional[int] = None
) -> int:
    """Largest digit-class size worth computing on the host instead of
    compiling a device kernel for.  With the native fold that is 1e7
    (d <= 7).  ``dyn_k`` is the k of a digit-position-dynamic kernel
    (the unfactored pallas tier's), which already serves every class
    d > ``dyn_k`` with no compile: the host then keeps only d <=
    ``dyn_k`` (1e6 at the pallas tier's k = 6).  On a TPU v5e host a
    1e7-nonce fold took 0.067-0.085 s of ``sweep.host_fold_s`` (13
    cores, on the pipeline's dispatch thread), as long as the chip takes
    for ~1.4e8 nonces, so jobs of a few 1e8 nonces were paced by the
    host and by how busy its cores were.  ``native_ok=False``
    (non-default workloads, whose host tier is the hashlib-speed oracle)
    keeps the budget at the pure-Python level."""
    budget = 10**5
    if native_ok:
        try:
            from .. import native

            if native.available():
                budget = 10**7
        except Exception:
            pass
    if dyn_k is not None:
        budget = min(budget, 10**dyn_k)
    return budget


def run_sweep_dispatches(
    data: str,
    lower: int,
    upper: int,
    max_k: int,
    batch: int,
    get_kernel,
    run_kernel,
    consume,
    max_inflight: int = 32,
    host_lane_budget: int = 0,
    sep: bytes = b" ",
    host_min=None,
    family: str = "sha256",
    n_devices: int = 1,
) -> int:
    """The decompose → template-fill → dispatch skeleton that
    :class:`SweepPipeline`'s dispatcher runs for each job, on one device
    or a mesh.  ``n_devices > 1`` is a mesh dispatch: ``batch`` is then
    ``n_devices`` blocks of slots, and each dispatch's rows spread over
    them (:class:`MeshRows`).

    ``sep``/``host_min``/``family`` are the workload knobs
    (``_workload_knobs``): the message-template separator baked into
    each digit class's layout, the host-tier fold used for host-routed
    tiny classes, and the kernel family whose layout builder runs
    (defaults = the frozen mining workload).

    ``get_kernel(layout, group)`` builds/caches the kernel for a shape class;
    ``run_kernel(kern, midstate, tail_const, bounds, groups)`` queues one
    dispatch and returns its (not-yet-fetched) output handle, where
    ``groups`` is the row-group extent of the dispatch's grid
    (:func:`_grid_groups`; None for a kernel whose grid is fixed);
    ``consume(out, chunk_bases, 10^k)`` fetches and folds one result — it
    must also accept a :class:`HostFold` as ``out`` (with None bases):
    digit classes with ``10^d <= host_lane_budget`` are min-folded on the
    host instead of compiling a one-off kernel shape for a negligible lane
    count.  0 (the default) disables routing so library callers and kernel
    tests always exercise the device path; the miner's production pipeline
    passes :func:`auto_host_lane_budget`.
    At most ``max_inflight`` dispatches stay queued — enough to keep the
    device busy while the host fills the next templates, while bounding host
    state for huge ranges (a 10^12-nonce sweep is ~10^6 dispatches on the
    xla tier).  Returns the number of lanes swept.
    """
    data_bytes = data.encode("utf-8")
    if host_min is None:
        host_min = _host_min
    pending: Deque[Tuple] = collections.deque()
    lanes = 0
    for group in decompose_range(lower, upper, max_k=max_k):
        if 10**group.d <= host_lane_budget:
            g_lo = group.chunks[0].base + group.chunks[0].lo_off
            g_hi = group.chunks[-1].base + group.chunks[-1].hi_off - 1
            t_fold = _time.monotonic()
            h, n = host_min(data, g_lo, g_hi)
            dt_fold = _time.monotonic() - t_fold
            pending.append((HostFold(h, n), None, None))
            n_host = sum(c.hi_off - c.lo_off for c in group.chunks)
            lanes += n_host
            METRICS.inc("sweep.host_fold_lanes", n_host)
            METRICS.inc("sweep.host_fold_s", dt_fold)
            if _trace.enabled():
                _trace.emit(
                    None, "miner", "host_fold",
                    d=group.d, lanes=n_host, seconds=round(dt_fold, 6),
                )
            continue
        layout = _layout_cache(data_bytes, group.d, sep, family)
        kern = get_kernel(layout, group)
        midstate = np.array(layout.midstate, dtype=np.uint32)
        for s in range(0, len(group.chunks), batch):
            rows = group.chunks[s : s + batch]
            slots = None
            fullest = len(rows)
            if n_devices > 1:
                place = MeshRows(len(rows), n_devices)
                slots = place.slots(batch // n_devices)
                fullest = max(place.counts())
                _count_mesh_dispatch(place, batch // n_devices)
            tail_const, bounds = _fill_templates(layout, group, rows, batch, slots)
            groups = _grid_groups(kern, fullest, batch // n_devices, n_devices)
            out = run_kernel(kern, midstate, tail_const, bounds, groups)
            pending.append((out, [c.base for c in rows], 10**group.k))
            n_dev = sum(c.hi_off - c.lo_off for c in rows)
            lanes += n_dev
            METRICS.inc("sweep.device_lanes", n_dev)
            if len(pending) > max_inflight:
                consume(*pending.popleft())
    while pending:
        consume(*pending.popleft())
    return lanes


def _grid_groups(
    kern, rows: int, per_dev_batch: int, n_devices: int = 1
) -> Optional[int]:
    """The row groups each device's grid runs for a dispatch whose fullest
    device holds ``rows`` rows: ``ceil(rows / kern.cpb)`` for a kernel
    whose row-group extent is a runtime operand (the pallas dyn kernels,
    which carry their ``cpb``), None for a kernel whose grid is fixed.
    Counts ``sweep.grid_groups`` and the groups of the full ``per_dev_batch
    // cpb`` grid it skips (``sweep.grid_groups_skipped``), over the
    devices."""
    cpb = getattr(kern, "cpb", None)
    if cpb is None:
        return None
    groups = -(-rows // cpb)
    METRICS.inc("sweep.grid_groups", n_devices * groups)
    METRICS.inc(
        "sweep.grid_groups_skipped", n_devices * (per_dev_batch // cpb - groups)
    )
    return groups


def _count_mesh_dispatch(place: MeshRows, per_dev_batch: int) -> None:
    """One mesh dispatch's placement: its valid rows, the slots the mesh
    spends on them (every device works as long as the fullest one), and
    the row slots the dispatch carries in all."""
    counts = place.counts()
    slots = place.n_devices * per_dev_batch
    METRICS.inc("sweep.mesh_rows", place.n_rows)
    METRICS.inc("sweep.mesh_row_slots", place.n_devices * max(counts))
    METRICS.inc("sweep.mesh_dispatch_slots", slots)
    METRICS.inc("sweep.mesh_dispatches")
    if _trace.enabled():
        _trace.emit(
            None, "miner", "mesh_dispatch",
            rows=place.n_rows, per_device=list(counts), slots=slots,
        )


@lru_cache(maxsize=8)
def _zero_tile_dev(n_pad):
    from .pallas_sha256 import zero_tile_np

    return jnp.asarray(zero_tile_np(n_pad))


@lru_cache(maxsize=64)
def _window_contribs_dev(k, low_pos, w_lo, w_hi, n_pad):
    """Device-resident window contribution tiles for one digit class —
    cached so repeated sweeps don't re-transfer them; untouched words
    share one device zero tile across all classes."""
    from .pallas_sha256 import window_contribs_np, zero_tile_np

    zero = zero_tile_np(n_pad)
    return tuple(
        _zero_tile_dev(n_pad) if c is zero else jnp.asarray(c)
        for c in window_contribs_np(k, low_pos, w_lo, w_hi, n_pad)
    )


def _build_kernel(
    backend, batch, tile, cpb, interpret, rolled, layout, group, sieve=False,
    factored=False,
):
    """One place for the backend-specific kernel construction of
    SweepPipeline's single-device mode (the underlying factories are
    lru_cached).  ``sieve`` picks the two-stage variant of whichever
    backend kernel applies (ISSUE 13); ``factored`` the outer/inner
    digit-factored variant (ISSUE 14, classes with ``k >= 2`` — a 1-digit
    lane axis has nothing to factor), composable with ``sieve``.

    The pallas tier uses the digit-position-DYNAMIC kernel: one compiled
    executable serves every digit class d in [k+1, 20] of this data length
    (per-class contributions are runtime inputs), so crossing a decimal
    digit boundary mid-sweep never costs a fresh trace and lower (16.5 s
    on a v5e host).  Under the Mosaic lowering the kernel is served from
    its stored export (ops/kernel_store.py), so once the store is warm a
    fresh process does not trace it either.  The returned closure carries
    a stable ``class_key`` (the shared kernel) so SweepPipeline's
    single-flight build locks key on the executable, not the per-class
    wrapper.

    The FACTORED pallas kernel is per-class STATIC, not dynamic — and
    must be: the dyn kernel's word window spans every digit class's
    possible digit bytes, and over d in [k+1, 20] the outer and inner
    byte ranges cover the SAME window words, so a dyn-factored kernel
    would have nothing left to demote to scalars (the whole point of the
    split).  The cost is per-class compiles again; SweepPipeline's
    prewarm machinery (digit-boundary speculation + single-flight build
    locks) already exists to hide exactly that.

    Layouts carry their kernel family (``layout.family``): the blake2b
    family resolves to its own grouped-unrolled xla kernel
    (ops/blake2b.py) with the same operand/result contract, so every
    caller of this function serves both families unchanged.
    """
    if getattr(layout, "family", "sha256") == "blake2b":
        if backend != "xla":
            raise ValueError(
                f"blake2b kernel family has no {backend!r} tier (xla only)"
            )
        from .blake2b import build_kernel_for

        return build_kernel_for(
            layout, group, batch, sieve=sieve, factored=factored
        )
    low_pos = layout.digit_pos[layout.digit_count - group.k :]
    if backend == "pallas":
        if factored and group.k >= 2:
            from .pallas_sha256 import DEFAULT_TILE, make_pallas_minhash_factored

            return make_pallas_minhash_factored(
                layout.n_tail_blocks,
                low_pos,
                group.k,
                default_factor_k_in(group.k),
                batch,
                tile=tile if tile is not None else DEFAULT_TILE,
                interpret=interpret,
                cpb=cpb,
                sieve=sieve,
            )
        from .pallas_sha256 import (
            DEFAULT_TILE,
            dyn_params,
            make_pallas_minhash,
            make_pallas_minhash_dyn,
            resolve_cpb,
        )

        window = dyn_params(layout, group.k)
        if window is None:
            # The d == k class (d=1) is one class — the dynamic kernel
            # buys nothing; use the per-class static form.
            return make_pallas_minhash(
                layout.n_tail_blocks,
                low_pos,
                group.k,
                batch,
                tile=tile if tile is not None else DEFAULT_TILE,
                interpret=interpret,
                cpb=cpb,
                sieve=sieve,
            )
        w_lo, w_hi = window
        cpb = resolve_cpb(batch, cpb)
        params = dict(
            n_tail_blocks=layout.n_tail_blocks,
            w_lo=w_lo,
            w_hi=w_hi,
            k=group.k,
            batch=batch,
            tile=tile if tile is not None else DEFAULT_TILE,
            cpb=cpb,
            sieve=sieve,
        )
        fn, n_pad = make_pallas_minhash_dyn(**params, interpret=interpret)
        if not interpret and pallas_platform() == "mosaic":
            # A fresh process loads the kernel's stored export instead of
            # tracing and lowering it again (ops/kernel_store.py).
            from .kernel_store import stored_kernel

            fn = stored_kernel(fn, **params)
        contribs = _window_contribs_dev(group.k, low_pos, w_lo, w_hi, n_pad)

        # *th is empty (baseline) or the one threshold operand (sieve):
        # one wrapper serves both calling conventions.
        def kern(midstate, tailc_bounds, n_groups, *th, _fn=fn, _c=contribs):
            return _fn(midstate, tailc_bounds, n_groups, *th, *_c)

        kern.class_key = fn
        kern.cpb = cpb  # the grid's row-group extent is an operand
        return kern
    return _make_kernel(
        layout.n_tail_blocks, low_pos, group.k, batch, rolled, sieve,
        default_factor_k_in(group.k) if factored and group.k >= 2 else 0,
    )


def _invoke_kernel(
    backend, kern, midstate, tail_const, bounds, thresh=None, groups=None
):
    """One place for the backend-specific calling convention (the pallas
    tier takes the chunk table + bounds as one flattened operand).

    ``thresh`` (sieve kernels only): the host's running-min h0 as a plain
    int in [0, U32_MAX] — U32_MAX (everything survives) until the first
    candidate lands.  The pallas tier wants it pre-sign-flipped int32
    (its comparisons live in that domain); the xla tier compares uint32
    directly.  ``groups`` (the pallas dyn kernel only, see
    :func:`_grid_groups`): the grid's row-group extent, an int32 operand
    ahead of the threshold."""
    if backend == "pallas":
        tailcb = np.concatenate([tail_const, bounds.astype(np.uint32)], axis=1)
        ops = [jnp.asarray(midstate), jnp.asarray(tailcb)]
        if groups is not None:
            ops.append(jnp.asarray(np.int32(groups)))
        if thresh is not None:
            tflip = np.array([thresh ^ 0x80000000], dtype=np.uint32)
            ops.append(jnp.asarray(tflip.view(np.int32)))
        return kern(*ops)
    if thresh is None:
        return kern(
            jnp.asarray(midstate), jnp.asarray(tail_const), jnp.asarray(bounds)
        )
    return kern(
        jnp.asarray(midstate),
        jnp.asarray(tail_const),
        jnp.asarray(bounds),
        jnp.uint32(thresh),
    )


#: TPU-runtime fault injection (ISSUE 10 satellite, carry-over from PR 2):
#: ``BMT_WEDGE_DISPATCH=N`` makes the N-th result fetched by the FIRST
#: armed pipeline in this process hang until that pipeline is closed —
#: exactly what a wedged device future looks like from the outside — so
#: the miner watchdog's tier-downgrade drill exercises a real stuck
#: dispatch inside :class:`SweepPipeline` instead of only a simulated
#: sleeping search fn.  One-shot per process: the fallback tier the
#: watchdog builds next must not inherit the wedge and cascade off the
#: bottom of the chain.
_WEDGE_STATE = {"fired": False}


class SweepPipeline:
    """Cross-request sweep pipeline: the device never idles between jobs.

    The one sweep driver, on one device or a mesh.  Concurrent
    synchronous sweeps from separate threads would race their dispatch
    enqueues so the device interleaves both jobs and both finish late (an
    older remote-runtime run, before PR 1, saw a pipelined fleet stuck at
    ~38% of kernel rate).  This pipeline serializes *enqueue* order in
    one dispatcher thread — jobs' dispatches land on the device queue
    back-to-back, FIFO — while a fetcher thread blocks on results in the
    same order and resolves each job's future the moment its last
    dispatch lands.  Submitting job N+1
    while job N computes therefore costs zero device idle, and results
    stream back with per-job latency, not per-job-pair bursts.

    Used by the miner worker (apps/miner.py) to serve the scheduler's
    pipelined 2-deep assignment window; ``submit`` is thread-safe.
    :func:`sweep_min_hash` and ``parallel.sweep_min_hash_sharded`` are
    its synchronous form: one job through a pipeline of their own.
    """

    _DONE = object()

    def __init__(
        self,
        *,
        max_k: Optional[int] = None,
        batch: Optional[int] = None,
        tile: Optional[int] = None,
        cpb: Optional[int] = None,
        backend: Optional[str] = None,
        interpret: bool = False,
        max_inflight: int = 32,
        host_lane_budget: Optional[int] = None,
        mesh=None,
        axis_name: str = "miners",
        workload=None,
        sieve: Optional[bool] = None,
        factored: Optional[bool] = None,
    ) -> None:
        import queue as _queue
        import threading
        from concurrent.futures import Future

        self._Future = Future
        # Workload knobs (ISSUE 9/20): the message-template separator,
        # the host fold for host-routed tiny digit classes, and the
        # kernel family.  None = the frozen mining default,
        # byte-identical to the pre-registry path.
        (
            self._sep, self._host_min, native_ok, self._family,
        ) = _workload_knobs(workload)
        if mesh is not None and backend is None:
            # Resolve the backend from the MESH devices, not the process
            # default: a CPU mesh in a TPU-default process must get xla,
            # not a Mosaic kernel.
            from ..utils.platform import is_tpu_device

            if not is_tpu_device(mesh.devices.flat[0]):
                backend = "xla"
        self._n_devices = 1 if mesh is None else mesh.devices.size
        (
            self._backend, self._batch, self._max_k, self._sieve,
            self._factored,
        ) = auto_tune(
            backend, batch, max_k, sieve, factored,
            family=self._family, n_devices=self._n_devices,
        )
        if mesh is not None and self._backend == "pallas":
            # The sharded tier runs the PER-SHARD sieve (ISSUE 14
            # satellite) on both backends, and — since ISSUE 16 — the
            # FACTORED kernels on the xla backend too (the outer/inner
            # split threads through _make_sharded_kernel, so a mesh
            # miner gets the 2.76× xla win).  Factoring stays off for
            # sharded *pallas* only: that tier keeps the dyn kernels
            # (the factored pallas kernel is per-class static, and its
            # cost can only be priced on real TPU — same arbitration
            # follow-on as the single-device pallas rung).
            self._factored = False
        self._tile = tile
        self._cpb = cpb
        self._interpret = interpret
        # Mesh mode: the same cross-request pipeline drives the sharded
        # (shard_map + pmin cascade) kernels — a multi-chip miner must not
        # idle its whole mesh between the scheduler's chunks any more than
        # a single chip may.  ``batch`` stays per-device (its default
        # from auto_tune's n_devices); dispatch slots total n_devices *
        # batch, sharded contiguously along axis_name, and each
        # dispatch's rows spread evenly over them (MeshRows).
        self._mesh = mesh
        self._axis_name = axis_name
        self._per_dev_batch = self._batch
        # None = auto: this is the miner's production path, where a tiny
        # digit class must never cost a Mosaic compile (see HostFold).
        dyn = self._backend == "pallas" and not self._factored
        self._host_lane_budget = (
            auto_host_lane_budget(native_ok, self._max_k if dyn else None)
            if host_lane_budget is None
            else host_lane_budget
        )
        if mesh is not None:
            from ..utils.platform import is_tpu_device

            self._batch = self._n_devices * self._per_dev_batch
            self._rolled = not is_tpu_device(mesh.devices.flat[0])
        else:
            self._rolled = not is_tpu()
        # Fault injection (module constant above): which fetched result,
        # if any, this pipeline should wedge on.  Read once at build so a
        # late env mutation can't arm a production pipeline mid-run.
        try:
            self._wedge_after = int(_os.environ.get("BMT_WEDGE_DISPATCH", "0") or 0)
        except ValueError:
            self._wedge_after = 0
        self._fetched_count = 0
        self._prewarmed: set = set()
        self._prewarm_lock = threading.Lock()
        # Single-flight warm-up per kernel class (keyed by the lru-cached
        # kernel object): a class's first invocation in a process builds
        # it (prewarm_async gives the costs) — if the prewarm thread and
        # the dispatcher both hit a cold class, they must share ONE build
        # (measured r5: the unsynchronized race re-traced the full 17 s in
        # the dispatcher even though prewarm was seconds from finishing).
        self._kernel_locks: dict = {}
        self._warm_keys: set = set()
        self._jobs: "_queue.Queue" = _queue.Queue()
        # Backpressure: bounds both host memory and the device backlog.
        self._fetches: "_queue.Queue" = _queue.Queue(maxsize=max_inflight)
        self._closed = False
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, name="sweep-dispatch", daemon=True
        )
        self._fetcher = threading.Thread(
            target=self._fetch_loop, name="sweep-fetch", daemon=True
        )
        self._dispatcher.start()
        self._fetcher.start()

    @property
    def backend(self) -> str:
        """The kernel tier ``auto_tune`` resolved: "pallas" or "xla"."""
        return self._backend

    @property
    def devices(self) -> list:
        """The jax devices this pipeline dispatches to."""
        if self._mesh is not None:
            return list(self._mesh.devices.flat)
        return jax.devices()[:1]

    def submit(self, data: str, lower: int, upper: int):
        """Queue one sweep; returns a Future of :class:`SweepResult`."""
        if self._closed:
            raise RuntimeError("pipeline closed")
        fut = self._Future()
        self._jobs.put((data, lower, upper, fut))
        return fut

    def prewarm_async(self, data: str, d: int) -> bool:
        """Build + compile + device-load digit class ``d``'s kernel on a
        background thread, overlapping the device's current work.

        Why: each kernel shape's first use in a process traces and lowers
        it *even on a persistent-cache hit* — 13.8 s + 2.7 s for the pallas
        dyn kernel on a v5e host, which its stored export
        (ops/kernel_store.py) cuts to ~0.1 s once the store is warm, while
        a static per-class kernel pays it in full — then compiles it
        (12.7 s) or loads it from the cache (0.04 s): a mid-job stall if
        paid when the sweep first crosses a digit boundary.  The miner
        calls this speculatively for the class one past each assignment's
        upper bound.

        Returns False without spawning when the class is host-routed
        (see :class:`HostFold`), beyond u64's 20 digits, or already
        prewarmed/warming.
        """
        import threading

        if not 1 <= d <= 20:
            return False
        if 10**d <= self._host_lane_budget:
            return False
        # Kernel shape classes depend on the data LENGTH only (digit byte
        # offset + tail block count), so same-length jobs share the warm —
        # dedupe on length, not content, or every new job's data would
        # re-run a ~0.5 s full-batch warm dispatch for a hot kernel.
        key = (len(data.encode("utf-8")), d)
        with self._prewarm_lock:
            if key in self._prewarmed:
                return False
            self._prewarmed.add(key)
        threading.Thread(
            target=self._prewarm,
            args=(data, d),
            name=f"sweep-prewarm-d{d}",
            daemon=True,
        ).start()
        return True

    def _prewarm(self, data: str, d: int) -> None:
        try:
            rep = 10 ** (d - 1)  # any nonce in the class: (d, k) is all
            group = next(decompose_range(rep, rep, max_k=self._max_k))
            layout = _layout_cache(
                data.encode("utf-8"), group.d, self._sep, self._family
            )
            kern = self._get_kernel(layout, group)
            midstate = np.array(layout.midstate, dtype=np.uint32)
            tail_const, bounds = _fill_templates(
                layout, group, group.chunks, self._batch
            )
            # With the dynamic kernel, neighbouring digit classes share one
            # executable — skip the warm dispatch if it's already hot.
            key = getattr(kern, "class_key", kern)
            if key in self._warm_keys:
                return
            # One real (single-row, padded) dispatch: triggers trace +
            # compile + load with exactly the shapes run_sweep_dispatches
            # will use, so the dispatcher's later call is a pure cache hit.
            # The class lock makes a racing dispatcher wait for this build
            # instead of duplicating it.
            with self._class_lock(kern):
                if key in self._warm_keys:
                    return
                out = self._invoke(
                    kern, midstate, tail_const, bounds,
                    thresh=U32_MAX if self._sieve else None,
                    groups=_grid_groups(
                        kern, len(group.chunks), self._per_dev_batch,
                        self._n_devices,
                    ),
                )
                for o in out:
                    o.block_until_ready()
                self._warm_keys.add(key)
        except Exception:
            with self._prewarm_lock:  # let a later attempt retry
                self._prewarmed.discard((len(data.encode("utf-8")), d))

    def close(self) -> None:
        """Stop both worker threads and reap them (threadcheck): the
        sentinel flows jobs -> dispatcher -> fetches -> fetcher, so both
        exit once work queued ahead of it drains.  The joins are timed —
        a wedged device future (the injected-wedge drill, a real stuck
        runtime) must not turn close() into a hang; a timeout leaves the
        daemon thread to the process reaper, which is exactly the
        pre-ISSUE-19 behaviour, now as the fallback instead of the rule.
        The bound is short on purpose: an idle pipeline reaps in
        milliseconds, and a wedged one should cost a beat, not seconds,
        in every fleet teardown."""
        self._closed = True
        self._jobs.put(None)
        self._dispatcher.join(timeout=1)
        self._fetcher.join(timeout=1)

    # ------------------------------------------------------------- threads

    @staticmethod
    def _fail(fut, e: BaseException) -> None:
        """Resolve a Future to an error, tolerating the dispatcher/fetcher
        race where both observe the same device failure — the loser's
        InvalidStateError must not kill its pipeline thread."""
        try:
            fut.set_exception(e)
        except Exception:
            pass  # already resolved by the other thread

    def _get_kernel(self, layout, group):
        if self._mesh is not None:
            from ..parallel.sweep import sharded_kernel_for

            return sharded_kernel_for(
                layout,
                group,
                self._per_dev_batch,
                self._mesh,
                self._axis_name,
                self._backend,
                self._interpret,
                self._rolled,
                sieve=self._sieve,
                factored=self._factored,
            )
        return _build_kernel(
            self._backend,
            self._batch,
            self._tile,
            self._cpb,
            self._interpret,
            self._rolled,
            layout,
            group,
            sieve=self._sieve,
            factored=self._factored,
        )

    def _invoke(
        self, kern, midstate, tail_const, bounds, thresh=None, groups=None
    ):
        if self._mesh is not None:
            from ..parallel.sweep import sharded_invoke

            return sharded_invoke(
                kern, midstate, tail_const, bounds,
                self._mesh, self._axis_name, thresh=thresh, groups=groups,
            )
        return _invoke_kernel(
            self._backend, kern, midstate, tail_const, bounds,
            thresh=thresh, groups=groups,
        )

    def _class_lock(self, kern):
        import threading

        key = getattr(kern, "class_key", kern)
        with self._prewarm_lock:
            lk = self._kernel_locks.get(key)
            if lk is None:
                lk = self._kernel_locks[key] = threading.Lock()
        return lk

    def _dispatch_loop(self) -> None:
        while True:
            item = self._jobs.get()
            if item is None:
                self._fetches.put(None)
                return
            data, lower, upper, fut = item
            state = {"best": [], "lanes": 0, "fut": fut}

            def run_kernel(kern, midstate, tail_const, bounds, groups):
                # Class lock: a cold class traces inside this call; holding
                # the lock shares that build with a concurrent prewarm of
                # the same class.  Warm classes just enqueue (~ms) so the
                # lock is uncontended in steady state.  The enqueue stamp
                # rides with the handle so the fetcher can report each
                # dispatch's enqueue→fetch time (hist.device_dispatch_s).
                th = None
                if self._sieve:
                    # Sieve threshold: the running-min h0 known at ENQUEUE
                    # time (the fetcher updates state["best"]; a stale —
                    # looser — read is conservative-correct, so no lock).
                    b = state["best"]
                    th = (b[0][0] >> 32) if b else U32_MAX
                with self._class_lock(kern):
                    out = self._invoke(
                        kern, midstate, tail_const, bounds, thresh=th,
                        groups=groups,
                    )
                    self._warm_keys.add(getattr(kern, "class_key", kern))
                    return (out, _time.monotonic())

            def consume(out, bases, n_lanes) -> None:
                # Blocks when max_inflight results are unfetched — that's
                # the backpressure; the device queue stays deep meanwhile.
                self._fetches.put((state, out, bases, n_lanes))

            try:
                state["lanes"] = run_sweep_dispatches(
                    data,
                    lower,
                    upper,
                    self._max_k,
                    self._batch,
                    self._get_kernel,
                    run_kernel,
                    consume,
                    host_lane_budget=self._host_lane_budget,
                    sep=self._sep,
                    host_min=self._host_min,
                    family=self._family,
                    n_devices=self._n_devices,
                )
            except BaseException as e:  # resolve, don't kill the pipeline
                self._fail(fut, e)
                continue
            self._fetches.put((state, self._DONE, None, None))

    def _fetch_loop(self) -> None:
        while True:
            item = self._fetches.get()
            if item is None:
                return
            state, out, bases, n_lanes = item
            fut = state["fut"]
            if (
                self._wedge_after
                and out is not self._DONE
                and not _WEDGE_STATE["fired"]
            ):
                self._fetched_count += 1
                if self._fetched_count >= self._wedge_after:
                    # Injected wedge: this fetch never completes (the
                    # future hangs exactly like a stuck device runtime)
                    # until close() — the watchdog's budget must fire.
                    _WEDGE_STATE["fired"] = True
                    while not self._closed:
                        _time.sleep(0.02)
                    continue  # closing: drop the fetch, future stays open
            if out is self._DONE:
                if not fut.done():  # not already failed by the dispatcher
                    best = state["best"]
                    if not best:
                        self._fail(
                            fut, RuntimeError("sweep produced no candidates")
                        )
                    else:
                        fut.set_result(
                            SweepResult(
                                hash=best[0][0],
                                nonce=best[0][1],
                                lanes_swept=state["lanes"],
                            )
                        )
                continue
            if fut.done():
                continue  # job already failed; drain its remaining fetches
            if isinstance(out, HostFold):
                cand = (out.hash, out.nonce)
                best = state["best"]
                if not best or cand < best[0]:
                    best[:] = [cand]
                continue
            try:
                handles, t_enq = out  # run_kernel stamped the enqueue
                if len(handles) == 4:  # mesh mode: (h0, h1, device, flat)
                    h0, h1, dev, flat_idx = handles
                    fi = int(flat_idx)  # blocks until the dispatch lands
                    row = MeshRows(len(bases), self._n_devices).row(
                        int(dev), fi // n_lanes
                    )
                else:
                    h0, h1, flat_idx = handles
                    fi = int(flat_idx)
                    row = fi // n_lanes
                # Per-dispatch device time (ISSUE 6): enqueue→fetched.
                # The fetch above blocked until the device finished this
                # dispatch, so the delta is queue + kernel time — the
                # number adaptive chunking needs per shape class.
                dt = _time.monotonic() - t_enq
                METRICS.observe("hist.device_dispatch_s", dt)
                if _trace.enabled():
                    _trace.emit(
                        None, "kernel", "dispatch_done",
                        rows=len(bases), lanes=n_lanes, dt=round(dt, 6),
                    )
                if fi != I32_MAX:
                    h = (int(h0) << 32) | int(h1)
                    cand = (h, bases[row] + fi % n_lanes)
                    best = state["best"]
                    if not best or cand < best[0]:
                        best[:] = [cand]
            except BaseException as e:
                self._fail(fut, e)


def sweep_min_hash(
    data: str,
    lower: int,
    upper: int,
    *,
    max_k: Optional[int] = None,
    batch: Optional[int] = None,
    tile: Optional[int] = None,
    cpb: Optional[int] = None,
    backend: Optional[str] = None,
    interpret: bool = False,
    host_lane_budget: int = 0,
    workload=None,
    sieve: Optional[bool] = None,
    factored: Optional[bool] = None,
) -> SweepResult:
    """Find ``(min Hash(data, n), argmin n)`` over inclusive ``[lower,
    upper]`` on the default JAX device.  Bit-exact vs the hashlib oracle
    (``bitcoin_miner_tpu.bitcoin.hash_nonce`` for the default;
    ``workload.hash_nonce`` for any registered SHA-256-template
    workload); ties -> lowest nonce.

    ``backend``: "pallas" (VMEM-resident kernel, the fast TPU path), "xla"
    (plain fused jnp — reference tier, also the CPU path), or None for
    auto (pallas on TPU).  ``interpret`` runs Pallas in interpreter mode
    (for CPU tests of the Pallas tier).

    ``batch`` = chunks per dispatch.  Every dispatch+fetch pays a fixed
    latency, so the pallas tier defaults to a large super-batch
    (~1e9 nonces/dispatch); padding rows are skipped in-kernel.
    ``tile`` = lanes per pallas grid program (VMEM blocking; pallas only).
    ``cpb`` = chunk rows per pallas grid program (amortises per-program
    fixed cost; must divide ``batch``; None = largest divisor up to 8).
    ``sieve`` = the two-stage sieve kernel (ISSUE 13; None = the
    :func:`auto_tune` rung for this backend): dispatches carry the
    running-min h0 as a threshold operand and the full fold runs only on
    survivors — bit-exact either way (ties conservatively survive).
    ``factored`` = the outer/inner digit-factored kernel (ISSUE 14; None
    = the :func:`auto_tune` rung): the lane axis splits into outer digit
    groups whose invariant round prefix is computed once per group on
    the scalar unit — composable with ``sieve``, bit-exact either way.
    ``host_lane_budget`` = the largest digit class min-folded on the host
    (:class:`HostFold`); 0 keeps every class on the device.

    The synchronous form of :class:`SweepPipeline`: one job through a
    pipeline of its own, closed on return.  A failure raises here with
    the dispatcher's own exception (``ValueError`` for a bad ``cpb``,
    ``RuntimeError`` when the sweep found no candidate).
    """
    p = SweepPipeline(
        max_k=max_k, batch=batch, tile=tile, cpb=cpb, backend=backend,
        interpret=interpret, host_lane_budget=host_lane_budget,
        workload=workload, sieve=sieve, factored=factored,
    )
    try:
        return p.submit(data, lower, upper).result()
    finally:
        p.close()
