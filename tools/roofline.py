"""Empirical VPU roofline for the Pallas SHA-256 sweep.

The sweep is pure elementwise uint32 work on the VPU (the MXU is useless
for SHA — SURVEY §7 hard-part 2), so its ceiling is the chip's sustained
u32 ALU rate, not FLOPs or HBM.  This tool measures that rate **in situ
with the production kernel**, by comparing sweeps whose tails have one vs
two vector compression blocks: the marginal cost of the extra block
isolates pure compression time from per-program overhead (epilogue,
masking, window DMA, grid bookkeeping).

Why not a synthetic micro-kernel: two environment facts defeat that
approach here, both discovered the hard way —

1. the remote TPU runtime this was written against (before PR 1) returned
   cached results for byte-identical (executable, args) re-executions, so
   repeated identical dispatches measured RPC latency, not compute;
2. Mosaic's layout inference collapses work it can prove redundant:
   grid programs with no program_id dependence dedupe, and sublane-
   replicated tensors compute on one sublane — a naive probe quietly
   loses 64-1000x of its claimed work.

**Exact folded op counts** (r5, replacing the r4 upper-bound handwave):
the kernel's constant-word folding keeps every all-scalar sub-expression
off the VPU (ops/sha256.py `compress` docstring), so the static count
that matters is the number of ops with at least one *vector* input.  That
is computed here exactly, by abstract interpretation: `compress` is run
on tracer values carrying only a scalar/vector flag, counting each op
whose result is vector, for the exact word layout of the measured data
shapes.  With both measured rates and both exact counts, the marginal-
block algebra yields a *point estimate* of sustained VPU throughput, not
a bound:

    1/r1 = (ops1 + EPI)/S + o        (o = per-nonce non-ALU overhead:
    1/r2 = (ops2 + EPI)/S + o         grid/DMA/bookkeeping, identical for
                                      both shapes — same batch/tile/cpb/k)
    =>  S = (ops2 - ops1) / (1/r2 - 1/r1)

and the compute-only ceiling of the flagship shape is

    ceiling = S / (ops1 + EPI)        (reached iff o -> 0).

The model's fidelity caveat: "scalar stays scalar" mirrors Mosaic's lazy
broadcast, but Mosaic's own CSE may trim a few more ops and register
pressure may add spill traffic the count can't see; treat the ceiling as
good to a few percent, which is enough to size the remaining headroom.

Usage: python tools/roofline.py   (on the TPU; prints one JSON line)
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))  # repo root

# Tail shapes for 10-digit nonces (base 1e9).  The production kernel is
# digit-position-DYNAMIC (ops/pallas_sha256.make_pallas_minhash_dyn): its
# vector-word set is the whole dyn window, not just this d-class's digit
# words, and the op model below mirrors that.  'cmu440' -> 1 vector
# block, dyn window = words 2..6; 'y'*54 -> c_len 55, digits at bytes
# 55..64, dyn window = words 14..18.  BOTH tail blocks carry vector words
# (a 60-byte prefix would leave block 0 fully constant => scalar-unit,
# measuring nothing) AND both shapes stream exactly FIVE contrib VMEM
# windows per program, so the per-program overhead o really is identical
# between the two measurements.  If you change a probe shape, re-check
# the two dyn windows (pallas_sha256.dyn_params) are the same width —
# unequal window streaming folds an asymmetry into the marginal.
DATA_1BLK = "cmu440"
DATA_2BLK = "y" * 54


MAX_K = 6  # explicit: the measurement premise below depends on it

from bitcoin_miner_tpu.ops.pallas_sha256 import DEFAULT_CPB as CPB  # noqa: E402

# Per-nonce VPU ops of the kernel OUTSIDE compress, hand-counted from
# ops/pallas_sha256.py's kernel body (per row-visit per lane).  This is
# the BASELINE kernel's reduction epilogue — and the sieve kernel's
# pass-2 (survivor-groups-only) epilogue, which is the same full fold:
#   valid mask        2 cmp + 1 and                  = 3
#   h0/h1 select      2 where                        = 2
#   sign-flip         2 xor (bitcast is layout-free) = 2
#   idx               1 add + 1 where                = 2
#   running-min fold  9 cmp/and/or + 3 where = 12, skipped on the first
#                     of the cpb rows                = 12 * (CPB-1)/CPB
# amortised once per program over cpb rows:
#   lane index i      ~5 (2 iota + mul + 2 add)      = 5 / CPB
#   accumulator RMW   12                             = 12 / CPB
EPILOGUE_OPS = 3 + 2 + 2 + 2 + 12 * (CPB - 1) / CPB + (5 + 12) / CPB

# The sieve kernel's PASS-1 epilogue (ISSUE 13) — the survivor predicate
# that replaces all of the above on non-survivor groups, hand-counted
# from the sieve branch of ops/pallas_sha256.py's kernel body:
#   valid mask        2 cmp + 1 and                  = 3
#   h0 select         1 where (no h1 chain at all)   = 1
#   sign-flip         1 xor                          = 1
#   predicate         1 cmp (h0b <= th)              = 1
#   OR-accumulate     1 or, skipped on the first row = 1 * (CPB-1)/CPB
# amortised once per program over cpb rows:
#   lane index i      ~5                             = 5 / CPB
#   any(surv) reduce  ~1 (one cross-lane reduce)     = 1 / CPB
SIEVE_PASS1_EPILOGUE = 3 + 1 + 1 + 1 + 1 * (CPB - 1) / CPB + (5 + 1) / CPB


class _Tr:
    """Abstract value for the folded-op count: tracks only vectorness."""

    __array_ufunc__ = None  # make numpy scalars defer to our reflected ops
    __slots__ = ("vec",)

    def __init__(self, vec: bool) -> None:
        self.vec = vec


_COUNT = [0]


def _op(*xs):
    vec = any(isinstance(x, _Tr) and x.vec for x in xs)
    if vec:
        _COUNT[0] += 1  # result is vector => one VPU op
    return _Tr(vec)


for _name in ("add", "xor", "and", "or"):
    setattr(_Tr, f"__{_name}__", lambda self, o: _op(self, o))
    setattr(_Tr, f"__r{_name}__", lambda self, o: _op(self, o))
for _name in ("lshift", "rshift"):
    setattr(_Tr, f"__{_name}__", lambda self, o: _op(self, o))


def count_vector_ops(
    data: str,
    d: int,
    k: int,
    h0_only: bool = False,
    factored_k_in: "int | None" = None,
) -> int:
    """Exact VPU op count per nonce for one full tail hash of ``data`` at
    digit count ``d`` with ``k`` in-kernel digits: the contrib-word ORs of
    the kernel's w assembly plus every vector op inside each block's
    `compress` (final block in final_only form — or its ``"h0"``
    output-mask form with ``h0_only=True``, the sieve kernel's pass 1),
    threading the state's vectorness across blocks exactly as the kernel
    does.

    Vector words mirror the PRODUCTION (digit-position-dynamic) kernel:
    every word of the dyn window is a vector (OR with a runtime contrib
    tile, zero or not), not just the d-class's own digit words — this is
    the dyn kernel's documented cost and must be in the op model or the
    sustained-throughput estimate comes out biased low.

    ``factored_k_in`` (ISSUE 14) models the per-class STATIC factored
    kernel instead: only the k_in INNER digit words are vector — the
    outer digits are per-group SMEM scalars, the pre-inner-word round
    prefix runs once per group on the scalar unit (which the tracer sees
    automatically: every all-scalar sub-expression counts zero), and
    there is no dyn window at all (ops/sweep.py ``_build_kernel`` on why
    the factored form must be static)."""
    from bitcoin_miner_tpu.ops.pallas_sha256 import dyn_params
    from bitcoin_miner_tpu.ops.sha256 import build_layout, compress

    layout = build_layout(data.encode(), d)
    if factored_k_in is not None:
        split = layout.factor(k, factored_k_in)
        cwords = {p.word for p in split.inner_pos}
    else:
        window = dyn_params(layout, k)
        if window is not None:
            cwords = set(range(window[0], window[1] + 1))
        else:  # d == k static fallback: only the digit words are vector
            cwords = {p.word for p in layout.digit_pos[layout.digit_count - k :]}
    state = tuple(_Tr(False) for _ in range(8))  # midstate scalars
    total = 0
    for b in range(layout.n_tail_blocks):
        w = []
        for widx in range(b * 16, (b + 1) * 16):
            if widx in cwords:
                total += 1  # the contrib | base assembly OR
                w.append(_Tr(True))
            else:
                w.append(_Tr(False))
        _COUNT[0] = 0
        last = b == layout.n_tail_blocks - 1
        fo = ("h0" if h0_only else True) if last else False
        state = compress(state, w, final_only=fo)
        total += _COUNT[0]
    return total


def sieve_op_report(data: str, d: int, k: int) -> dict:
    """Per-pass op accounting for the two-stage sieve kernel (ISSUE 13),
    so its claimed savings are auditable without TPU time:

    - ``pass1`` = h0-only compression + the survivor-predicate epilogue —
      what EVERY lane pays;
    - ``pass2`` = the full (h0, h1) compression + the argmin-bookkeeping
      epilogue — what lanes in SURVIVOR groups pay *again* (a vanishing
      fraction once the running min tightens: its h0 falls like
      U32_MAX / nonces_swept);
    - ``baseline`` = the current kernel (full compression + bookkeeping
      on 100% of lanes), for the steady-state comparison.
    """
    full = count_vector_ops(data, d, k)
    h0 = count_vector_ops(data, d, k, h0_only=True)
    baseline = full + EPILOGUE_OPS
    pass1 = h0 + SIEVE_PASS1_EPILOGUE
    pass2 = full + EPILOGUE_OPS
    return {
        "compress_full_ops": full,
        "compress_h0_ops": h0,
        "baseline_epilogue_ops": round(EPILOGUE_OPS, 2),
        "sieve_pass1_epilogue_ops": round(SIEVE_PASS1_EPILOGUE, 2),
        "baseline_ops_per_lane": round(baseline, 2),
        "sieve_pass1_ops_per_lane": round(pass1, 2),
        "sieve_pass2_ops_per_lane": round(pass2, 2),
        # Steady state (survivor fraction -> 0): pass 1 is the whole cost.
        "sieve_steady_state_savings": round(1 - pass1 / baseline, 4),
    }


def factored_op_report(data: str, d: int, k: int) -> dict:
    """Per-pass op accounting for the FACTORED kernel (ISSUE 14) at the
    default inner split (ops/sweep.py ``default_factor_k_in``), so the
    claimed compression-side savings are auditable without TPU time.

    The factored epilogue is op-for-op the baseline's (same valid mask —
    the per-group bounds are scalar-clipped host bounds — same selects,
    flips, idx add and running-min fold; the outer-digit patching, scalar
    round prefix and group bookkeeping all live on the scalar unit), so
    EPILOGUE_OPS / SIEVE_PASS1_EPILOGUE carry over unchanged and the
    whole delta is the compression + assembly count: the inner-word-only
    vector set drops the flagship 1-block shape from 3002 to 2910
    (h0-only 3001 → 2909).  The reduction is reported against BOTH the
    unfactored baseline and the PR-13 sieve pass-1 count — the
    acceptance yardstick (3008.6 ops/lane on the flagship shape).
    """
    from bitcoin_miner_tpu.ops.sweep import default_factor_k_in

    k_in = default_factor_k_in(k)
    base = sieve_op_report(data, d, k)
    full = count_vector_ops(data, d, k, factored_k_in=k_in)
    h0 = count_vector_ops(data, d, k, h0_only=True, factored_k_in=k_in)
    f_plain = full + EPILOGUE_OPS
    f_pass1 = h0 + SIEVE_PASS1_EPILOGUE
    return {
        "k_in": k_in,
        "k_out": k - k_in,
        "compress_full_ops": full,
        "compress_h0_ops": h0,
        "factored_ops_per_lane": round(f_plain, 2),
        "factored_sieve_pass1_ops_per_lane": round(f_pass1, 2),
        # vs the unfactored kernels of the same sieve mode:
        "savings_vs_baseline": round(
            1 - f_plain / base["baseline_ops_per_lane"], 4
        ),
        "savings_vs_sieve_pass1": round(
            1 - f_pass1 / base["sieve_pass1_ops_per_lane"], 4
        ),
    }


def _rate(data: str, n: int) -> float:
    from bitcoin_miner_tpu.ops.sweep import sweep_min_hash

    base = 10**9
    sweep_min_hash(data, base, base + 10**6 - 1, max_k=MAX_K)  # compile
    t0 = time.perf_counter()
    r = sweep_min_hash(data, base, base + n - 1, max_k=MAX_K)
    dt = time.perf_counter() - t0
    assert r.lanes_swept == n
    return n / dt


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--ops-only",
        action="store_true",
        help="print only the static per-pass op accounting for the sieve "
        "kernel (no device measurement — runs anywhere, incl. CI)",
    )
    args = ap.parse_args()

    if args.ops_only:
        rep = sieve_op_report(DATA_1BLK, 10, MAX_K)
        rep2 = sieve_op_report(DATA_2BLK, 10, MAX_K)
        frep = factored_op_report(DATA_1BLK, 10, MAX_K)
        frep2 = factored_op_report(DATA_2BLK, 10, MAX_K)
        print(
            f"sieve op accounting ({DATA_1BLK!r}, d=10, k={MAX_K}): pass 1 "
            f"{rep['sieve_pass1_ops_per_lane']} ops/lane vs baseline "
            f"{rep['baseline_ops_per_lane']} -> steady-state savings "
            f"{rep['sieve_steady_state_savings']:.1%} (pass 2 on survivor "
            f"groups: {rep['sieve_pass2_ops_per_lane']} more)",
            file=sys.stderr,
        )
        print(
            f"factored op accounting (k_in={frep['k_in']}): sieve pass 1 "
            f"{frep['factored_sieve_pass1_ops_per_lane']} ops/lane vs the "
            f"unfactored {rep['sieve_pass1_ops_per_lane']} -> "
            f"{frep['savings_vs_sieve_pass1']:.1%} off the compression "
            f"plateau (plain kernel {frep['factored_ops_per_lane']} vs "
            f"{rep['baseline_ops_per_lane']}, "
            f"{frep['savings_vs_baseline']:.1%})",
            file=sys.stderr,
        )
        print(
            json.dumps(
                {
                    "metric": "sieve_op_report",
                    "shape_1blk": {"data": DATA_1BLK, "d": 10, "k": MAX_K, **rep},
                    "shape_2blk": {"data": DATA_2BLK, "d": 10, "k": MAX_K, **rep2},
                    "factored_1blk": {
                        "data": DATA_1BLK, "d": 10, "k": MAX_K, **frep,
                    },
                    "factored_2blk": {
                        "data": DATA_2BLK, "d": 10, "k": MAX_K, **frep2,
                    },
                }
            )
        )
        return 0

    import jax

    from bitcoin_miner_tpu.ops.sha256 import build_layout

    assert build_layout(DATA_1BLK.encode(), 10).n_tail_blocks == 1
    lay2 = build_layout(DATA_2BLK.encode(), 10)
    assert lay2.n_tail_blocks == 2
    # Both blocks must carry low-digit words or block 0 folds to scalars.
    low_words = {p.word for p in lay2.digit_pos[lay2.digit_count - MAX_K :]}
    assert min(low_words) < 16 <= max(low_words), low_words

    ops1 = count_vector_ops(DATA_1BLK, 10, MAX_K)
    ops2 = count_vector_ops(DATA_2BLK, 10, MAX_K)

    dev = jax.devices()[0]
    kind = (getattr(dev, "device_kind", "") or dev.platform)
    n = 2 * 10**9
    r1 = _rate(DATA_1BLK, n)
    r2 = _rate(DATA_2BLK, n)
    c = 1 / r2 - 1 / r1  # seconds per nonce for the marginal (ops2-ops1)
    # A non-positive marginal means a degenerate measurement (e.g. the
    # dispatch-caching hazard above) — refuse to publish nonsense numbers.
    assert c > 0, (r1, r2)
    sustained = (ops2 - ops1) / c
    ceiling = sustained / (ops1 + EPILOGUE_OPS)
    headroom = ceiling / r1 - 1
    print(
        f"device={kind}  exact folded ops: 1blk {ops1} + {EPILOGUE_OPS:.1f} "
        f"epilogue, 2blk {ops2} (marginal {ops2 - ops1})\n"
        f"1blk {r1 / 1e9:.3f}e9 n/s  2blk {r2 / 1e9:.3f}e9 n/s  "
        f"marginal {c * 1e9:.3f} ns -> sustained {sustained / 1e12:.2f} T "
        f"u32-ops/s; 1blk compute ceiling {ceiling / 1e9:.2f}e9 n/s "
        f"(headroom over current rate {headroom:.0%})",
        file=sys.stderr,
    )
    print(
        json.dumps(
            {
                "metric": "vpu_u32_ops_per_sec_sustained",
                "value": round(sustained),
                "ops_1blk": ops1,
                "ops_2blk": ops2,
                "epilogue_ops": round(EPILOGUE_OPS, 2),
                "rate_1blk": round(r1),
                "rate_2blk": round(r2),
                "marginal_ns": round(c * 1e9, 4),
                "ceiling_1blk": round(ceiling),
                "headroom": round(headroom, 4),
                "device_kind": kind,
                # Per-pass sieve accounting for the flagship shape: what
                # the measured rate's op model becomes with the sieve on.
                "sieve": sieve_op_report(DATA_1BLK, 10, MAX_K),
                # And the factored form's (ISSUE 14) — the compression-
                # side lever the sieve audit named as the real plateau.
                "factored": factored_op_report(DATA_1BLK, 10, MAX_K),
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
