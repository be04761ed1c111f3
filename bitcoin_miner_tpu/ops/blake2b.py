"""BLAKE2b-64 device kernel plane (ISSUE 20).

The registry's BLAKE2b workload (workloads/blake2b.py) was the only
workload with no device tier: every nonce ran on the host interpreter
while the SHA-256 stack enjoyed factored/sieve/hot XLA+Pallas kernels.
This module closes that gap with a jnp kernel computing BLAKE2b with an
8-byte digest over ``"<data> <nonce>"`` message lanes — the same
message-template decomposition as :mod:`ops.sha256` (constant prefix
folded host-side, iota-generated ASCII nonce digits per lane), adapted
to BLAKE2b's structure:

- **u32 hi/lo word pairs.**  BLAKE2b is a 64-bit-word hash and jax here
  runs without ``jax_enable_x64``, so every u64 word is an interleaved
  ``(hi, lo)`` u32 pair and the G-function's adds propagate carries
  explicitly: ``lo = al + bl; carry = lo < bl; hi = ah + bh + carry``
  (unsigned wraparound compare — the standard two-limb add).  G's
  double-adds ``a + b + x`` fuse into one two-carry chain (9 ops
  instead of 10).  Rotations are pairwise shifts; ``rotr 32`` is a free
  limb swap.

- **Midstate folding.**  BLAKE2b chains 128-byte blocks, so every whole
  block of the constant ``"<data> "`` prefix is compressed ONCE per job
  host-side (:func:`compress_py`) into a 16-u32 midstate — the analogue
  of ops/sha256's SHA-256 midstate.  For multi-block job data the cpu
  tier re-hashes the full prefix per nonce while the device tier hashes
  exactly one tail block per lane; that asymmetry is the family's
  architectural win and what ``bench.py --tier-compare`` prices.

- **Zero-word folding.**  BLAKE2b zero-pads its final block (no padding
  bits), so for short tails most of the 16 message words are
  structurally zero for EVERY lane of a shape class.  Those words'
  additions are elided from the unrolled G DAG entirely (the word set
  is part of the kernel cache key) — for the flagship short-tail
  layouts 13 of 16 message words vanish, ~780 vector ops per lane.

- **Grouped unrolled compression.**  The 12 rounds are unrolled
  straight-line (~5k-op DAG) inside an outer ``fori_loop`` over decimal
  digit groups — the ISSUE-14 factoring, reusing
  :func:`ops.sha256.factor_low_pos` / :func:`outer_patch_table` — so
  the working set stays cache-resident at ``(B, 10^k_in)``.  Unlike
  SHA-256's message schedule, BLAKE2b's SIGMA permutation feeds raw
  message words to every round, so the unrolled DAG is what makes the
  zero-word elision reach all 12 rounds.  XLA fuses that DAG whole (three
  ~4.5k-instruction loop fusions a class on the CPU), and XLA:CPU's
  default MLIR fusion emitters take minutes and tens of GB to generate
  code for fusions that size; its legacy emitter compiles a class in
  ~3 s at 0.4 GB peak RSS (jax 0.9.0, 8-core x86 host), so every jit of
  this kernel passes :func:`compiler_options`.  A rolled 12-round
  ``fori_loop`` also compiles in ~1.5 s, but runs 17-45x slower there
  (0.4-1.1M vs 18.7M nonces/s a k=5 dispatch) because each round splits
  into ~20-70 fusions.

The kernel keeps the exact operand/result contract of the SHA-256 xla
tier — ``(midstate, tail_const (B, nw), bounds (B, 2)[, thresh]) ->
(min_h0, min_h1, flat_idx)`` with the lexicographic big-endian
``(h0, h1)`` min-fold and lowest-nonce ties — so ``ops.sweep``'s
drivers, the hot plane's donated steps, and ``parallel/sweep.py``'s
collective cascade all serve the family unchanged; only the layout
builder and kernel factory differ (dispatched on ``layout.family``).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .sha256 import DigitPos, factor_low_pos, outer_patch_table

U32_MAX = 0xFFFFFFFF
I32_MAX = 0x7FFFFFFF
_MASK64 = (1 << 64) - 1

#: BLAKE2b IV (RFC 7693 §2.6): the SHA-512 IV.
IV = (
    0x6A09E667F3BCC908, 0xBB67AE8584CAA73B, 0x3C6EF372FE94F82B,
    0xA54FF53A5F1D36F1, 0x510E527FADE682D1, 0x9B05688C2B3E6C1F,
    0x1F83D9ABFB41BD6B, 0x5BE0CD19137E2179,
)

#: Message schedule (RFC 7693 §2.7); rounds 10/11 repeat rows 0/1.
SIGMA = (
    (0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15),
    (14, 10, 4, 8, 9, 15, 13, 6, 1, 12, 0, 2, 11, 7, 5, 3),
    (11, 8, 12, 0, 5, 2, 15, 13, 10, 14, 3, 6, 7, 1, 9, 4),
    (7, 9, 3, 1, 13, 12, 11, 14, 2, 6, 5, 10, 4, 0, 15, 8),
    (9, 0, 5, 7, 2, 4, 10, 15, 14, 1, 11, 12, 6, 8, 3, 13),
    (2, 12, 6, 10, 0, 11, 8, 3, 4, 13, 7, 5, 15, 14, 1, 9),
    (12, 5, 1, 15, 14, 13, 4, 10, 0, 7, 6, 3, 9, 2, 8, 11),
    (13, 11, 7, 14, 12, 1, 3, 9, 5, 0, 15, 4, 8, 6, 2, 10),
    (6, 15, 14, 9, 11, 3, 0, 8, 12, 2, 13, 7, 1, 4, 10, 5),
    (10, 2, 8, 4, 7, 6, 1, 5, 15, 11, 9, 14, 3, 12, 13, 0),
    (0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15),
    (14, 10, 4, 8, 9, 15, 13, 6, 1, 12, 0, 2, 11, 7, 5, 3),
)

#: The column/diagonal (a, b, c, d) state indices of one round's 8 G's.
GIDX = (
    (0, 4, 8, 12), (1, 5, 9, 13), (2, 6, 10, 14), (3, 7, 11, 15),
    (0, 5, 10, 15), (1, 6, 11, 12), (2, 7, 8, 13), (3, 4, 9, 14),
)

#: BLAKE2b parameter-block word 0 for digest_size=8, no key, fanout=1,
#: depth=1 — XORed into h[0] (digest size KEYS the hash; BLAKE2b-64 is
#: its own function, not a truncation of BLAKE2b-512).
_PARAM0 = 0x01010008


# --------------------------------------------------------------------------
# Host-side reference (python ints) — midstate folding + oracle
# --------------------------------------------------------------------------


def _rotr64_py(x: int, n: int) -> int:
    return ((x >> n) | (x << (64 - n))) & _MASK64


def compress_py(
    h: Tuple[int, ...], block: bytes, t: int, final: bool
) -> Tuple[int, ...]:
    """One BLAKE2b compression over a 128-byte block, on python ints —
    the host-side midstate fold (and the oracle :func:`digest64_py` is
    built on).  ``t`` counts total message bytes through this block."""
    m = [int.from_bytes(block[8 * i : 8 * i + 8], "little") for i in range(16)]
    v = list(h) + list(IV)
    v[12] ^= t & _MASK64
    v[13] ^= (t >> 64) & _MASK64
    if final:
        v[14] ^= _MASK64
    for r in range(12):
        s = SIGMA[r]
        for gi, (a, b, c, d) in enumerate(GIDX):
            x, y = m[s[2 * gi]], m[s[2 * gi + 1]]
            v[a] = (v[a] + v[b] + x) & _MASK64
            v[d] = _rotr64_py(v[d] ^ v[a], 32)
            v[c] = (v[c] + v[d]) & _MASK64
            v[b] = _rotr64_py(v[b] ^ v[c], 24)
            v[a] = (v[a] + v[b] + y) & _MASK64
            v[d] = _rotr64_py(v[d] ^ v[a], 16)
            v[c] = (v[c] + v[d]) & _MASK64
            v[b] = _rotr64_py(v[b] ^ v[c], 63)
    return tuple(h[i] ^ v[i] ^ v[8 + i] for i in range(8))


def init_h() -> Tuple[int, ...]:
    """The BLAKE2b-64 initial chaining state: IV with the parameter
    block's word 0 folded into h[0]."""
    return (IV[0] ^ _PARAM0,) + IV[1:]


def digest64_py(msg: bytes) -> int:
    """Pure-python BLAKE2b-64 of ``msg`` read big-endian — an
    hashlib-independent oracle (the analyzer's contract pass uses it to
    pin the compression math itself, not just hashlib agreement)."""
    h = init_h()
    n_blocks = max(1, (len(msg) + 127) // 128)
    for b in range(n_blocks):
        chunk = msg[128 * b : 128 * (b + 1)]
        final = b == n_blocks - 1
        t = len(msg) if final else 128 * (b + 1)
        h = compress_py(h, chunk.ljust(128, b"\x00"), t, final)
    return int.from_bytes(h[0].to_bytes(8, "little"), "big")


# --------------------------------------------------------------------------
# Message layout (host): midstate + tail template + digit positions
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class Blake2bLayout:
    """Per-(data, digit-count) message layout for the BLAKE2b kernels —
    the family's analogue of :class:`ops.sha256.MsgLayout`, sharing its
    field contract so ``ops.sweep``'s template fill / dispatch plumbing
    is family-generic:

    - ``midstate``: 16 u32 (hi, lo per u64 h word) — the chaining state
      after compressing every whole 128-byte block of the constant
      ``data + sep`` prefix (``tail_off`` bytes folded host-side, once
      per job).
    - ``tail_template``: ``32 * n_tail_blocks`` u32 — the remaining
      message bytes as LE u64 words split into (hi, lo) pairs, digit
      positions zero.
    - ``digit_pos``: flat (word, shift) of each of the ``digit_count``
      ASCII nonce digits, most significant first — byte ``o`` of a u64
      word lands in the LO half for ``o < 4`` (LE), else the HI half.
    - ``live_words``: the template word indices that can be nonzero for
      any lane (template content or digit positions) — the zero-word
      elision set, part of the kernel shape class.
    """

    family = "blake2b"

    data_len: int
    digit_count: int
    msg_len: int
    tail_off: int
    midstate: Tuple[int, ...]
    tail_template: Tuple[int, ...]
    digit_pos: Tuple[DigitPos, ...]
    live_words: Tuple[int, ...]

    @property
    def n_tail_blocks(self) -> int:
        return len(self.tail_template) // 32

    @property
    def static_key(self):
        """The kernel shape class this layout compiles under."""
        return (
            self.msg_len, self.tail_off, self.n_tail_blocks,
            self.digit_pos, self.live_words,
        )


def build_layout(data: bytes, digit_count: int, sep: bytes = b" ") -> Blake2bLayout:
    """Build the :class:`Blake2bLayout` for ``data + sep + <digit_count
    decimal digits>``: fold whole prefix blocks into the midstate, lay
    the remainder out as zero-padded LE word-pair templates (BLAKE2b
    zero-fills its final block — no padding bits; ``t`` counts actual
    message bytes)."""
    if not 1 <= digit_count <= 20:
        raise ValueError(f"digit_count {digit_count} outside u64's 1..20")
    prefix = data + sep
    c_len = len(prefix)
    msg_len = c_len + digit_count
    n_const = c_len // 128
    tail_off = 128 * n_const
    tail_len = msg_len - tail_off
    n_tail_blocks = (tail_len + 127) // 128
    tail = bytearray(128 * n_tail_blocks)
    tail[: c_len - tail_off] = prefix[tail_off:]

    digit_pos = []
    for j in range(digit_count):
        off = (c_len - tail_off) + j
        q, o = off // 8, off % 8
        digit_pos.append(
            DigitPos(word=2 * q + 1, shift=8 * o)
            if o < 4
            else DigitPos(word=2 * q, shift=8 * (o - 4))
        )

    tmpl = []
    for q in range(16 * n_tail_blocks):
        w = int.from_bytes(tail[8 * q : 8 * q + 8], "little")
        tmpl.append((w >> 32) & U32_MAX)
        tmpl.append(w & U32_MAX)

    h = init_h()
    for b in range(n_const):
        h = compress_py(h, prefix[128 * b : 128 * (b + 1)], 128 * (b + 1), False)
    midstate = []
    for hv in h:
        midstate.append((hv >> 32) & U32_MAX)
        midstate.append(hv & U32_MAX)

    dwords = {dp.word for dp in digit_pos}
    live = tuple(
        w for w in range(32 * n_tail_blocks) if tmpl[w] or w in dwords
    )
    return Blake2bLayout(
        data_len=len(data),
        digit_count=digit_count,
        msg_len=msg_len,
        tail_off=tail_off,
        midstate=tuple(midstate),
        tail_template=tuple(tmpl),
        digit_pos=tuple(digit_pos),
        live_words=live,
    )


# --------------------------------------------------------------------------
# Device-side primitives: two-limb adds, pairwise rotations, G
# --------------------------------------------------------------------------


def _addm(ah, al, bh, bl, x):  # jit-kernel
    """u64 add ``a + b`` on (hi, lo) u32 limbs with explicit carry; with
    ``x = (xh, xl)`` the fused double-add ``a + b + x`` (two carries,
    one chain — G's message-word adds).  ``x = None`` elides the second
    operand entirely: structurally-zero message words cost nothing."""
    lo = al + bl
    c1 = (lo < bl).astype(jnp.uint32)
    if x is None:  # trace-ok: structural None/tuple switch, static per call site
        return ah + bh + c1, lo
    xh, xl = x
    lo2 = lo + xl
    c2 = (lo2 < xl).astype(jnp.uint32)
    return ah + bh + xh + c1 + c2, lo2


def _rotr64(h, l, n: int):  # jit-kernel
    """Pairwise rotr of a (hi, lo) u32 pair by static n; n == 32 is a
    free limb swap."""
    if n == 32:  # trace-ok: n is a Python int literal at every call site
        return l, h
    if n < 32:  # trace-ok: n is a Python int literal at every call site
        nn = jnp.uint32(n)
        m = jnp.uint32(32 - n)
        return (h >> nn) | (l << m), (l >> nn) | (h << m)
    nn = jnp.uint32(n - 32)
    m = jnp.uint32(32 - (n - 32))
    return (l >> nn) | (h << m), (h >> nn) | (l << m)


def _G(v, a, b, c, d, x, y):  # jit-kernel
    """One BLAKE2b G on the flat (hi, lo)-interleaved v list; ``x``/``y``
    are (hi, lo) message-word pairs or None (zero word — add elided)."""
    ah, al = v[2 * a], v[2 * a + 1]
    bh, bl = v[2 * b], v[2 * b + 1]
    ch, cl = v[2 * c], v[2 * c + 1]
    dh, dl = v[2 * d], v[2 * d + 1]
    ah, al = _addm(ah, al, bh, bl, x)
    dh, dl = _rotr64(dh ^ ah, dl ^ al, 32)
    ch, cl = _addm(ch, cl, dh, dl, None)
    bh, bl = _rotr64(bh ^ ch, bl ^ cl, 24)
    ah, al = _addm(ah, al, bh, bl, y)
    dh, dl = _rotr64(dh ^ ah, dl ^ al, 16)
    ch, cl = _addm(ch, cl, dh, dl, None)
    bh, bl = _rotr64(bh ^ ch, bl ^ cl, 63)
    v[2 * a], v[2 * a + 1] = ah, al
    v[2 * b], v[2 * b + 1] = bh, bl
    v[2 * c], v[2 * c + 1] = ch, cl
    v[2 * d], v[2 * d + 1] = dh, dl


def _compress_pairs(h, m: Dict[int, Tuple], t: int, final: bool):  # jit-kernel
    """Unrolled 12-round compression on (hi, lo) u32 pairs.  ``h`` is the
    16-entry flat chaining state; ``m`` maps u64 message-word index ->
    (hi, lo) pair, with structurally-zero words ABSENT (their G adds are
    elided).  ``t``/``final`` are static per shape class."""
    v = list(h)
    for q in range(8):
        hi = IV[q] >> 32
        lo = IV[q] & U32_MAX
        if q == 4:  # v[12] ^= t (t < 2^64: message bytes)  # trace-ok: t/q static
            hi ^= (t >> 32) & U32_MAX
            lo ^= t & U32_MAX
        if q == 6 and final:  # v[14] ^= ~0  # trace-ok: final static per shape
            hi ^= U32_MAX
            lo ^= U32_MAX
        v.append(jnp.uint32(hi))
        v.append(jnp.uint32(lo))
    for r in range(12):
        s = SIGMA[r]
        for gi, (a, b, c, d) in enumerate(GIDX):
            _G(v, a, b, c, d, m.get(s[2 * gi]), m.get(s[2 * gi + 1]))
    return [h[i] ^ v[i] ^ v[16 + i] for i in range(16)]


def _bswap32(x):  # jit-kernel
    """Byte-swap a u32: the digest is h[0]'s LE bytes read big-endian, so
    the comparable (h0, h1) pair is (bswap(lo), bswap(hi))."""
    return (
        ((x & jnp.uint32(0xFF)) << 24)
        | ((x & jnp.uint32(0xFF00)) << 8)
        | ((x >> 8) & jnp.uint32(0xFF00))
        | (x >> 24)
    )


# --------------------------------------------------------------------------
# The kernel body + jitted factory
# --------------------------------------------------------------------------


def make_blake2b_kernel_body(
    msg_len: int,
    tail_off: int,
    n_tail_blocks: int,
    live_words: Tuple[int, ...],
    low_pos: Tuple[DigitPos, ...],
    k: int,
    batch: int,
    sieve: bool = False,
    factored: int = 0,
):
    """Build the pure (un-jitted) BLAKE2b min-hash kernel body for one
    shape class — the family's :func:`ops.sweep.make_kernel_body`.

    Returned fn: ``(midstate (16,), tail_const (B, 32*n_tail_blocks),
    bounds (B, 2)[, thresh]) -> (min_h0, min_h1, flat_idx)`` — the same
    contract as the SHA-256 xla kernels (big-endian lexicographic min,
    lowest flat-lane ties, I32_MAX when every lane is masked), so the
    per-chunk drivers, the hot plane's donated steps, and the sharded
    collective cascade work unchanged.

    ``factored = k_in > 0`` runs the grouped form: an outer ``fori_loop``
    over ``10^(k - k_in)`` digit groups (template patched per group from
    :func:`ops.sha256.outer_patch_table`) with the fully unrolled
    compression inside at the cache-resident ``(B, 10^k_in)`` shape —
    the family's production form.  ``factored = 0`` is the single-group
    full-lane form (tiny classes).

    ``sieve = True`` takes the running-min h0 threshold operand: lanes
    with ``h0 > thresh`` are masked before the fold (``<=`` keeps ties —
    the conservative survival contract), and the threshold tightens
    across groups with the carried best (the sequential-dimension
    tightening of the factored SHA-256 sieve).  BLAKE2b's h0 and h1 fall
    out of one compression output word, so there is no cheaper h0-only
    pass to stage — the operand exists for the hot plane's carried
    threshold, not as a two-pass win.
    """
    n_lanes = 10**k
    live = frozenset(live_words)
    if factored:
        split = factor_low_pos(low_pos, factored)
        k_in = split.k_in
        inner_pos = split.inner_pos
        owords, otab_np = outer_patch_table(split.outer_pos)
    else:
        k_in = k
        inner_pos = low_pos
        owords, otab_np = (), np.zeros((1, 1), dtype=np.uint32)
    s_in = 10**k_in
    g_count = 10 ** (k - k_in)
    owidx = {wd: m for m, wd in enumerate(owords)}

    _start = (
        jnp.uint32(U32_MAX), jnp.uint32(U32_MAX), jnp.int32(I32_MAX),
    )

    def kernel(midstate, tail_const, bounds, *th):
        i = jnp.arange(s_in, dtype=jnp.int32)
        contrib = {}
        for j, dp in enumerate(inner_pos):
            p = 10 ** (k_in - 1 - j)
            dig = ((i // p) % 10 + 48).astype(jnp.uint32) << jnp.uint32(dp.shift)
            contrib[dp.word] = (
                contrib[dp.word] | dig if dp.word in contrib else dig
            )
        h_pairs = [midstate[q] for q in range(16)]
        otabj = jnp.asarray(otab_np)
        flat = jnp.arange(batch * s_in, dtype=jnp.int32)

        def body(og, carry):
            orow = lax.dynamic_index_in_dim(otabj, og, 0, keepdims=False)
            state = h_pairs
            for b in range(n_tail_blocks):
                m = {}
                for q in range(16):
                    w_hi, w_lo = 32 * b + 2 * q, 32 * b + 2 * q + 1
                    if w_hi not in live and w_lo not in live:
                        continue  # structurally zero for every lane
                    halves = []
                    for w in (w_hi, w_lo):
                        col = tail_const[:, w][:, None]  # (B, 1)
                        if w in owidx:
                            col = col | orow[owidx[w]]
                        if w in contrib:
                            col = col | contrib[w][None, :]  # (B, s_in)
                        halves.append(col)
                    m[q] = tuple(halves)
                final = b == n_tail_blocks - 1
                t = msg_len if final else tail_off + 128 * (b + 1)
                state = _compress_pairs(state, m, t, final)
            # digest = h'[0] serialized LE, read big-endian.
            oh0 = jnp.broadcast_to(_bswap32(state[1]), (batch, s_in))
            oh1 = jnp.broadcast_to(_bswap32(state[0]), (batch, s_in))
            gb = jnp.clip(bounds - og * s_in, 0, s_in)
            valid = (i[None, :] >= gb[:, :1]) & (i[None, :] < gb[:, 1:2])
            mask = valid
            if sieve:
                # Tighten with the carried best across the group loop
                # (the sequential dimension); <= keeps ties.
                tgt = jnp.minimum(th[0], carry[0])
                mask = mask & (oh0 <= tgt)
            oh0 = jnp.where(mask, oh0, jnp.uint32(U32_MAX))
            oh1 = jnp.where(mask, oh1, jnp.uint32(U32_MAX))
            h0f = oh0.reshape(-1)
            h1f = oh1.reshape(-1)
            maskf = mask.reshape(-1)
            min_h0 = jnp.min(h0f)
            e0 = h0f == min_h0
            min_h1 = jnp.min(jnp.where(e0, h1f, jnp.uint32(U32_MAX)))
            e1 = e0 & (h1f == min_h1) & maskf
            fi = jnp.min(jnp.where(e1, flat, jnp.int32(I32_MAX)))
            bh0, bh1, bidx = carry
            # Remap the group-local flat lane to the dispatch-global
            # index (same row-major remap as the factored SHA-256
            # kernel) so cross-group ties stay lowest-nonce.
            gidx = jnp.where(
                fi == jnp.int32(I32_MAX),
                jnp.int32(I32_MAX),
                (fi // s_in) * n_lanes + og * s_in + fi % s_in,
            )
            better = (min_h0 < bh0) | (
                (min_h0 == bh0)
                & ((min_h1 < bh1) | ((min_h1 == bh1) & (gidx < bidx)))
            )
            return (
                jnp.where(better, min_h0, bh0),
                jnp.where(better, min_h1, bh1),
                jnp.where(better, gidx, bidx),
            )

        if g_count == 1:
            return body(jnp.int32(0), _start)
        return lax.fori_loop(0, g_count, body, _start)

    return kernel


def compiler_options() -> Optional[Dict[str, bool]]:
    """XLA options for every jit of this family's kernel, on the default
    backend: XLA:CPU's legacy fusion emitter, which compiles the unrolled
    compression in seconds where its default takes minutes (module
    docstring); other backends get none."""
    if jax.default_backend() == "cpu":
        return {"xla_cpu_use_fusion_emitters": False}
    return None


@lru_cache(maxsize=256)
def _make_blake2b_kernel(
    msg_len: int,
    tail_off: int,
    n_tail_blocks: int,
    live_words: Tuple[int, ...],
    low_pos: Tuple[DigitPos, ...],
    k: int,
    batch: int,
    sieve: bool = False,
    factored: int = 0,
):
    """Jitted single-device wrapper over :func:`make_blake2b_kernel_body`
    (the family's ``_make_kernel``)."""
    return jax.jit(
        make_blake2b_kernel_body(
            msg_len, tail_off, n_tail_blocks, live_words, low_pos, k,
            batch, sieve=sieve, factored=factored,
        ),
        compiler_options=compiler_options(),
    )


def build_kernel_for(
    layout: Blake2bLayout,
    group,
    batch: int,
    sieve: bool = False,
    factored: bool = False,
):
    """Resolve one (layout, chunk-group) shape class to its cached jitted
    kernel — the blake2b branch of :func:`ops.sweep._build_kernel`.
    ``factored`` resolves through :func:`ops.sweep.default_factor_k_in`
    exactly like the SHA-256 xla tier (k=5 -> k_in=3, the measured-best
    grouping on this host); a 1-digit lane axis has nothing to factor."""
    from .sweep import default_factor_k_in

    low_pos = layout.digit_pos[layout.digit_count - group.k :]
    return _make_blake2b_kernel(
        layout.msg_len,
        layout.tail_off,
        layout.n_tail_blocks,
        layout.live_words,
        low_pos,
        group.k,
        batch,
        sieve=sieve,
        factored=(
            default_factor_k_in(group.k) if factored and group.k >= 2 else 0
        ),
    )
