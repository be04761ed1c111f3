"""The plain reference for the min-hash contract, and the checks that use it.

The contract (CMU 15-440 P1): the answer to a job ``(data, lower, upper)``
is the least ``Hash(data, n) = BigEndian.Uint64(SHA256("<data> <n>")[:8])``
over every ``n`` in ``[lower, upper]``, the lowest nonce winning a tie.

Two checks, both independent of the program under test:

- :func:`hash_of` recomputes one answer's hash with ``hashlib``: every
  answer the window received must hash to itself and lie in its range.
- :func:`sweep_min` recomputes the minimum itself with a plain jax.numpy
  SHA-256 (:mod:`sha256_plain`) over every nonce, on whatever device jax
  has.  A job is cut into rows of 10**6 nonces that share their high
  digits; the lanes of a row carry the six low digits.  The digit count
  ``d`` is a run-time operand, so one compiled program serves every digit
  class of one data length.

``sweep_min`` also returns the 32-bit control: the answer of a sweep held
to the vector unit's native u32 width, in its nonces (none above
``2**32 - 1`` is reached) and in its comparison (on the digest's first word
alone, the lowest nonce winning among equal first words).
"""

from __future__ import annotations

import hashlib
from functools import lru_cache
from typing import Dict, Iterable, List, Sequence, Tuple

import numpy as np

from sha256_plain import H0, compress

# A row's lanes carry its nonces' LANE_DIGITS low digits (the tests run
# the same code with fewer).
LANE_DIGITS = 6
ROWS_PER_CALL = 32
_NONE = 1 << 31  # lane index of a row with no valid lane
U32 = 1 << 32  # the first nonce the 32-bit control cannot reach


def use_device(checkout) -> None:
    """Start jax for the reference: the compile cache where the program
    keeps it (``JAX_COMPILATION_CACHE_DIR``, else the checkout's
    ``.jax_cache``), and small rows on the CPU (the benchmark's tests)."""
    global LANE_DIGITS, ROWS_PER_CALL
    import os

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        cache = os.path.join(str(checkout), ".jax_cache")
        os.makedirs(cache, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache)
    if jax.default_backend() == "cpu":
        LANE_DIGITS, ROWS_PER_CALL = 3, 8


def hash_of(data: str, nonce: int) -> int:
    digest = hashlib.sha256(f"{data} {nonce}".encode()).digest()
    return int.from_bytes(digest[:8], "big")


def answer_ok(data: str, lower: int, upper: int, ans) -> bool:
    """The answer names a nonce in range whose hash is the one given."""
    h, n = ans
    return lower <= n <= upper and hash_of(data, n) == h


def _rows(lower: int, upper: int) -> Iterable[Tuple[int, int, int, int]]:
    """``(d, high, lane_lo, lane_hi)`` rows covering ``[lower, upper]``:
    nonces ``high * 10**LANE_DIGITS + lane`` with ``d`` digits, lanes
    inclusive."""
    ROW = 10**LANE_DIGITS
    for d in range(len(str(lower)), len(str(upper)) + 1):
        s = max(lower, 0 if d == 1 else 10 ** (d - 1))
        e = min(upper, 10**d - 1)
        if s > e:
            continue
        if d <= LANE_DIGITS:
            yield d, 0, s, e
            continue
        for high in range(s // ROW, e // ROW + 1):
            base = high * ROW
            yield d, high, max(s, base) - base, min(e, base + ROW - 1) - base


def _n_blocks(data_len: int, d: int) -> int:
    return (data_len + 1 + d + 9 + 63) // 64


def _template(data: bytes, d: int, high: int) -> np.ndarray:
    """The padded message words of a row, with zeros at the lane digits."""
    msg = bytearray(data + b" ")
    if d > LANE_DIGITS:
        msg += str(high).encode()
    msg += bytes(min(d, LANE_DIGITS))
    bit_len = len(msg) * 8
    msg.append(0x80)
    msg += bytes((-len(msg) - 8) % 64)
    msg += bit_len.to_bytes(8, "big")
    return np.frombuffer(bytes(msg), dtype=">u4").astype(np.uint32)


@lru_cache(maxsize=None)
def _program(data_len: int, n_blocks: int, lane_digits: int, full: bool):
    """Per row: with ``full``, the least (first word, second word, lane);
    else the least first word, the lowest lane holding it, and how many
    lanes hold it.  The first pass computes one digest word, so the
    compression is computed once per lane; rows with a tie on it (about
    one in 4000) go through the ``full`` pass."""
    import jax
    import jax.numpy as jnp

    u32 = jnp.uint32
    n_words = 16 * n_blocks
    # Words that can hold a lane digit for some digit count 1..20.
    first = (data_len + 1) // 4
    last = min(n_words - 1, (data_len + 20) // 4)
    lane_words = range(first, last + 1)

    # Lanes form a (10**top, 10**low) grid: the low digits of a lane come
    # from its column, the others from its row, with no division per lane.
    low = lane_digits // 2
    top = lane_digits - low

    # XLA:CPU takes many minutes to compile the unrolled rounds; there (in
    # the tests) the rounds run as a loop.  The chip runs them unrolled.
    rolled = jax.default_backend() == "cpu"

    def rows_min(tmpl, d, lo, hi):
        r = jnp.arange(10**top, dtype=u32)[:, None]
        c = jnp.arange(10**low, dtype=u32)[None, :]
        lane = r * u32(10**low) + c
        digit = []
        for t in range(lane_digits):
            src, p = (c, t) if t < low else (r, t - low)
            digit.append(((src // u32(10**p)) % u32(10))[None])
        col = lambda x: x[:, None, None]  # noqa: E731
        words = [col(tmpl[:, j]) for j in range(n_words)]
        for t in range(lane_digits):
            pos = (data_len + d - t).astype(u32)  # byte of digit t
            on = t < jnp.minimum(d, lane_digits)
            byte = (u32(0x30) + digit[t]) << col(u32(8) * (u32(3) - pos % u32(4)))
            for j in lane_words:
                sel = jnp.where(on & (pos // u32(4) == u32(j)), u32(0xFFFFFFFF), u32(0))
                words[j] = words[j] | (byte & col(sel))
        state = tuple(u32(x) for x in H0)
        shape = jnp.broadcast_shapes(*(w.shape for w in words))
        n_out = 2 if full else 1
        for b in range(n_blocks):
            last_block = b == n_blocks - 1
            block = words[16 * b: 16 * b + 16]
            if rolled:
                state = _compress_rolled(state, block, shape, n_out if last_block else 8)
            else:
                state = compress(state, block, u32, outputs=n_out if last_block else 8)
        # Materialise the digest words: each reduction below would otherwise
        # fuse (and recompute) the whole compression.
        state = jax.lax.optimization_barrier(state)
        h0 = state[0]
        valid = (lane >= col(lo).astype(u32)) & (lane <= col(hi).astype(u32))
        top32 = u32(0xFFFFFFFF)
        none = u32(_NONE)
        axes = (1, 2)
        m0 = jnp.min(jnp.where(valid, h0, top32), axis=axes)
        tie0 = valid & (h0 == col(m0))
        if not full:
            idx32 = jnp.min(jnp.where(tie0, lane, none), axis=axes)
            return m0, idx32, jnp.sum(tie0, axis=axes, dtype=jnp.int32)
        h1 = state[1]
        m1 = jnp.min(jnp.where(tie0, h1, top32), axis=axes)
        idx = jnp.min(jnp.where(tie0 & (h1 == col(m1)), lane, none), axis=axes)
        return m0, m1, idx

    return jax.jit(rows_min)


def _compress_rolled(state, w, shape, outputs):
    """:func:`sha256_plain.compress` with the rounds as a loop."""
    import jax
    import jax.numpy as jnp

    from sha256_plain import K, rotr

    u32 = jnp.uint32
    k = jnp.asarray(np.array(K, np.uint32))
    sched = jnp.stack([jnp.broadcast_to(x, shape) for x in w])
    regs = tuple(jnp.broadcast_to(u32(x) if isinstance(x, int) else x, shape)
                 for x in state)

    def step(t, carry):
        (a, b, c, d, e, f, g, h), ws = carry
        w15, w2 = ws[(t + 1) % 16], ws[(t + 14) % 16]
        s0 = rotr(w15, 7, u32) ^ rotr(w15, 18, u32) ^ (w15 >> u32(3))
        s1 = rotr(w2, 17, u32) ^ rotr(w2, 19, u32) ^ (w2 >> u32(10))
        wt = jnp.where(t < 16, ws[t % 16], ws[t % 16] + s0 + ws[(t + 9) % 16] + s1)
        ws = ws.at[t % 16].set(wt)
        s1e = rotr(e, 6, u32) ^ rotr(e, 11, u32) ^ rotr(e, 25, u32)
        t1 = h + s1e + (g ^ (e & (f ^ g))) + k[t] + wt
        s0a = rotr(a, 2, u32) ^ rotr(a, 13, u32) ^ rotr(a, 22, u32)
        t2 = s0a + ((a & b) ^ (a & c) ^ (b & c))
        return (t1 + t2, a, b, c, d + t1, e, f, g), ws

    regs, _ = jax.lax.fori_loop(0, 64, step, (regs, sched))
    return tuple(s + x for s, x in zip(state[:outputs], regs[:outputs]))


def sweep_min(data: str, bounds: Sequence[Tuple[int, int]]) -> Dict:
    """Exact answers for each ``(lower, upper)`` in ``bounds`` over one
    data string, from one sweep of their union.

    Returns ``{(lower, upper): ((hash, nonce), (hash32, nonce32))}``: the
    exact answer and the 32-bit control's."""
    raw = data.encode()
    # Cut the union at every range end, so that each piece lies wholly
    # inside or wholly outside each range; sweep each piece once.
    cuts = sorted({lo for lo, _ in bounds} | {hi + 1 for _, hi in bounds} | {U32})
    pieces = [
        (a, b - 1) for a, b in zip(cuts, cuts[1:])
        if any(lo <= a and b - 1 <= hi for lo, hi in bounds)
    ]
    best = _sweep_pieces(raw, pieces)
    out = {}
    for lo, hi in bounds:
        inside = [(p, best[p]) for p in pieces if lo <= p[0] and p[1] <= hi]
        exact = min(e for _, (e, _) in inside)
        low = [c for p, (_, c) in inside if p[1] < U32]
        ctl = min(low, key=lambda c: (c[0] >> 32, c[1])) if low else None
        out[(lo, hi)] = (exact, ctl)
    return out


def _sweep_pieces(raw: bytes, pieces: Sequence[Tuple[int, int]]) -> Dict:
    """``{piece: ((hash, nonce), (hash32, nonce32))}``.  The rows of every
    piece go to the device in one stream, ``ROWS_PER_CALL`` to a call."""
    data = raw.decode()
    by_blocks: Dict[int, List] = {}
    for p in pieces:
        for d, high, a, b in _rows(*p):
            by_blocks.setdefault(_n_blocks(len(raw), d), []).append((p, d, high, a, b))
    best: Dict = {}
    for nb, rows in by_blocks.items():
        tied = []
        for part, (m0, idx32, ties) in _run_rows(raw, nb, rows, full=False):
            for r, (p, _, high, _, _) in enumerate(part):
                if idx32[r] == _NONE:
                    continue
                n32 = high * 10**LANE_DIGITS + int(idx32[r])
                h32 = hash_of(data, n32)
                if h32 >> 32 != int(m0[r]):
                    raise RuntimeError(f"reference: {data} {n32} first word disagrees")
                if ties[r] > 1:
                    tied.append(part[r])
                _fold(best, p, (h32, n32), (h32, n32))
        for part, (m0, m1, idx) in _run_rows(raw, nb, tied, full=True):
            for r, (p, _, high, _, _) in enumerate(part):
                exact = ((int(m0[r]) << 32) | int(m1[r]), high * 10**LANE_DIGITS + int(idx[r]))
                _fold(best, p, exact, None)
    return best


def _run_rows(raw: bytes, nb: int, rows: List, full: bool):
    """Yield ``(rows of one call, host arrays of its outputs)``."""
    fn = _program(len(raw), nb, LANE_DIGITS, full)
    pending = []
    for s in range(0, len(rows), ROWS_PER_CALL):
        part = rows[s: s + ROWS_PER_CALL]
        pad = ROWS_PER_CALL - len(part)
        tmpl = np.stack([_template(raw, d, hi) for _, d, hi, _, _ in part])
        tmpl = np.concatenate([tmpl, np.zeros((pad, tmpl.shape[1]), np.uint32)])
        col = lambda k, fill: np.array(  # noqa: E731
            [r[k] for r in part] + [fill] * pad, np.int32
        )
        pending.append((part, fn(tmpl, col(1, 1), col(3, 1), col(4, 0))))
        if len(pending) > 4:  # keep the device fed while the host folds
            part, outs = pending.pop(0)
            yield part, [np.asarray(x) for x in outs]
    for part, outs in pending:
        yield part, [np.asarray(x) for x in outs]


def _fold(best: Dict, piece, exact, c32) -> None:
    """Fold one row's candidates into its piece's: the exact answer by the
    whole hash, the control by the first word, lowest nonce first."""
    e, c = best.get(piece, (None, None))
    if e is None or exact < e:
        e = exact
    if c32 is not None and (c is None or (c32[0] >> 32, c32[1]) < (c[0] >> 32, c[1])):
        c = c32
    best[piece] = (e, c)
