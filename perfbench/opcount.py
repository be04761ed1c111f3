"""Vector operations per nonce that the min-hash contract needs.

The count runs the plain SHA-256 of :mod:`sha256_plain` on abstract values
that carry only whether they vary across the lanes of one vector register.
An operation whose inputs are all the same across the lanes is scalar work
and is not counted; every other one is one u32 vector operation.  So the
count is the work of the hash alone, whatever kernel does it:

- only the message bytes that differ between the nonces of one (8, 128)
  u32 register are vector: its 1024 lanes hold consecutive nonces, which
  differ in their ``VARY_DIGITS`` = 4 low decimal digits; every other byte
  (the data, the separator, the high digits, the padding) is the same
  across the register;
- only the digest's first word is computed in full (the minimum is decided
  by it; the second word matters only on ties);
- a rotation counts as three operations (two shifts and an or), since the
  vector unit has no rotate;
- nothing of the kernel's own bookkeeping (masks, the running minimum,
  index arithmetic) counts.

Run ``python perfbench/opcount.py`` to print the count of each digit class
of a 6-byte data string.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from sha256_plain import H0, compress  # noqa: E402

VARY_DIGITS = 4  # 10**4 >= 1024 lanes of one (8, 128) u32 register


class _Counter:
    def __init__(self) -> None:
        self.ops = 0


class _V:
    """An abstract u32: ``vec`` if it differs across lanes."""

    __slots__ = ("vec", "ctr")

    def __init__(self, vec: bool, ctr: _Counter) -> None:
        self.vec = vec
        self.ctr = ctr

    def _op(self, other) -> "_V":
        vec = self.vec or (isinstance(other, _V) and other.vec)
        if vec:
            self.ctr.ops += 1
        return _V(vec, self.ctr)

    __add__ = __radd__ = __xor__ = __rxor__ = _op
    __and__ = __rand__ = __or__ = __ror__ = _op
    __lshift__ = __rshift__ = _op


def ops_per_nonce(data_len: int, d: int, vary_digits: int = VARY_DIGITS) -> int:
    """Vector operations to hash one ``d``-digit nonce of a ``data_len``-byte
    data string, up to the digest's first word."""
    ctr = _Counter()

    def u32(_x) -> _V:
        return _V(False, ctr)

    msg_len = data_len + 1 + d
    n_blocks = (msg_len + 9 + 63) // 64
    vary = {data_len + 1 + d - 1 - t for t in range(min(vary_digits, d))}
    state = tuple(u32(x) for x in H0)
    for b in range(n_blocks):
        w = [_V(any(4 * j + k in vary for k in range(4)), ctr)
             for j in range(16 * b, 16 * b + 16)]
        last = b == n_blocks - 1
        state = compress(state, w, u32, outputs=1 if last else 8)
    return ctr.ops


def per_class(data_len: int, classes) -> dict:
    return {d: ops_per_nonce(data_len, d) for d in classes}


if __name__ == "__main__":
    counts = per_class(6, range(8, 11))
    print(json.dumps({"data_len": 6, "vary_digits": VARY_DIGITS,
                      "ops_per_nonce": counts,
                      "least": min(counts.values())}))
