"""The plain reference against hashlib, one nonce at a time (CPU, small
lanes)."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import pytest  # noqa: E402

import reference  # noqa: E402

U32 = 1 << 32


def brute(data, lo, hi, stop=None):
    """(exact, control): the least 64-bit hash, and the least first word over
    nonces below ``stop``, lowest nonce first."""
    exact = ctl = None
    for n in range(lo, hi + 1):
        h = reference.hash_of(data, n)
        if exact is None or h < exact[0]:
            exact = (h, n)
        if (stop is None or n < stop) and (ctl is None or h >> 32 < ctl[0] >> 32):
            ctl = (h, n)
    return exact, ctl


@pytest.fixture(autouse=True)
def small_lanes(monkeypatch):
    monkeypatch.setattr(reference, "LANE_DIGITS", 2)
    monkeypatch.setattr(reference, "ROWS_PER_CALL", 8)


@pytest.mark.parametrize("bounds", [
    [(0, 12345), (0, 3000), (5, 999)],
    [(99990, 100123), (7, 7)],
    [(U32 - 300, U32 + 300)],
])
def test_sweep_matches_hashlib(bounds):
    got = reference.sweep_min("cmu440", bounds)
    for lo, hi in bounds:
        assert got[(lo, hi)] == brute("cmu440", lo, hi, stop=U32)


def test_answer_ok_checks_hash_and_range():
    h = reference.hash_of("abc", 17)
    assert reference.answer_ok("abc", 0, 20, (h, 17))
    assert not reference.answer_ok("abc", 0, 16, (h, 17))
    assert not reference.answer_ok("abc", 0, 20, (h ^ 1, 17))


def test_u32_control_misses_a_minimum_above_two_to_the_32():
    """At a size a test holds: a data string whose minimum over a range
    straddling 2**32 lies above it, so the 32-bit control answers wrong."""
    lo, hi = U32 - 200, U32 + 200
    for i in range(20):
        data = f"ctl{i:03d}"
        exact, _ = brute(data, lo, hi)
        if exact[1] >= U32:
            break
    got = reference.sweep_min(data, [(lo, hi)])[(lo, hi)]
    assert got[0] == exact
    assert got[1] != exact


def test_full_pass_is_the_least_whole_hash():
    """The pass that rows with a tie on the first word take, run on every
    row of a range."""
    rows = [(None, d, high, a, b) for d, high, a, b in reference._rows(0, 2500)]
    best = {}
    for part, (m0, m1, idx) in reference._run_rows(b"abcdef", 1, rows, full=True):
        for r, (_, _, high, _, _) in enumerate(part):
            reference._fold(best, "all", ((int(m0[r]) << 32) | int(m1[r]),
                                          high * 100 + int(idx[r])), None)
    assert best["all"][0] == brute("abcdef", 0, 2500)[0]
