"""The frozen operation count: what the roofline's work is made of."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import pytest  # noqa: E402

import opcount  # noqa: E402


@pytest.mark.parametrize("d,ops", [(8, 2963), (9, 2896), (10, 2907)])
def test_six_byte_data_counts(d, ops):
    assert opcount.ops_per_nonce(6, d) == ops


def test_nothing_varies_nothing_counts():
    assert opcount.ops_per_nonce(6, 10, vary_digits=0) == 0


def test_more_varying_bytes_never_count_less():
    for d in (8, 9, 10):
        counts = [opcount.ops_per_nonce(6, d, v) for v in range(1, 7)]
        assert counts == sorted(counts)
