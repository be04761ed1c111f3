"""The controls come out not correct (CPU, sizes a test holds; the chip
runs ``control.py`` at the cells' own sizes)."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path[:0] = [str(Path(__file__).resolve().parents[1]), str(Path(__file__).resolve().parents[2])]

import pytest  # noqa: E402

import control  # noqa: E402
import reference  # noqa: E402
from lspjobs import Job  # noqa: E402


@pytest.fixture(autouse=True)
def small_lanes(monkeypatch):
    monkeypatch.setattr(reference, "LANE_DIGITS", 2)
    monkeypatch.setattr(reference, "ROWS_PER_CALL", 8)


def test_data_keyed_cache_answers_a_later_bound_wrong():
    jobs = [Job("zipf01", 0, 40, due=0.0), Job("zipf01", 0, 4000, due=1.0),
            Job("zipf02", 0, 900, due=2.0)]
    exact, ctl32 = {}, {}
    for data, bounds in (("zipf01", [(0, 40), (0, 4000)]), ("zipf02", [(0, 900)])):
        for (lo, hi), (e, c) in reference.sweep_min(data, bounds).items():
            exact[(data, lo, hi)], ctl32[(data, lo, hi)] = e, c
    assert exact[("zipf01", 0, 40)] != exact[("zipf01", 0, 4000)]
    ctl = control.control_answers("data_keyed_cache", jobs, exact, ctl32)
    wrong = [j for j in jobs if ctl[id(j)] != exact[(j.data, j.lower, j.upper)]]
    assert wrong == [jobs[1]]


def test_u32_control_is_the_reference_below_two_to_the_32():
    jobs = [Job("abcdef", 0, 3000)]
    got = reference.sweep_min("abcdef", [(0, 3000)])[(0, 3000)]
    ctl = control.control_answers(
        "u32", jobs, {("abcdef", 0, 3000): got[0]}, {("abcdef", 0, 3000): got[1]}
    )
    assert ctl[id(jobs[0])] == got[1]
