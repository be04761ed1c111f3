"""The trace reduction on events whose answer is known by hand."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import pytest  # noqa: E402

from trace_reduce import op_name, reduce_events  # noqa: E402

EVENTS = [(0, 10, "a"), (5, 20, "b"), (30, 40, "a"), (45, 50, "c")]


def test_busy_is_the_union_of_overlapping_ops():
    r = reduce_events(EVENTS, window=(0, 60))
    assert r["busy_s"] == pytest.approx((20 + 10 + 5) / 1e9)
    assert r["ops"] == pytest.approx({"a": 20e-9, "b": 15e-9, "c": 5e-9})


def test_gaps_are_named_by_their_neighbours():
    r = reduce_events(EVENTS, window=(0, 60))
    assert dict(r["top_gaps"]) == pytest.approx(
        {"b -> a": 10e-9, "a -> c": 5e-9, "c -> <end>": 10e-9}
    )
    assert r["longest_gap_s"] == pytest.approx(10e-9)


def test_window_clips_events_and_adds_the_leading_gap():
    r = reduce_events(EVENTS, window=(12, 42))
    assert r["busy_s"] == pytest.approx((8 + 10) / 1e9)
    assert r["ops"] == pytest.approx({"b": 8e-9, "a": 10e-9})
    r = reduce_events([(20, 30, "x")], window=(0, 40))
    assert dict(r["top_gaps"]) == pytest.approx({"<start> -> x": 20e-9, "x -> <end>": 10e-9})


@pytest.mark.parametrize("raw,name", [
    ("%fusion.3 = u32[8,128]{1,0} fusion(u32[8,128] %p)", "fusion.3"),
    ("%minhash.1 = (s32[1], s32[1]) custom-call(...)", "minhash.1"),
    ("copy.2", "copy.2"),
])
def test_op_names(raw, name):
    assert op_name(raw) == name
