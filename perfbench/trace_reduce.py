"""Reduce a JAX profiler trace (``*.xplane.pb``) to device busy time,
time per device operation and the longest idle gaps.

Device planes are those named ``/device:<KIND>:<n>`` (TPU, GPU); their
``XLA Ops`` line holds one event per operation run.  Busy time is the union
of those events' intervals, so overlapping operations count once.  A gap
is the time between two consecutive busy intervals, named after the
operations on either side of it.  Gaps before the first and after the last
operation are idle too and are named ``<start>`` and ``<end>``.

Read it with ``ProfileData`` (jax's own reader); nothing else of jax is
touched, so no backend is started.  Run by hand:

    python perfbench/trace_reduce.py <file.xplane.pb> [window_s]
"""

from __future__ import annotations

import json
import re
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")
_OP_NAME = re.compile(r"^%?([^\s=]+)")


def op_name(event_name: str) -> str:
    """``%fusion.3 = u32[..] fusion(...)`` -> ``fusion.3``."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name


def device_events(path: str) -> Dict[str, List[Tuple[int, int, str]]]:
    """``{device plane: [(start_ns, end_ns, op name), ...]}`` sorted."""
    from jax.profiler import ProfileData

    out: Dict[str, List[Tuple[int, int, str]]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not _DEVICE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        line = lines.get("XLA Ops")
        if line is None:
            continue
        evs = [
            (int(e.start_ns), int(e.start_ns + e.duration_ns), op_name(e.name))
            for e in line.events
        ]
        out[plane.name] = sorted(evs)
    return out


def reduce_events(
    evs: List[Tuple[int, int, str]],
    window: Optional[Tuple[int, int]] = None,
    top: int = 10,
) -> dict:
    """Busy union, per-op time and the longest gaps of one device's events,
    clipped to ``window`` (ns) when given."""
    if window is not None:
        lo, hi = window
        evs = [(max(s, lo), min(e, hi), n) for s, e, n in evs if e > lo and s < hi]
    per_op: Dict[str, float] = defaultdict(float)
    for s, e, n in evs:
        per_op[n] += (e - s) / 1e9
    busy_ns = 0
    gaps: List[Tuple[str, float]] = []
    cur_s = cur_e = None
    cur_name = "<start>"
    first = window[0] if window is not None else None
    for s, e, n in evs:
        if cur_e is None:
            if first is not None and s > first:
                gaps.append((f"<start> -> {n}", (s - first) / 1e9))
            cur_s, cur_e, cur_name = s, e, n
            continue
        if s > cur_e:
            busy_ns += cur_e - cur_s
            gaps.append((f"{cur_name} -> {n}", (s - cur_e) / 1e9))
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
        if e >= cur_e:
            cur_name = n
    if cur_e is not None:
        busy_ns += cur_e - cur_s
        if window is not None and window[1] > cur_e:
            gaps.append((f"{cur_name} -> <end>", (window[1] - cur_e) / 1e9))
    named: Dict[str, float] = defaultdict(float)
    for name, sec in gaps:
        named[name] += sec
    return {
        "busy_s": busy_ns / 1e9,
        "ops": dict(per_op),
        "top_ops": sorted(per_op.items(), key=lambda kv: -kv[1])[:top],
        "top_gaps": sorted(named.items(), key=lambda kv: -kv[1])[:top],
        "longest_gap_s": max((g for _, g in gaps), default=0.0),
        "n_events": len(evs),
    }


def reduce_trace(path: str, window: Optional[Tuple[int, int]] = None) -> dict:
    """:func:`reduce_events` over every device plane, busy time averaged
    over the devices."""
    per_dev = {
        name: reduce_events(evs, window) for name, evs in device_events(path).items()
    }
    if not per_dev:
        return {"devices": 0}
    n = len(per_dev)
    ops: Dict[str, float] = defaultdict(float)
    for r in per_dev.values():
        for k, v in r["ops"].items():
            ops[k] += v
    first = next(iter(per_dev.values()))
    return {
        "devices": n,
        "busy_s": sum(r["busy_s"] for r in per_dev.values()) / n,
        "device_s": sum(ops.values()),
        "ops": dict(ops),
        "top_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:10],
        "top_gaps": first["top_gaps"],
        "n_events": sum(r["n_events"] for r in per_dev.values()),
    }


if __name__ == "__main__":
    r = reduce_trace(sys.argv[1])
    if len(sys.argv) > 2 and r.get("devices"):
        r["idle_share"] = 1 - r["busy_s"] / float(sys.argv[2])
    r.pop("ops", None)
    print(json.dumps(r, indent=1))
