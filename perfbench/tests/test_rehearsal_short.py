"""Whole runs of the many-users cell ``ref.short`` on the CPU at its
traffic's rehearsal sizes, with and without the trace, so its
configuration, generator and ``host_fold_share.short`` reader stay tested.
Slow (about a minute a run); run by hand:

    python -m pytest perfbench/tests -q
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


def run(*args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", *args, "--cpu-rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout,
    )
    lines = p.stdout.strip().splitlines()
    last = json.loads(lines[-1]) if lines and lines[-1].startswith("{") else None
    return p, last


@pytest.mark.parametrize("trace,metrics", [
    (0, {"nonces_per_s", "setup_s"}),
    (1, {"host_fold_share.short"}),
])
def test_short_cell_rehearsal(trace, metrics):
    p, last = run("--workload", "ref.short", "--seed", "3000000041",
                  "--seconds", "5", "--trace", str(trace))
    assert p.returncode == 0, p.stderr[-3000:]
    assert last["correct"] is True
    assert last["device"]["platform"] == "cpu"
    assert last["attempted"] >= 8  # every user sent at least two jobs
    # The CPU trace has no device plane, so only the fleet-log metric reads.
    assert set(last["metrics"]) == metrics
    if trace:
        assert 0 < last["metrics"]["host_fold_share.short"]["value"] <= 100
    for check in ("unanswered", "bad_hash_or_range", "not_the_minimum"):
        assert f"check {check} 0 limit 0" in p.stderr
