"""A whole run of the four-chip cell ``mesh4.long`` on the CPU at its
traffic's rehearsal sizes, with the miner on a virtual mesh of four CPU
devices (``BMT_FORCE_CPU_DEVICES=4``), so its configuration, traffic and
``mesh_row_balance`` reader stay tested.  Slow (about a minute a run);
run by hand:

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
    env = dict(os.environ, JAX_PLATFORMS="cpu", BMT_FORCE_CPU_DEVICES="4")
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", *args, "--cpu-rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout,
    )
    lines = p.stdout.strip().splitlines()
    last = json.loads(lines[-1]) if lines and lines[-1].startswith("{") else None
    return p, last


@pytest.mark.parametrize("cell,seconds,trace,metrics", [
    ("mesh4.long", 3, 0, {"nonces_per_s", "setup_s"}),
    ("mesh4.long", 4, 1, {"mesh_row_balance"}),
])
def test_mesh_cell_rehearsal(cell, seconds, trace, metrics):
    p, last = run("--workload", cell, "--seed", "3000000021", "--seconds",
                  str(seconds), "--trace", str(trace))
    assert p.returncode == 0, p.stderr[-3000:]
    assert last["correct"] is True
    assert last["device"]["count"] == 4
    # The CPU trace has no device plane, so only the fleet-log metric reads.
    assert set(last["metrics"]) == metrics
    if trace:
        assert 0 < last["metrics"]["mesh_row_balance"]["value"] <= 100
    assert "check not_the_minimum 0 limit 0" in p.stderr
