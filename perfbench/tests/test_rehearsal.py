"""Whole runs of the harness on the CPU at the traffic's rehearsal sizes:
the process plumbing, the warm-up, the fleet-log differences, the checks
and the last line.  Slow (tens of seconds a run); run by hand:

    python -m pytest perfbench/tests -q
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def staged(tmp_path_factory):
    """A checkout whose BENCHMARK.json also holds the staged gw.zipf cell
    (``perfbench/staged_gw.zipf.json``), so its files stay tested."""
    d = tmp_path_factory.mktemp("staged")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    extra = json.loads((ROOT / "perfbench" / "staged_gw.zipf.json").read_text())
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        bench[key] += extra[key]
    (d / "BENCHMARK.json").write_text(json.dumps(bench))
    for name in ("perfbench", "bitcoin_miner_tpu"):
        (d / name).symlink_to(ROOT / name)
    return d


def run(*args, cwd=ROOT, rehearse=True, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "perfbench/run.py", *args]
    if rehearse:
        cmd.append("--cpu-rehearsal")
    p = subprocess.run(cmd, cwd=cwd, env=env, capture_output=True, text=True,
                       timeout=timeout)
    lines = p.stdout.strip().splitlines()
    last = json.loads(lines[-1]) if lines and lines[-1].startswith("{") else None
    return p, last


@pytest.mark.parametrize("cell,seconds,metrics", [
    ("ref.long", 3, {"nonces_per_s", "setup_s"}),
    ("gw.zipf", 6, {"job_p95_s", "job_p50_s", "setup_s"}),
])
def test_cell_rehearsal(cell, seconds, metrics, staged):
    p, last = run("--workload", cell, "--seed", "3000000011", "--seconds", str(seconds),
                  cwd=staged)
    assert p.returncode == 0, p.stderr[-3000:]
    assert last["correct"] is True
    assert last["device"]["platform"] == "cpu"
    assert set(last["metrics"]) == metrics
    assert list(last)[-1] == "checks"
    assert "check not_the_minimum 0 limit 0" in p.stderr


def test_traced_rehearsal_reads_the_fleet_log(staged):
    p, last = run("--workload", "gw.zipf", "--seed", "3000000012", "--seconds", "5",
                  "--trace", "1", cwd=staged)
    assert p.returncode == 0, p.stderr[-3000:]
    assert {"chunk_rtt_mean_s.open", "lsp_rtt_mean_s.open",
            "gateway_zero_sweep_share"} <= set(last["metrics"])
    assert last["device"]["window_s"] > 0
    assert "breakdown" in last


@pytest.mark.parametrize("fault", ["half", "alter"])
def test_broken_miner_is_not_correct(fault):
    p, last = run("--workload", "ref.long", "--seed", "3000000013", "--seconds", "3",
                  "--fault", fault)
    assert p.returncode == 0, p.stderr[-3000:]
    assert last["correct"] is False
    assert last["checks"]["not_the_minimum"]["value"] > 0


def test_no_tpu_no_result():
    p, last = run("--workload", "ref.long", "--seed", "3000000014", "--seconds", "3",
                  rehearse=False)
    assert p.returncode != 0
    assert last is None
    assert "not platform tpu" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns(".runs", "__pycache__"))
    p, last = run("--workload", "ref.long", "--seed", "3000000015", "--seconds", "3",
                  cwd=tmp_path, rehearse=False, timeout=120)
    assert p.returncode != 0
    assert last is None
