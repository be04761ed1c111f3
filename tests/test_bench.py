"""Driver-artifact contract test: `python bench.py` must always emit
exactly one parseable JSON line on stdout with the fields the driver and
judge read (BENCH_r{N}.json).  Round 1 lost its entire perf artifact to an
unguarded backend init; this pins the hardened contract.
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def run_bench(*flags, env=None, timeout=560):
    full_env = None
    if env:
        import os

        full_env = {**os.environ, **env}
    return subprocess.run(
        [sys.executable, str(REPO / "bench.py"), *flags],
        capture_output=True,
        text=True,
        timeout=timeout,
        cwd=REPO,
        env=full_env,
    )


def test_sharded_devices_mode_on_virtual_mesh():
    """--devices N must run the sharded sweep on a virtual CPU mesh when
    there aren't N real chips, and report per-device + overlap stats."""
    p = run_bench("--devices", "2", "--cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert len(lines) == 1, lines
    out = json.loads(lines[0])
    assert out["metric"] == "nonces_per_sec_total_sharded"
    assert out["devices"] == 2
    assert out["value"] > 0
    # value and per_device are rounded independently from the raw rate.
    assert abs(out["per_device"] - out["value"] / 2) <= 1
    assert out["dispatches"] >= 1
    assert "fetch_wait_seconds" in out


def test_hung_backend_init_still_emits_json():
    """If backend init or a compile hangs, the watchdog must still land an
    error JSON line instead of hanging forever (VERDICT r3 weak-item 4)."""
    p = run_bench(
        "--cpu",
        env={"BENCH_WATCHDOG_SECS": "2", "BENCH_SIMULATE_WEDGE": "60"},
        timeout=30,
    )
    assert p.returncode == 2, (p.returncode, p.stderr[-500:])
    lines = p.stdout.strip().splitlines()
    assert len(lines) == 1
    out = json.loads(lines[0])
    assert "hung" in out["error"]


def test_sieve_compare_fast_leg():
    """``--sieve-compare --fast`` (ISSUE 13): the tier-1 correctness leg
    of the sieve-vs-baseline comparison — both kernels oracle-gated on a
    digit-boundary range, the interpret-mode pallas sieve included, and
    the JSON honest about which kernel auto_tune keeps: a losing sieve
    must demonstrably keep the baseline."""
    p = run_bench("--sieve-compare", "--fast", "--cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert len(lines) == 1, lines
    out = json.loads(lines[0])
    assert out["metric"] == "sieve_compare"
    assert out["bitexact"] is True
    assert out["interpret_pallas_sieve_bitexact"] is True
    assert out["baseline_nps"] > 0 and out["sieve_nps"] > 0
    assert out["fast"] is True
    # The honesty contract: on a shape where the sieve leg loses, the
    # auto_tune rung must keep the baseline kernel (and vice versa the
    # sieve default may only claim a shape where it does not lose).
    if out["ratio"] < 1.0:
        assert out["kept_kernel"] == "baseline"
    assert out["auto_tune_sieve"] == (out["kept_kernel"] == "sieve")


def test_factor_compare_fast_leg():
    """``--factor-compare --fast`` (ISSUE 14): the tier-1 correctness leg
    of the factored-vs-baseline comparison — both kernels oracle-gated on
    a digit-boundary range, the interpret-mode pallas factored kernel
    (plain and sieve-composed) included, and the JSON honest about which
    kernel auto_tune keeps (BENCH_pr14.json is the full-speed artifact:
    the factored xla kernel wins 2.7x on this host, so auto_tune keeps
    it there)."""
    p = run_bench("--factor-compare", "--fast", "--cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert len(lines) == 1, lines
    out = json.loads(lines[0])
    assert out["metric"] == "factor_compare"
    assert out["bitexact"] is True
    assert out["interpret_pallas_factored_bitexact"] is True
    assert out["baseline_nps"] > 0 and out["factored_nps"] > 0
    assert out["fast"] is True
    # The honesty contract here is SELF-consistency: the JSON must record
    # exactly what auto_tune picks for this backend.  (Unlike the sieve
    # test, no ratio→kept coupling: the xla factored rung is calibrated
    # on the FULL-SPEED same-seed pair — BENCH_pr14.json, 2.76× — and the
    # --fast leg's tiny window under tier-1 load is a correctness gate,
    # not a measurement; asserting on its noisy ratio would flake.)
    assert out["auto_tune_factored"] == (out["kept_kernel"] == "factored")
    assert out["kept_kernel"] in ("baseline", "factored")


def test_hot_compare_fast_leg():
    """``--hot-compare --fast`` (ISSUE 16): the tier-1 correctness leg
    of the persistent-vs-per-chunk dispatch comparison — both disciplines
    oracle-gated on a digit-boundary range, the interpret-mode pallas hot
    plane (plain and sieve-composed, threshold device-carried) included,
    and the JSON honest about which dispatch auto_tune keeps
    (BENCH_pr16.json is the full-speed artifact)."""
    p = run_bench("--hot-compare", "--fast", "--cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert len(lines) == 1, lines
    out = json.loads(lines[0])
    assert out["metric"] == "hot_compare"
    assert out["bitexact"] is True
    assert out["interpret_pallas_hot_bitexact"] is True
    assert out["perchunk_nps"] > 0 and out["hot_nps"] > 0
    assert out["fast"] is True
    # The honesty contract here is SELF-consistency: the JSON must record
    # exactly what auto_tune picks for this backend.  (No ratio→kept
    # coupling: the hot rung is calibrated on the FULL-SPEED same-seed
    # pair — BENCH_pr16.json — and the --fast leg's tiny window under
    # tier-1 load is a correctness gate, not a measurement; asserting on
    # its noisy ratio would flake.)
    assert out["auto_tune_hot"] == (out["kept_kernel"] == "hot")
    assert out["kept_kernel"] in ("per-chunk", "hot")


def test_tier_compare_fast_leg():
    """``--tier-compare --fast`` (ISSUE 20): the tier-1 correctness leg
    of the heterogeneous-plane comparison — the blake2b64 device tier and
    the cpu tier both oracle-gated on digit-boundary ranges (long AND
    sub-block-tail payload shapes) before the tiny timed windows, with
    the JSON honest about the platform, the pallas rung probe, and what
    auto_tune keeps for the family (BENCH_pr20.json is the full-speed
    same-seed artifact; the --fast ratio is load-noisy, so no ratio
    assertion here)."""
    p = run_bench("--tier-compare", "--workload", "blake2b64", "--fast", "--cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert len(lines) == 1, lines
    out = json.loads(lines[0])
    assert out["metric"] == "tier_compare"
    assert out["workload"] == "blake2b64"
    assert out["device_tier"] == "xla"
    assert out["bitexact"] is True
    assert out["device_nps"] > 0 and out["cpu_nps"] > 0
    assert out["short_device_nps"] > 0 and out["short_cpu_nps"] > 0
    assert out["fast"] is True
    # Honesty fields: the pallas rung must be reported as probed (null
    # off-TPU/GPU — never silently assumed), and kept_kernel must record
    # exactly what auto_tune picks for the blake2b family on this host.
    assert "pallas_platform" in out
    assert out["auto_tune_factored"] == ("factored" in out["kept_kernel"])
    assert out["auto_tune_hot"] == ("hot" in out["kept_kernel"])


@pytest.mark.parametrize("flags", [(), ("--devices", "2")])
def test_no_tpu_without_cpu_flag_is_an_error(flags):
    """Without ``--cpu`` the bench runs on the TPU or fails: on a host where
    jax finds no TPU it prints one error JSON line and exits non-zero — it
    never benches the CPU (or a virtual CPU mesh) under the TPU's name."""
    p = run_bench(*flags, env={"JAX_PLATFORMS": "cpu"}, timeout=120)
    assert p.returncode != 0
    lines = p.stdout.strip().splitlines()
    assert len(lines) == 1, lines
    out = json.loads(lines[0])
    assert "no TPU" in out["error"]
    assert "value" not in out


def test_cpu_bench_emits_one_valid_json_line():
    p = run_bench("--cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert len(lines) == 1, f"stdout must be exactly one JSON line: {lines}"
    out = json.loads(lines[0])
    assert out["metric"] == "nonces_per_sec_per_chip"
    assert out["unit"] == "nonces/s"
    assert out["value"] > 0
    assert out["vs_baseline"] == round(out["value"] / 1e9, 4)
    # Attribution fields (VERDICT round 1: numbers must be attributable).
    assert out["platform"] == "cpu"
    assert out["backend"] in ("native", "xla")
    assert "device_kind" in out
