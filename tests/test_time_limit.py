"""The per-test time limit (tests/time_limit.py) holds and names its test.

A generated two-test file runs in a pytest subprocess under xdist: the
first test sleeps past its 2 s ``time_limit``, the second passes.  The
first must fail by name within seconds, with the limit printed, and the
run must go on to the second.
"""

import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path

TESTS = Path(__file__).resolve().parent

GENERATED = textwrap.dedent(
    """
    import time

    import pytest


    @pytest.mark.time_limit(2)
    def test_sleeps_past_its_limit():
        time.sleep(120)


    def test_runs_after_the_overrun():
        pass
    """
)


def test_overrun_fails_by_name_and_the_run_goes_on(tmp_path):
    (tmp_path / "test_generated.py").write_text(GENERATED)
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(TESTS), env.get("PYTHONPATH")) if p
    )
    t0 = time.monotonic()
    p = subprocess.run(
        [
            sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
            "-p", "time_limit", "-p", "xdist", "-n", "1",
            "-c", str(TESTS.parent / "pyproject.toml"),
            "--rootdir", str(tmp_path), str(tmp_path / "test_generated.py"),
        ],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=60,
    )
    elapsed = time.monotonic() - t0
    out = p.stdout + p.stderr
    assert p.returncode == 1, out
    assert "Timeout (0:00:02)!" in out, out
    assert "crashed while running" in out, out
    assert "FAILED test_generated.py::test_sleeps_past_its_limit" in out, out
    assert "1 failed, 1 passed" in out, out
    assert elapsed < 30, (elapsed, out)
