"""chip_smoke.py refuses every run that is not on a TPU.

The smoke itself runs on the chip (the chip tool); here it must fail, exit
non-zero, and print no result: on the CPU, where the default miner
resolves a host tier, and in a directory holding nothing of the repo.
"""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("where", ["cpu", "alone"])
def test_smoke_fails_without_a_tpu(tmp_path, where):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if where == "alone":
        shutil.copy(REPO / "chip_smoke.py", tmp_path)
        script, cwd = tmp_path / "chip_smoke.py", tmp_path
        env.pop("PYTHONPATH", None)
    else:
        script, cwd = REPO / "chip_smoke.py", REPO
    p = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        timeout=240, cwd=cwd, env=env,
    )
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout
    if where == "cpu":
        # The miner said what it resolved, and the smoke refused it.
        assert 'miner-a: resolved {"tier": "cpu", "platform": "host"' in p.stdout
        assert "not tpu" in p.stderr
