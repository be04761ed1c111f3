"""A benchmark configuration that names another in ``flags_of`` runs that
deployment's processes: every key the harness (``perfbench/run.py``,
``perfbench/control.py``) reads from a configuration file has to match,
so a change to one file's flags cannot leave two cells on two
deployments without a test failing."""

import json
from pathlib import Path

import pytest

CONFIGS = Path(__file__).resolve().parents[1] / "perfbench" / "configs"

#: What the harness reads from a configuration; ``control`` only up to
#: its first ``:`` (the control's kind, the rest is prose).
RUN_KEYS = ("server_flags", "miner_flags", "rehearsal", "reduced", "control")


def _load(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def _run_value(cfg, key):
    value = cfg.get(key)
    return value.split(":")[0] if key == "control" else value


def _pairs():
    for path in sorted(CONFIGS.glob("*.json")):
        target = json.loads(path.read_text()).get("flags_of")
        if target:
            yield path.stem, target


PAIRS = list(_pairs())


def test_some_configuration_borrows_flags():
    assert PAIRS, "no configuration names another in flags_of"


@pytest.mark.parametrize("key", RUN_KEYS)
@pytest.mark.parametrize("name,target", PAIRS)
def test_flags_of_matches_the_named_configuration(name, target, key):
    cfg, base = _load(name), _load(target)
    assert key in cfg and key in base
    assert _run_value(cfg, key) == _run_value(base, key)
