"""The many-users closed loop (``generators/closed_users.py``): every seed
carries the same multiset of job sizes, and no key is ever sent twice."""

import importlib.util
import json
import sys
from collections import Counter
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

spec = importlib.util.spec_from_file_location(
    "closed_users", HERE / "generators" / "closed_users.py")
gen = importlib.util.module_from_spec(spec)
spec.loader.exec_module(gen)

TRAFFIC = json.loads((HERE / "traffic" / "short_closed.json").read_text())
SEEDS = (1, 3000000031, 2**31 + 12345, 9_000_000_000)


def test_every_seed_carries_the_same_sizes():
    first = gen.uppers(TRAFFIC, SEEDS[0])
    assert len(first) == TRAFFIC["sizes"]
    assert TRAFFIC["upper_min"] <= min(first) < max(first) <= TRAFFIC["upper_max"]
    orders = set()
    for seed in SEEDS:
        sizes = gen.uppers(TRAFFIC, seed)
        assert Counter(sizes) == Counter(first)
        orders.add(tuple(sizes))
    assert len(orders) == len(SEEDS)  # the seed orders them


def test_jobs_cycle_the_shared_sizes():
    sizes = gen.uppers(TRAFFIC, SEEDS[1])
    n = len(sizes)
    jobs = [gen.job(TRAFFIC, SEEDS[1], i) for i in range(3 * n)]
    assert [j.upper for j in jobs] == sizes * 3
    assert all(j.lower == TRAFFIC["lower"] for j in jobs)
    assert all(len(j.data) == TRAFFIC["data_len"] for j in jobs)


def test_no_key_repeats():
    for seed in SEEDS:
        sizes = gen.uppers(TRAFFIC, seed)
        datas = [gen.job(TRAFFIC, seed, i, sizes=sizes).data for i in range(1000)]
        assert len(set(datas)) == len(datas)
        # The same draw from another user is another key too.
        assert gen.job(TRAFFIC, seed, 5, user=0).data != gen.job(TRAFFIC, seed, 5, user=1).data
