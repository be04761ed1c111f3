"""The control of a cell's comparison: the plain reference put in the
program's place with one of the configuration's guarantees broken (its
``control`` key), on the jobs a run of that cell would check.  Every seed
has to come out not correct, or the comparison could not see the fault.

    python3 perfbench/control.py --workload ref.long --seeds 1,2,3 --jobs 8
    python3 perfbench/control.py --workload gw.zipf --seeds 1,2,3

``--jobs`` is how many jobs a closed loop's window completes; an open
loop's window is its schedule.  Needs a device for jax (the chip); the
benchmark's own runs never run it.  Prints one JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent)]

import run as harness  # noqa: E402


def window_jobs(cell, seed: int, seconds: float, n_jobs: int) -> list:
    gen = harness.load_module(HERE / "generators" / f"{cell.traffic['generator']}.py")
    if hasattr(gen, "schedule"):
        from lspjobs import Job

        return [Job(d, lo, hi, due=t)
                for t, d, lo, hi in gen.schedule(cell.traffic, seed, seconds)]
    return [gen.job(cell.traffic, seed, i) for i in range(n_jobs)]


def control_answers(kind: str, sample: list, exact: dict, ctl32: dict) -> dict:
    """The control's answer for each sampled job."""
    if kind == "u32":
        return {id(j): ctl32[(j.data, j.lower, j.upper)] for j in sample}
    if kind == "data_keyed_cache":
        first = {}
        for j in sorted(sample, key=lambda j: j.due):
            first.setdefault(j.data, exact[(j.data, j.lower, j.upper)])
        return {id(j): first[j.data] for j in sample}
    raise ValueError(f"unknown control {kind!r}")


def one_seed(cell, seed: int, seconds: float, n_jobs: int) -> dict:
    import reference

    jobs = window_jobs(cell, seed, seconds, n_jobs)
    # The run checks a sample drawn from its answered jobs; a sound program
    # answers every job with the exact answer, so sample from those.
    for j in jobs:
        j.answer = (0, 0)
    sample = harness.sample_jobs(cell.traffic["check"], seed, jobs)
    want = {}
    for j in sample:
        want.setdefault(j.data, set()).add((j.lower, j.upper))
    t = time.monotonic()
    exact, ctl32 = {}, {}
    for data, bounds in want.items():
        for (lo, hi), (e, c) in reference.sweep_min(data, sorted(bounds)).items():
            exact[(data, lo, hi)] = e
            ctl32[(data, lo, hi)] = c
    ctl = control_answers(cell.config["control"].split(":")[0], sample, exact, ctl32)
    wrong = sum(1 for j in sample if ctl[id(j)] != exact[(j.data, j.lower, j.upper)])
    return {"workload": cell.name, "seed": seed, "control": cell.config["control"].split(":")[0],
            "checked": len(sample), "not_the_minimum": wrong, "limit": 0,
            "correct": wrong == 0, "reference_s": time.monotonic() - t}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--jobs", type=int, default=8)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="the benchmark's own tests: the traffic's rehearsal sizes")
    args = ap.parse_args(argv)
    bench = harness.load_json(HERE.parent / "BENCHMARK.json")
    cell = harness.Cell(args.workload, rehearsal=args.cpu_rehearsal)
    seconds = args.seconds or bench["run_seconds"]
    import reference

    reference.use_device(HERE.parent)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(one_seed(cell, seed, seconds, args.jobs)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
