"""Find the knee of an open-loop cell once: one set-up, then windows at
rising offered rates, each on a seed of its own.

    python3 perfbench/knee.py --workload gw.zipf --rates 6,8,10,12,14 --seconds 20

For each rate it prints one JSON line: jobs offered and answered, the
latency median and 95th percentile, and the backlog's growth (mean latency
of the last third of the arrivals over that of the first third).  The knee
is the highest rate whose backlog does not grow.  Not run by the driver;
``traffic/<mix>.json`` records the rate chosen from it.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import sys
import types
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as harness  # noqa: E402


def step_stats(rate: float, jobs: list) -> dict:
    lat = [(j.done - j.due) if j.answer else math.inf for j in jobs]
    srt = sorted(lat)
    third = max(1, len(lat) // 3)
    first = sum(x for x in lat[:third] if math.isfinite(x)) / third
    last = sum(x for x in lat[-third:] if math.isfinite(x)) / third
    q = lambda p: srt[max(0, math.ceil(p * len(srt)) - 1)]  # noqa: E731
    return {
        "rate": rate, "offered": len(jobs),
        "answered": sum(1 for j in jobs if j.answer),
        "p50_s": q(0.5), "p95_s": q(0.95) if math.isfinite(q(0.95)) else None,
        "growth": last / first if first > 0 else None,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=4100000000)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)
    run = harness.Run(types.SimpleNamespace(
        workload=args.workload, seed=args.seed, seconds=args.seconds, trace=0,
        cpu_rehearsal=args.cpu_rehearsal, fault=None,
    ))
    try:
        run.start_server()
        run.start_miner()
        asyncio.run(run.warm_up())
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            run.cell.traffic["rate"] = rate
            run.args.seed = args.seed + k
            print(json.dumps(step_stats(rate, run.window())), flush=True)
    except harness.Failed as e:
        print(f"knee sweep FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        run.stop_all()
    return 0


if __name__ == "__main__":
    sys.exit(main())
