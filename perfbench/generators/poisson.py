"""Open loop: independent users send jobs at Poisson arrivals, whatever the
server does; each job is timed from when it was due.

Traffic parameters: ``rate`` (jobs per second), ``data_len``, ``keys`` and
``zipf`` (key popularity: rank r drawn with weight 1 / r**zipf over
``keys`` ranks), ``lower``, and ``upper_min``/``upper_max`` (each job's
upper bound, log-uniform between them).

Every seed gets the same set of gaps, bounds and key ranks, taken at the
distributions' evenly spaced quantiles, and the seed only orders them and
names the keys: runs of different seeds then carry the same work."""

from __future__ import annotations

import asyncio
import math
import random
import time

import keys
from lspjobs import Job, run_job


def _quantiles(n: int):
    return [(i + 0.5) / n for i in range(n)]


def schedule(traffic: dict, seed: int, seconds: float):
    """``[(offset_s, data, lower, upper), ...]`` in due order."""
    rate = traffic["rate"]
    n = max(1, round(rate * seconds))
    rng = random.Random(f"{seed}/poisson")
    gaps = [-math.log(1 - q) / rate for q in _quantiles(n)]
    lo_b, hi_b = math.log(traffic["upper_min"]), math.log(traffic["upper_max"])
    uppers = [round(math.exp(lo_b + (hi_b - lo_b) * q)) for q in _quantiles(n)]
    weights = [1 / r ** traffic["zipf"] for r in range(1, traffic["keys"] + 1)]
    total = sum(weights)
    cdf, acc = [], 0.0
    for w in weights:
        acc += w / total
        cdf.append(acc)
    ranks, r = [], 0
    for q in _quantiles(n):
        while cdf[r] < q:
            r += 1
        ranks.append(r + 1)
    for xs in (gaps, uppers, ranks):
        rng.shuffle(xs)
    scale = seconds / sum(gaps)  # the last arrival is due at ``seconds``
    out, t = [], 0.0
    for gap, upper, rank in zip(gaps, uppers, ranks):
        t += gap * scale
        data = keys.window_key(seed, f"rank{rank}", traffic["data_len"])
        out.append((t, data, traffic["lower"], upper))
    return out


async def drive(ctx) -> list:
    plan = schedule(ctx.traffic, ctx.seed, ctx.seconds)
    t0 = time.monotonic() + 0.01
    ctx.window_start(t0)
    deadline = t0 + ctx.seconds + ctx.job_timeout
    tasks = []
    for off, data, lower, upper in plan:
        job = Job(data, lower, upper, due=t0 + off)
        wait = job.due - time.monotonic()
        if wait > 0:
            await asyncio.sleep(wait)
        tasks.append(asyncio.ensure_future(run_job(ctx.port, job, deadline)))
    return list(await asyncio.gather(*tasks))
