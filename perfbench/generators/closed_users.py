"""Closed loop of many users: ``clients`` users, each sending its next job
when its last one is answered, and each job on a fresh LSP connection (the
CMU client's shape, ``lspjobs.run_job``).

Traffic parameters: ``clients``, ``data_len`` (bytes of each job's data,
fresh from the seed per job), ``lower``, and ``upper_min``/``upper_max``
with ``sizes``: the jobs' upper bounds are one sequence that all users
draw from in turn, log-uniform between the two bounds, taken at ``sizes``
evenly spaced quantiles and shuffled from the seed, so every seed carries
the same work.  The window opens with the first Request; a user sends no
job once ``seconds`` have passed, and the window closes at the last Result
of the jobs sent.  An unanswered job ends its user's loop at the run's job
timeout."""

from __future__ import annotations

import asyncio
import itertools
import math
import random
import time
from typing import Optional

import keys
from lspjobs import Job, run_job


def uppers(traffic: dict, seed: int) -> list:
    """The shared sequence of upper bounds under ``seed``."""
    n = traffic["sizes"]
    lo_b, hi_b = math.log(traffic["upper_min"]), math.log(traffic["upper_max"])
    out = [round(math.exp(lo_b + (hi_b - lo_b) * (i + 0.5) / n)) for i in range(n)]
    random.Random(f"{seed}/sizes").shuffle(out)
    return out


def job(traffic: dict, seed: int, i: int, user: Optional[int] = None,
        sizes: Optional[list] = None) -> Job:
    """The ``i``-th job drawn under ``seed``, sent by ``user`` (by default
    the users take turns)."""
    if user is None:
        user = i % traffic["clients"]
    if sizes is None:
        sizes = uppers(traffic, seed)
    data = keys.window_key(seed, f"u{user}/job{i}", traffic["data_len"])
    return Job(data, traffic["lower"], sizes[i % len(sizes)])


async def drive(ctx) -> list:
    sizes = uppers(ctx.traffic, ctx.seed)
    drawn = itertools.count()
    done = []
    t0 = time.monotonic()
    ctx.window_start(t0)

    async def user(c: int) -> None:
        while True:
            j = job(ctx.traffic, ctx.seed, next(drawn), c, sizes)
            j.due = time.monotonic()
            await run_job(ctx.port, j, j.due + ctx.job_timeout)
            done.append(j)
            if j.answer is None or j.done - t0 >= ctx.seconds:
                return

    await asyncio.gather(*(user(c) for c in range(ctx.traffic["clients"])))
    return done
