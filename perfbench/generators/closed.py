"""Closed loop: one client sends its next job when the last one is answered.

Traffic parameters: ``data_len`` (bytes of each job's data, fresh from the
seed per job), ``lower`` and ``upper`` (every job's range).  The window
opens with the first Request and closes at the first Result at or after
``seconds``, so every job in it is whole."""

from __future__ import annotations

import time

import keys
from lspjobs import Job, run_job


def job(traffic: dict, seed: int, i: int) -> Job:
    """The ``i``-th job this traffic sends under ``seed``."""
    return Job(keys.window_key(seed, f"job{i}", traffic["data_len"]),
               traffic["lower"], traffic["upper"])


async def drive(ctx) -> list:
    done = []
    t0 = None
    i = 0
    while True:
        j = job(ctx.traffic, ctx.seed, i)
        j.due = time.monotonic()
        if t0 is None:
            t0 = j.due
            ctx.window_start(t0)
        await run_job(ctx.port, j, j.due + ctx.job_timeout)
        done.append(j)
        if j.answer is None or j.done - t0 >= ctx.seconds:
            return done
        ctx.boundary(i + 1, done)
        i += 1
