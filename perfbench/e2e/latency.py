"""Job latency from the client's side: from when a job was due (an open
loop's schedule) to its Result.  A job that got no answer counts as
missing every limit."""

import math


def quantile(ctx, q: float):
    """The ``q`` quantile (nearest rank) of every job's latency."""
    lat = sorted(
        (j.done - j.due) if j.answer is not None else math.inf for j in ctx.jobs
    )
    if not lat:
        return None
    v = lat[max(0, math.ceil(q * len(lat)) - 1)]
    return v if math.isfinite(v) else 1e9
