"""Share of the traced slice the miner spent min-folding the tiny digit
classes (d <= 7) on the host: the difference of its ``sweep.host_fold_s``
over the slice, from the fleet log, in percent of the slice.  The fold
runs on the pipeline's one dispatch thread, so the share is at most 100%.
None when the miner counts no host fold time."""


def read(ctx):
    fold_s = (ctx.fleet or {}).get("counters", {}).get("sweep.host_fold_s")
    if fold_s is None or ctx.slice_s <= 0:
        return None
    return 100.0 * fold_s / ctx.slice_s
