"""Share of the chip's u32 vector peak that the traced slice's hashing
reached: the nonces the miner swept on the device in the slice (its own
``sweep.device_lanes`` counter) times the operations one nonce needs
(``opcount.py``, the least over the cell's device digit classes), over the
peak (``peaks.json``) times the device time of every operation in the
slice.  Counting every operation's time keeps a renamed or split kernel in
the sum; counting the least work per nonce lets the share only under-read.
The slice holds whole jobs, so its lanes and its device time are of the
same dispatches."""


def read(ctx):
    if not ctx.peak or ctx.lanes <= 0 or ctx.trace.get("device_s", 0) <= 0:
        return None
    work = ctx.lanes * ctx.ops_per_nonce
    return 100.0 * work / (ctx.peak["vpu_u32_ops_per_s"] * ctx.trace["device_s"])
