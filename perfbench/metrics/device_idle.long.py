"""Share of the traced slice in which no operation ran on the device:
1 - (union of device operation intervals) / slice, in percent."""


def read(ctx):
    if not ctx.trace.get("devices") or ctx.slice_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.slice_s)
