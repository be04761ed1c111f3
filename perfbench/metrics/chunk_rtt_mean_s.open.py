"""Mean scheduler chunk round trip (assignment to Result) over the traced
slice: the difference of ``hist.chunk_rtt_s``'s sum over the difference of
its count, from the server's fleet log."""


def read(ctx):
    h = (ctx.fleet or {}).get("hists", {}).get("hist.chunk_rtt_s")
    if not h or h[0] <= 0:
        return None
    return h[1] / h[0]
