"""Mean LSP message round trip (send to ack) over the traced slice, on the
server's and the miner's connections: the difference of
``hist.lsp_rtt_s``'s sum over that of its count, from the fleet log."""


def read(ctx):
    h = (ctx.fleet or {}).get("hists", {}).get("hist.lsp_rtt_s")
    if not h or h[0] <= 0:
        return None
    return h[1] / h[0]
