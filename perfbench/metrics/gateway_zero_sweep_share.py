"""Share of the gateway's requests in the traced slice that cost no sweep of
their own: answered from the result cache or from solved spans whole, or
coalesced onto a sweep already running; in percent, from the fleet log."""


def read(ctx):
    c = (ctx.fleet or {}).get("counters", {})
    asked = c.get("gateway.requests", 0)
    if asked <= 0:
        return None
    free = sum(c.get(k, 0) for k in
               ("gateway.cache_hits", "gateway.span_hits", "gateway.coalesced"))
    return 100.0 * free / asked
