"""Set-up: from the harness's start to the window's first Request (the
server and miner starting, the chip reached, the kernel compiled or loaded
from the cache, the warm-up jobs)."""


def read(ctx):
    return ctx.setup_s
