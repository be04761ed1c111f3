"""Delivered nonces per second: every nonce of every job answered in the
window, over the time from the window's first Request to the Result that
closed it.  A closed loop's window holds whole jobs only."""


def read(ctx):
    done = [j for j in ctx.jobs if j.answer is not None]
    if not done:
        return None
    span = max(j.done for j in done) - min(j.sent for j in ctx.jobs)
    return sum(j.nonces for j in done) / span
