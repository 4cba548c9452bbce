"""Round program: device-busy milliseconds per round. The union of the
ops' intervals in the window, averaged over the pool's devices, over the
rounds (``poll`` spans) in the window."""


def read(ctx):
    polls = ctx.trace.spans("poll")
    if not polls:
        return None
    return ctx.busy_s() * 1e3 / len(polls)
