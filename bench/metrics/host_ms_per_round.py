"""The run loop (``BatteryRun.poll``): host milliseconds per round. Each
``poll`` span (one round: dispatch, the blocking wait, fold and verdict
on the host) minus the part of it in which a device of the pool ran an
op."""


def read(ctx):
    polls = ctx.trace.spans("poll")
    if not polls:
        return None
    busy = ctx.trace.busy_all()
    host_ns = sum((b - a) - busy.covered(a, b) for a, b in polls)
    return host_ns / len(polls) / 1e6
