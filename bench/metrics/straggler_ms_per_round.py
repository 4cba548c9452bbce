"""Round program across chips: per round (``poll`` span), the slowest
device's busy time minus the mean busy time of the pool's devices, in
milliseconds, averaged over the rounds."""


def read(ctx):
    ids = ctx.device_ids()
    polls = ctx.trace.spans("poll")
    if len(ids) < 2 or not polls:
        return None
    tot = 0
    for a, b in polls:
        per = [ctx.busy(d).covered(a, b) for d in ids]
        tot += max(per) - sum(per) / len(per)
    return tot / len(polls) / 1e6
