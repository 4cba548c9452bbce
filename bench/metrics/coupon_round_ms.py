"""Test kernels: wall milliseconds of a round that runs a coupon test
(the harness's ``poll`` around it, host clock), averaged over every such
round of the window outside the traced block. Coupon's scan takes one
device loop step per digit, so its rounds are too long to trace; on
four chips a lock-step round lasts as long as its slowest worker, so
the straggler of these rounds is in this number too."""


def read(ctx):
    times = [s for families, s in ctx.rounds if "coupon" in families]
    return 1e3 * sum(times) / len(times) if times else None
