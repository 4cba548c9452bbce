"""Device: percent of the window in which no op ran, averaged over the
pool's devices."""


def read(ctx):
    return 100.0 * (1.0 - ctx.busy_s() / ctx.window_s())
