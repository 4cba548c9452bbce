"""Set-up: seconds in XLA's backend compile during set-up
(``jax.monitoring``), a load from the persistent compile cache included."""


def read(ctx):
    return ctx.setup_clock["compile"]
