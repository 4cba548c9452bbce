"""Set-up: seconds JAX spent tracing and lowering to MLIR during set-up
(``jax.monitoring`` durations); paid even when the persistent compile
cache hits."""


def read(ctx):
    return ctx.setup_clock["trace"] + ctx.setup_clock["lower"]
