"""Programs compiled or loaded from the persistent cache while the window
was open (``jax.monitoring`` events); set-up warms every shape, so it
should read 0. Moves ``tbt_p99_ms``: a compile stalls the loop, and
every decoding stream with it."""


def read(ctx):
    return ctx.compiles.compiles + ctx.compiles.cache_loads
