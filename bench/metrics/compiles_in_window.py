"""Programs compiled, or loaded from the persistent cache, inside the
measured window (JAX's monitoring events).  Should read 0."""


def read(ctx):
    return float(ctx.compiles)
