"""XLA compilations (fresh or loaded from the persistent cache) during the
whole window, from JAX's compile events."""


def read(ctx):
    return float(ctx.compiles)
