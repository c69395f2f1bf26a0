"""Window seconds x 1000 over the rounds completed in the window."""


def read(ctx):
    return ctx.window_s * 1e3 / ctx.completed if ctx.completed else None
