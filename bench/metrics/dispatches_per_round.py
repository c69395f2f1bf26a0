"""Device program executions in the traced window per round traced."""
from harness import xplane


def read(ctx):
    if ctx.trace is None or not ctx.traced_rounds:
        return None
    n = xplane.executions(ctx.trace)
    return n / ctx.traced_rounds if n else None
