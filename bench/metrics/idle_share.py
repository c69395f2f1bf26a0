"""Share of the traced window in which no operation ran on the device."""
from harness import xplane


def read(ctx):
    if ctx.trace is None:
        return None
    busy = xplane.busy_s(ctx.trace)
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / xplane.window_s(ctx.trace))
