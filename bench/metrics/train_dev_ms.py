"""Device ms per traced round of local training; the programs
``_batched_stage_impl``."""
from harness import xplane

PROGRAMS = ('_batched_stage_impl',)


def read(ctx):
    if ctx.trace is None or not ctx.traced_rounds:
        return None
    t = xplane.module_seconds(ctx.trace)
    found = [t[p] for p in PROGRAMS if p in t]
    return 1e3 * sum(found) / ctx.traced_rounds if found else None
