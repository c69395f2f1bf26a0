"""Device ms per traced round of the secure exchange and secagg masking;
the programs ``_secure_stage_impl``, ``_ring_send_y_impl``,
``secagg_mask_stream``."""
from harness import xplane

PROGRAMS = ('_secure_stage_impl', '_ring_send_y_impl', 'secagg_mask_stream')


def read(ctx):
    if ctx.trace is None or not ctx.traced_rounds:
        return None
    t = xplane.module_seconds(ctx.trace)
    found = [t[p] for p in PROGRAMS if p in t]
    return 1e3 * sum(found) / ctx.traced_rounds if found else None
