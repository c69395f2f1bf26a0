"""Model FLOPs of the traced rounds (bench/harness/flops.py: the dense
gate-by-gate count, training and evaluation) over the traced window times
the chips times their published bf16 peak."""
from harness import xplane


def read(ctx):
    if ctx.trace is None or not ctx.traced_rounds or ctx.peak is None:
        return None
    window = xplane.window_s(ctx.trace)
    return 100.0 * ctx.traced_flops / (window * ctx.chips
                                       * ctx.peak["bf16_flops_s"])
