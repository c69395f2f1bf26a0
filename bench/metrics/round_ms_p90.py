"""90th percentile of every round's wall time in the window (host clock,
from the call of the round to ``block_until_ready`` on the global model)."""
import statistics


def read(ctx):
    if len(ctx.round_s) < 10:
        return None
    return statistics.quantiles(ctx.round_s, n=10)[8] * 1e3
