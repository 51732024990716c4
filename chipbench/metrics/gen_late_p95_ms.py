"""95th percentile of how late the open-loop generator sent its requests."""

from chipbench import loadgen


def read(ctx):
    if ctx.lateness_s is None or ctx.lateness_s.size == 0:
        return None
    return 1e3 * loadgen.quantile(ctx.lateness_s, 0.95)
