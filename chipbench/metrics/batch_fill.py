"""Mean share of the bucket's batch that real requests filled, over the
window (the server's cumulative counters, differenced)."""


def read(ctx):
    batches = ctx.stat_delta("batches")
    if batches <= 0:
        return None
    return 100.0 * ctx.stat_delta("fill_sum") / batches
