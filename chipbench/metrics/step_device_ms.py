"""Device busy time per batch served in the traced window."""


def read(ctx):
    batches = ctx.stat_delta("batches")
    if not ctx.trace or batches <= 0 or ctx.trace["busy_s"] <= 0:
        return None
    return 1e3 * ctx.trace["busy_s"] / batches
