"""Model operations of the requests completed in the traced window, over
the device's busy time there times the peak for the cell's precision."""


def read(ctx):
    done = ctx.stat_delta("completed")
    peak = ctx.peak_ops()
    if not ctx.trace or done <= 0 or ctx.trace["busy_s"] <= 0 or peak <= 0:
        return None
    return 100.0 * ctx.per_image_ops * done / (ctx.trace["busy_s"] * peak)
