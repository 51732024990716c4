"""Mean time a request waited in the server's queue before its batch ran,
over the window (the server's cumulative counters, differenced)."""


def read(ctx):
    completed = ctx.stat_delta("completed")
    if completed <= 0:
        return None
    return 1e3 * ctx.stat_delta("wait_sum") / completed
