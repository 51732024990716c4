"""Milliseconds per batch in ``serve.fetch``, over the traced part of the
window: the drain thread waiting for the device and copying the output
to the host (``fetch_s``, differenced).  ``None`` where the program has
no such counter or the part served no batch."""


def read(ctx):
    if "fetch_s" not in ctx.stats1:
        return None
    batches = ctx.stat_delta("batches")
    if batches <= 0:
        return None
    return 1e3 * ctx.stat_delta("fetch_s") / batches
