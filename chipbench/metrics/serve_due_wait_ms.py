"""Milliseconds per batch from when a batch became due (full, or its
head's wait over) to the start of its ``serve.pad``, over the traced part
of the window: a due batch waiting for the drain thread (``due_wait_s``,
differenced).  ``None`` where the program has no such counter or the
part served no batch."""


def read(ctx):
    if "due_wait_s" not in ctx.stats1:
        return None
    batches = ctx.stat_delta("batches")
    if batches <= 0:
        return None
    return 1e3 * ctx.stat_delta("due_wait_s") / batches
