"""Host milliseconds per batch in the serving loop's own steps, over the
traced part of the window: the ``serve.pad``, ``serve.put``,
``serve.dispatch`` and ``serve.fulfil`` phases' counters, differenced.
``None`` where the program has no such counters or the part served no
batch."""

FIELDS = ("pad_s", "put_s", "dispatch_s", "fulfil_s")


def read(ctx):
    if not all(f in ctx.stats1 for f in FIELDS):
        return None
    batches = ctx.stat_delta("batches")
    if batches <= 0:
        return None
    return 1e3 * sum(ctx.stat_delta(f) for f in FIELDS) / batches
