"""Seconds of the StreamingMerge the window held, inside the merge thread
(SystemStats.merge_seconds)."""


def read(ctx):
    b, a = ctx.rec.before, ctx.rec.after
    if a.merges == b.merges:
        return None
    return a.merge_seconds - b.merge_seconds
