"""Seconds the StreamingMerge spent in its host-only phases in the traced
window: the ``fdann.merge.stage``, ``.publish`` and ``.retire`` spans
(staging, id/label table rebuild and swap, DeleteList retire), which hold
the GIL and delay the swap."""
from harness import spans


def read(ctx):
    if ctx.events is None or not spans.named(ctx.events, spans.MERGE):
        return None
    return sum(e.dur_ns for name in spans.MERGE_HOST_PHASES
               for e in spans.named(ctx.events, name)) / 1e9
