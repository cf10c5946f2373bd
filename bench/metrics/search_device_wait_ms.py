"""Longest wait, over the traced window's search micro-batches, from the
start of the ``fdann.search.device`` span (the program's launch) to the
start of that micro-batch's unified search program on the device: the time
a search queues behind work already on the chip."""
from harness import spans


def read(ctx):
    if ctx.events is None:
        return None
    waits = spans.device_waits_ns(ctx.events)
    return max(waits) / 1e6 if waits else None
