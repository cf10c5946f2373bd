"""Share of the traced window in which no operation ran on the device:
1 - (union of device operation intervals) / window, averaged over chips."""
from harness import trace as tr


def read(ctx):
    if ctx.events is None:
        return None
    lo, hi = ctx.rec.trace_span
    busy = tr.busy_seconds(ctx.events)
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / (hi - lo))
