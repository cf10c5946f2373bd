"""Median, over the traced window's search micro-batches, of the LTI lane's
largest hop count (``max_hops`` on the ``fdann.search.device`` span): the
slowest query sets the trip count of the vmapped beam loop (ROADMAP S6)."""
from harness import spans


def read(ctx):
    if ctx.events is None:
        return None
    return spans.median_stat(ctx.events, "fdann.search.device", "max_hops")
