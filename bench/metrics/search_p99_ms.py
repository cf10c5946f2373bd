"""99th percentile of search latency over every search due in the window,
each timed from its due time to its answer on the host.  A shed or lost
search never answered counts as answered at the end of the drain."""
import numpy as np


def read(ctx):
    rec = ctx.rec
    if len(rec.search_due) == 0:
        return None
    done = np.where(np.isnan(rec.search_done), ctx.drain_end, rec.search_done)
    return float(np.percentile(done - rec.search_due, 99)) * 1e3
