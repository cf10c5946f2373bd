"""Longest interval in the window's searches during which at least one
search was pending and none completed, from the harness's own timestamps
(submission to completion)."""
import numpy as np


def read(ctx):
    rec = ctx.rec
    ok = ~np.isnan(rec.search_done)
    if not ok.any():
        return None
    sub, done = rec.search_submit[ok], rec.search_done[ok]
    # Walk time in order: a stall runs from the later of (last completion,
    # first pending submission) to the next completion.
    events = sorted([(t, 1) for t in sub] + [(t, -1) for t in done])
    pending, since, worst = 0, None, 0.0
    for t, kind in events:
        if kind == 1:
            if pending == 0:
                since = t
            pending += 1
        else:
            worst = max(worst, t - since)
            pending -= 1
            since = t
    return worst * 1e3
