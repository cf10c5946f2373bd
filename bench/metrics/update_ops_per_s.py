"""Inserts plus deletes acknowledged (and so visible to every later search)
within the window, over the window's seconds."""
import numpy as np


def read(ctx):
    rec = ctx.rec
    if len(rec.update_due) == 0:
        return None
    close = rec.t0 + rec.seconds
    acked = np.sum(~np.isnan(rec.update_ack) & (rec.update_ack <= close))
    return float(acked) / rec.seconds
