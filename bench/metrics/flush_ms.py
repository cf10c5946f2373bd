"""Median wall time of one insert-buffer flush in the window
(SystemStats.flush_latency)."""
import numpy as np


def read(ctx):
    s = ctx.rec.flush_samples
    return float(np.median(s)) * 1e3 if s else None
