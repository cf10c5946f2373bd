"""Median wall time of one search micro-batch dispatch in the window
(SystemStats.search_latency, host preparation plus device program)."""
import numpy as np


def read(ctx):
    s = ctx.rec.search_samples
    return float(np.median(s)) * 1e3 if s else None
