"""Device time of one unified search program (the whole fan-out of one
micro-batch), mean over its executions in the traced window."""
from harness import trace as tr


def read(ctx):
    if ctx.events is None:
        return None
    runs = [e.dur_ns for e in tr.module_events(ctx.events)
            if "unified_search" in e.name]
    return sum(runs) / len(runs) / 1e6 if runs else None
