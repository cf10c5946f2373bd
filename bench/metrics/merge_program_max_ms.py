"""Device time of the longest single program the StreamingMerge launched in
the traced window: programs whose every launch lies on the merge thread
inside its ``fdann.merge`` span."""
from harness import spans


def read(ctx):
    if ctx.events is None:
        return None
    runs = spans.merge_program_ms(ctx.events)
    return max(runs) if runs else None
