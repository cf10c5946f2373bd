"""Mean wall time of a search micro-batch's leading insert-buffer flush in
the traced window: the ``fdann.search.flush`` span, which holds the wait
for the flush lock and the flush itself (ROADMAP S3)."""
from harness import spans


def read(ctx):
    if ctx.events is None:
        return None
    return spans.mean_ms(spans.named(ctx.events, "fdann.search.flush"))
