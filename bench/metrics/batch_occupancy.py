"""Mean fill of the micro-batches the scheduler dispatched in the window:
queries served over (batches x batch_queries), from SystemStats deltas."""


def read(ctx):
    b, a = ctx.rec.before, ctx.rec.after
    batches = a.batches - b.batches
    if batches == 0:
        return None
    return 100.0 * (a.searches - b.searches) / (
        batches * ctx.config["batch_queries"])
