"""k-recall@k of the searches answered in the window against the exact
reference over the set live at each search's submission."""


def read(ctx):
    return ctx.recall
