"""Set-up seconds: data, bootstrap build, set-up merge round, staging and
every warm-up compile, up to the window (host clock)."""


def read(ctx):
    return ctx.setup_s
