"""Peak device memory in use (``peak_bytes_in_use``) after the window, in
GiB, on the fullest chip."""


def read(ctx):
    return ctx.memory_peak_bytes / 2 ** 30
