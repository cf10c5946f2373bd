"""Share of the device's idle time in the traced window during which no
``fdann.*`` span was open on any host thread: the idle the program's spans
cannot explain yet.  Near 100% would mean the spans and the device are not
on one clock."""
from harness import spans


def read(ctx):
    if ctx.events is None:
        return None
    got = spans.idle_unspanned_ns(ctx.events)
    if got is None or got[0] <= 0:
        return None
    idle, unspanned = got
    return 100.0 * unspanned / idle
