"""Share of the traced window in which no operation runs on the device:
one minus the union of device-op intervals over the window's length, both
from the profiler's trace of the same window (``bench.window``).  The
profiler lengthens the host's part of a loop period, so this reads above
what an untraced run leaves idle; PERF.md gives both."""


def read(ctx):
    r = ctx.trace
    if r is None or r.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
