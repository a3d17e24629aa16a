"""Share of the traced window in which no operation ran on the device.

1 - (union of the device's operation intervals) / (traced window), in %,
from the profiler trace.
"""


def read(ctx):
    if not ctx.trace.window_s:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s)
