"""Device time per trial of the static enumeration kernel.

The summed device durations of its operations (``sojourn_enum`` in the
trace's ``XLA Ops``) in the traced window.  Milliseconds per trial.
"""

KERNEL = "sojourn_enum"


def read(ctx):
    seconds = ctx.trace.op_seconds().get(KERNEL)
    if not seconds or not ctx.trials:
        return None
    return seconds / ctx.trials * 1e3
