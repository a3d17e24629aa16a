"""Device time per trial of the dynamic (index-policy) enumeration kernel.

The summed device durations of its operations (``dynamic_sojourn_enum`` in the
trace's ``XLA Ops``) in the traced window.  Milliseconds per trial.
"""

KERNEL = "dynamic_sojourn_enum"


def read(ctx):
    seconds = ctx.trace.op_seconds().get(KERNEL)
    if not seconds or not ctx.trials:
        return None
    return seconds / ctx.trials * 1e3
