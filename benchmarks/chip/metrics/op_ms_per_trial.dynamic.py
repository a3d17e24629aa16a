"""Wall time per trial inside the dynamic sojourn op on the chip path.

The sum of the program's ``prof.sojourn_eval.dynamic.<mode>.pallas`` spans,
each around one op call whose numpy conversion waits for the device:
host preparation, copies, dispatch, kernel and sync.  Milliseconds per
trial; nothing when the op did not run on the Pallas path.
"""

PREFIX = "sojourn_eval.dynamic."


def read(ctx):
    spans = [s for name, s in ctx.spans.items()
             if name.startswith(PREFIX) and name.endswith(".pallas")]
    if not spans or not ctx.trials:
        return None
    return sum(spans) / ctx.trials * 1e3
