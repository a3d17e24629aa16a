"""Device idle time per trial while a sojourn op call runs.

The first device's idle time in the traced window that lies inside the
union of the program's ``prof.sojourn_eval.*`` spans on the trace: the
ops' host preparation, copies, dispatch and sync, from the profiler
trace.  Milliseconds per trial; nothing when the trace holds no such span.
"""

import program_spans


def read(ctx):
    return program_spans.idle_ms_per_trial(ctx, "sojourn_eval.")
