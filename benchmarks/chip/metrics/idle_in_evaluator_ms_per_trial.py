"""Device idle time per trial in the evaluator, outside the ops.

The first device's idle time in the traced window that lies inside the
program's ``prof.evaluate_many`` spans on the trace and outside every
``prof.sojourn_eval.*`` span: the evaluator, the policies' tables, the
workload cache and OPTIMAL's orders.  The idle time outside
``prof.evaluate_many`` is the harness's own: ``device_idle_pct`` less
this metric and ``idle_in_ops_ms_per_trial``.  Milliseconds per trial;
nothing when the trace holds no ``prof.evaluate_many`` span.
"""

import program_spans


def read(ctx):
    return program_spans.idle_ms_per_trial(ctx, "evaluate_many", outside="sojourn_eval.")
