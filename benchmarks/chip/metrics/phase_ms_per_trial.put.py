"""Wall time per trial of the ops' ``put`` phase on the chip path.

The copies of the inputs to the device, with their conversion to the
kernels' float32. The sum of the program's
``prof.op_phase.<static|dynamic>.pallas.put`` spans, which with the
other three phases tile every ``sojourn_eval`` op call.  Milliseconds
per trial; nothing when the program has no such span.
"""

import program_spans


def read(ctx):
    return program_spans.phase_ms_per_trial(ctx, "put")
