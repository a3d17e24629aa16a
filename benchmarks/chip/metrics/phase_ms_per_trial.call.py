"""Wall time per trial of the ops' ``call`` phase on the chip path.

The dispatch of the jitted kernel call, which returns before the device
is done. The sum of the program's
``prof.op_phase.<static|dynamic>.pallas.call`` spans, which with the
other three phases tile every ``sojourn_eval`` op call.  Milliseconds
per trial; nothing when the program has no such span.
"""

import program_spans


def read(ctx):
    return program_spans.phase_ms_per_trial(ctx, "call")
