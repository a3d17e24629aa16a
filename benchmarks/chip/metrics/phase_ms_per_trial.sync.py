"""Wall time per trial of the ops' ``sync`` phase on the chip path.

The wait for the device and the copy of the answers back to NumPy. The
sum of the program's ``prof.op_phase.<static|dynamic>.pallas.sync``
spans, which with the other three phases tile every ``sojourn_eval`` op
call.  Milliseconds per trial; nothing when the program has no such
span.
"""

import program_spans


def read(ctx):
    return program_spans.phase_ms_per_trial(ctx, "sync")
