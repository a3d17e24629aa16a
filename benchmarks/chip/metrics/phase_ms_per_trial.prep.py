"""Wall time per trial of the ops' ``prep`` phase on the chip path.

The host NumPy work of each op call and order batch: argument
conversion, strides, CDFs, the permuted tables, and entering the
precision scope. The sum of the program's
``prof.op_phase.<static|dynamic>.pallas.prep`` spans, which with the
other three phases tile every ``sojourn_eval`` op call.  Milliseconds
per trial; nothing when the program has no such span.
"""

import program_spans


def read(ctx):
    return program_spans.phase_ms_per_trial(ctx, "prep")
