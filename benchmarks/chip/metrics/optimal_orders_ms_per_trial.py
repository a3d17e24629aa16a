"""Wall time per trial of building OPTIMAL's order array.

The sum of the program's ``prof.optimal.orders`` spans: all N! job
permutations as an ``(N!, N)`` array, before the static op scores them.
Milliseconds per trial; nothing when the program has no such span or
the traffic scores no OPTIMAL.
"""

import program_spans


def read(ctx):
    return program_spans.ms_per_trial(ctx, lambda name: name == "optimal.orders")
