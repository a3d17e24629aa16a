"""Device time per trial of the dynamic enumeration kernel on W servers.

The quantity of ``kernel_ms_per_trial.dynamic_enum`` (the summed device
durations of ``dynamic_sojourn_enum`` in the trace's ``XLA Ops``,
milliseconds per trial), read in the cells that list it, whose
configuration names ``n_servers`` = W > 1: there the kernel runs the
lockstep on W servers for every index policy, the online RANK included.
"""

import readers

read = readers.load("kernel_ms_per_trial.dynamic_enum").read
