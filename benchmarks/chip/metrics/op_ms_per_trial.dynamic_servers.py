"""Wall time per trial inside the dynamic sojourn op on W servers.

The quantity of ``op_ms_per_trial.dynamic`` (the program's
``prof.sojourn_eval.dynamic.<mode>.pallas`` spans, milliseconds per
trial), read in the cells that list it, whose configuration names
``n_servers`` = W > 1, so that every policy scored goes through the
dynamic op.  Against ``kernel_ms_per_trial.dynamic_enum_servers`` it
shows how much of the op's time is the host's.
"""

import readers

read = readers.load("op_ms_per_trial.dynamic").read
