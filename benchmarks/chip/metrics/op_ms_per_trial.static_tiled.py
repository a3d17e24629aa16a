"""Wall time per trial inside the static sojourn op where every call
spans combination tiles.

The quantity of ``op_ms_per_trial.static`` (the program's
``prof.sojourn_eval.static.<mode>.pallas`` spans, milliseconds per
trial), read in the cells that list it, whose static calls all span
tiles.  Against ``kernel_ms_per_trial.static_enum_tiled`` it shows how
much of the op's time is the host's.
"""

import readers

read = readers.load("op_ms_per_trial.static").read
