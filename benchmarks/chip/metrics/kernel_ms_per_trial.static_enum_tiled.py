"""Device time per trial of the static enumeration kernel where every
call spans combination tiles.

The quantity of ``kernel_ms_per_trial.static_enum`` (the summed device
durations of ``sojourn_enum`` in the trace's ``XLA Ops``, milliseconds
per trial), read in the cells that list it.  There every static call
spans tiles (``paper-n5-m8-stages``: K = 8**5 = 32,768, 32 tiles of 1,024
combinations), so the kernel's order blocks carry Kahan sums across
them.
"""

import readers

read = readers.load("kernel_ms_per_trial.static_enum").read
