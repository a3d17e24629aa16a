"""Share of its roofline that the static enumeration kernel reaches where
every call spans combination tiles.

The least time the chip could take for the trials' static-order work,
over the kernel's device time (``sojourn_enum`` in the trace), in %, in
the cells that list it, whose static calls all span tiles.  The work is
``static_enum_roofline``'s count from the shapes, loaded from that
reader: per order scored, outcome combination and service position, two
adds; the job tables read once, each order read and its two answers
written.  The decode of the stage axis is not counted, at M = 8 or any
other.  The least time is the larger of operations over the measured
VPU rate and bytes over HBM bandwidth (``peaks.json``).
"""

import readers

NAME = "static_enum_tiled_roofline"
static = readers.load("static_enum_roofline")


def read(ctx):
    seconds = ctx.trace.op_seconds().get(static.KERNEL)
    orders = static.orders_per_trial(ctx.config["n_jobs"], ctx.algorithms)
    if not seconds or not orders or not ctx.trials:
        return None
    ops, nbytes = static.work(ctx.config["n_jobs"], ctx.config["num_stages"], orders)
    t_ops = ops * ctx.trials / ctx.peaks["vpu_ops_per_s"]
    t_bytes = nbytes * ctx.trials / ctx.peaks["hbm_bytes_per_s"]
    ctx.notes[NAME] = "ops" if t_ops >= t_bytes else "bytes"
    return 100.0 * max(t_ops, t_bytes) / seconds
