"""Share of its roofline that the static enumeration kernel reaches.

The least time the chip could take for the trials' static-order work,
over the kernel's device time (``sojourn_enum`` in the trace), in %.
The work is a lower bound of the enumeration itself, from the shapes, so
it reads the same whatever implements it: for every order scored, every
outcome combination (masked tail lanes are not work) and every service
position, one add to the running completion time and one add into the
successful jobs' sum.  OPTIMAL scores all N! orders, RANK and RANDOM one
each.  The bytes are the job tables read once, each order read and its
two answers written.  The least time is the larger of operations over
the measured VPU rate and bytes over HBM bandwidth (``peaks.json``).
"""

import math

KERNEL = "sojourn_enum"


def orders_per_trial(n: int, algorithms) -> int:
    return (math.factorial(n) if "optimal" in algorithms else 0) + sum(
        a in ("rank", "random") for a in algorithms)


def work(n: int, m: int, orders: int) -> tuple[float, float]:
    """(32-bit operations, bytes) of scoring ``orders`` static orders of a
    group of ``n`` jobs with ``m`` checkpoints each."""
    combos = m**n
    return 2.0 * orders * combos * n, 4.0 * (2 * n * m + orders * (n + 2))


def read(ctx):
    seconds = ctx.trace.op_seconds().get(KERNEL)
    orders = orders_per_trial(ctx.config["n_jobs"], ctx.algorithms)
    if not seconds or not orders or not ctx.trials:
        return None
    ops, nbytes = work(ctx.config["n_jobs"], ctx.config["num_stages"], orders)
    t_ops = ops * ctx.trials / ctx.peaks["vpu_ops_per_s"]
    t_bytes = nbytes * ctx.trials / ctx.peaks["hbm_bytes_per_s"]
    ctx.notes["static_enum_roofline"] = "ops" if t_ops >= t_bytes else "bytes"
    return 100.0 * max(t_ops, t_bytes) / seconds
