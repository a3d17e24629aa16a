"""Share of its roofline that the dynamic enumeration kernel reaches on
W servers.

The least time the chip could take for the trials' index-policy work,
over the kernel's device time (``dynamic_sojourn_enum`` in the trace),
in %, in the cells that list it, whose configuration names
``n_servers`` = W.  The work counts every index policy the traffic
scores there: SR and SERPT, and on W > 1 servers the online RANK too.
Per policy and outcome combination (masked tail lanes are not work) it
is ``dynamic_enum_roofline``'s count, loaded from that reader (per
lockstep event, one per stage, N * M, and per job, one compare in the
scan for the earliest finish and one in the scan for the least index),
plus the min(W, N) seating passes at t = 0, one compare per job each.
The bytes are that reader's.  The least time is the larger of
operations over the measured VPU rate and bytes over HBM bandwidth
(``peaks.json``).
"""

import readers

NAME = "dynamic_enum_servers_roofline"
dynamic = readers.load("dynamic_enum_roofline")


def index_policies(algorithms, n_servers: int) -> int:
    """How many of ``algorithms`` the dynamic kernel scores on
    ``n_servers`` servers: RANK is an index policy only on W > 1."""
    online = ("rank", "sr", "serpt") if n_servers > 1 else ("sr", "serpt")
    return sum(a in online for a in algorithms)


def work(n: int, m: int, policies: int, n_servers: int) -> tuple[float, float]:
    """(32-bit operations, bytes) of evaluating ``policies`` index
    policies on a group of ``n`` jobs with ``m`` checkpoints each on
    ``n_servers`` servers."""
    ops, nbytes = dynamic.work(n, m, policies)
    seating = 1.0 * policies * m**n * min(n_servers, n) * n
    return ops + seating, nbytes


def read(ctx):
    seconds = ctx.trace.op_seconds().get(dynamic.KERNEL)
    w = ctx.config.get("n_servers", 1)
    policies = index_policies(ctx.algorithms, w)
    if not seconds or not policies or not ctx.trials:
        return None
    ops, nbytes = work(ctx.config["n_jobs"], ctx.config["num_stages"], policies, w)
    t_ops = ops * ctx.trials / ctx.peaks["vpu_ops_per_s"]
    t_bytes = nbytes * ctx.trials / ctx.peaks["hbm_bytes_per_s"]
    ctx.notes[NAME] = "ops" if t_ops >= t_bytes else "bytes"
    return 100.0 * max(t_ops, t_bytes) / seconds
