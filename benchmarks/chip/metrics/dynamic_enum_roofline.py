"""Share of its roofline that the dynamic enumeration kernel reaches.

The least time the chip could take for the trials' index-policy work,
over the kernel's device time (``dynamic_sojourn_enum`` in the trace),
in %.  The work is a lower bound of the enumeration itself, from the
shapes: for every policy (SR, SERPT), every outcome combination (masked
tail lanes are not work), every lockstep event (one per stage, N * M)
and every job, one compare in the scan for the earliest finish and one
in the scan for the least index.  The bytes are the job tables read
once, each policy's index table read and its two answers written.  The
least time is the larger of operations over the measured VPU rate and
bytes over HBM bandwidth (``peaks.json``).
"""

KERNEL = "dynamic_sojourn_enum"


def work(n: int, m: int, policies: int) -> tuple[float, float]:
    """(32-bit operations, bytes) of evaluating ``policies`` index
    policies on a group of ``n`` jobs with ``m`` checkpoints each."""
    combos = m**n
    return 2.0 * policies * combos * (n * m) * n, 4.0 * (2 * n * m + policies * (n * m + 2))


def read(ctx):
    seconds = ctx.trace.op_seconds().get(KERNEL)
    policies = sum(a in ("sr", "serpt") for a in ctx.algorithms)
    if not seconds or not policies or not ctx.trials:
        return None
    ops, nbytes = work(ctx.config["n_jobs"], ctx.config["num_stages"], policies)
    t_ops = ops * ctx.trials / ctx.peaks["vpu_ops_per_s"]
    t_bytes = nbytes * ctx.trials / ctx.peaks["hbm_bytes_per_s"]
    ctx.notes["dynamic_enum_roofline"] = "ops" if t_ops >= t_bytes else "bytes"
    return 100.0 * max(t_ops, t_bytes) / seconds
