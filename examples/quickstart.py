"""Quickstart: the paper's contribution in 60 seconds.

Builds a small workload of multi-stage jobs with early termination,
compares RANK (paper Eq. 23) against SERPT / SR (Gittins) / RANDOM /
OPTIMAL on the exact expected sojourn time of *successful* jobs, and
replays the worked example of paper §III-A.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core.evaluator import evaluate, evaluate_many, optimal_order
from repro.core.jobs import JobSpec, generate_workload
from repro.core.policies import (
    ensure_cache_dir,
    erpt_values,
    rank_values,
    sr_rank_values,
)
from repro.obs import format_snapshot, get_registry, profiling
from repro.runtime import init_compile_cache


def worked_example():
    """Paper §III-A: two jobs where SR=10, SERPT=9.75, OPTIMAL=9.1."""
    jobs = [
        JobSpec(sizes=np.array([1.0, 10.0]), probs=np.array([0.25, 0.75])),
        JobSpec(sizes=np.array([3.0, 6.0]), probs=np.array([0.6, 0.4])),
    ]
    print("== Paper §III-A worked example ==")
    print(f"  SR (Gittins)      : {evaluate(jobs, 'sr'):.4f}   (paper: 10)")
    print(f"  SERPT             : {evaluate(jobs, 'serpt'):.4f} (paper: 9.75)")
    order, val = optimal_order(jobs)
    print(f"  OPTIMAL {order}   : {val:.4f}  (paper: 9.1)")
    print(f"  RANK values       : {rank_values(jobs)} -> job {np.argmin(rank_values(jobs))} first")


def random_workload():
    rng = np.random.default_rng(0)
    jobs = generate_workload(rng, n_jobs=7, num_stages=3, workload_set=1)
    print("\n== 7 random 3-stage jobs (workload set 1) ==")
    print(f"  rank  R(i) : {np.round(rank_values(jobs), 3)}")
    print(f"  ERPT       : {np.round(erpt_values(jobs), 3)}")
    print(f"  SR rank    : {np.round(sr_rank_values(jobs), 3)}")
    res = evaluate_many(jobs, ("optimal", "rank", "serpt", "sr", "random"), rng)
    print("  expected sojourn of successful jobs:")
    for k, v in sorted(res.items(), key=lambda kv: kv[1]):
        print(f"    {k:8s} {v:.4f}")


if __name__ == "__main__":
    init_compile_cache()
    ensure_cache_dir()  # persist workload tables across invocations
    profiling.enable()  # time the fused evaluator ops + cache tiers
    worked_example()
    random_workload()
    print()
    print(format_snapshot(get_registry().snapshot(), title="profiling"))
