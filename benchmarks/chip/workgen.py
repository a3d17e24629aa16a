"""Job groups of the paper's numerical study, drawn from a seed.

arXiv 2205.12891, Section IV-A2 and Table III: each of N jobs has M
checkpoints; its stage increments come from the workload set's size
distribution and are summed into ascending cumulative sizes, its final
success probability comes from the set's success distribution, and the
rest of the probability mass lies on the early checkpoints (all of it on
the first when M = 2; split by a symmetric Dirichlet when M > 2, which
the paper does not pin down).  The distributions are data, read from a
configuration file of ``configs/``; this module holds no numbers of its
own.

Trial ``t`` of a run with seed ``s`` uses workload set
``sets[t mod len(sets)]`` and draws from the stream ``(JOBS, s, t)``, so
the same seed gives the same job groups and every seed gives every run
the same mix of sets.  RANDOM's order has a stream of its own.
"""

from __future__ import annotations

import numpy as np

JOBS, RANDOM, WARMUP, CHECK = range(4)  # independent stream tags


def stream(tag: int, seed: int, index: int = 0) -> np.random.Generator:
    """The generator of stream ``tag`` for ``seed`` (any integer) and ``index``."""
    return np.random.default_rng([tag, seed % (1 << 64), index])


def _draw(rng: np.random.Generator, dist: dict, size) -> np.ndarray:
    kind = dist["dist"]
    if kind == "uniform":
        return rng.uniform(dist["low"], dist["high"], size)
    if kind == "exponential":
        return rng.exponential(dist["scale"], size)
    if kind == "weibull":
        return dist["scale"] * rng.weibull(dist["shape"], size)
    if kind == "discrete":
        w = np.asarray(dist["weights"], np.float64)
        return rng.choice(np.asarray(dist["values"], np.float64), size=size, p=w / w.sum())
    raise ValueError(f"unknown distribution {kind!r}")


def job_group(config: dict, rng: np.random.Generator, trial: int):
    """``(sizes, probs)``, both ``(N, M)`` float64, of one job group.

    ``sizes[i]`` ascends to job i's full (successful) size; ``probs[i]``
    is its distribution over the checkpoints, the last entry being the
    success probability.
    """
    n, m = config["n_jobs"], config["num_stages"]
    ws = config["workload_sets"][trial % len(config["workload_sets"])]
    inc = np.maximum(_draw(rng, ws["stage_increment"], (n, m)), config["min_increment"])
    sizes = np.cumsum(inc, axis=1)
    p_success = _draw(rng, ws["success_prob"], n)
    if m == 1:
        return sizes, np.ones((n, 1))
    early = rng.dirichlet(np.ones(m - 1), size=n) if m > 2 else np.ones((n, 1))
    probs = np.concatenate([(1.0 - p_success)[:, None] * early, p_success[:, None]], axis=1)
    return sizes, probs


def trial_group(config: dict, seed: int, trial: int):
    """Job group of trial ``trial`` of a run with seed ``seed``."""
    return job_group(config, stream(JOBS, seed, trial), trial)
