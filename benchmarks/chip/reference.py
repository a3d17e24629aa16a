"""Plain reference for the answers of the numerical study.

Expected sojourn time of the successful jobs of one job group, written
straight from the paper's definitions (arXiv 2205.12891, Eqs. 7-9) over
every outcome combination: a combination fixes the checkpoint at which
each job stops, its weight is the product of those stop probabilities,
and it contributes the mean completion time of its successful jobs (0
when none succeeds).  It imports nothing of the scheduler and takes only
the job group's sizes and probabilities, so it shares no table, formula
or code path with the program it checks:

* static orders (OPTIMAL, RANK, RANDOM) through the bilinear form below,
  not the per-combination prefix sums of the fused kernels;
* stage-level index policies (SR, SERPT) by an event-by-event
  single-server simulation, vectorized over combinations, with index
  tables computed here from the textbook definitions.

``dtype`` is the precision every table and sum is held in: float64 for
the reference, ``ml_dtypes.bfloat16`` for the lower-precision control.
"""

from __future__ import annotations

import itertools

import numpy as np

#: Combinations simulated at once by :func:`dynamic_value`.
BLOCK = 1 << 14


def combinations(probs: np.ndarray, lo: int = 0, hi: int | None = None):
    """Stop stages ``(K', N)`` and weights ``(K',)`` of combinations ``lo:hi``.

    Combination ``k`` is the mixed-radix number whose digits are the
    stop stages, job 0 most significant.
    """
    n, m = probs.shape
    k_total = m**n
    hi = k_total if hi is None else min(hi, k_total)
    k = np.arange(lo, hi, dtype=np.int64)
    strides = m ** np.arange(n - 1, -1, -1, dtype=np.int64)
    stops = (k[:, None] // strides[None, :]) % m
    weights = np.prod(probs[np.arange(n)[None, :], stops], axis=1)
    return stops, weights


def rank_order(sizes: np.ndarray, probs: np.ndarray) -> np.ndarray:
    """RANK (paper Eq. 23): ascending E[size] / P(success), stable."""
    rank = (sizes * probs).sum(axis=1) / probs[:, -1]
    return np.argsort(rank, kind="stable")


def all_orders(n: int) -> np.ndarray:
    """Every permutation of ``n`` jobs, ``(n!, n)``: OPTIMAL's search space."""
    return np.array(list(itertools.permutations(range(n))), dtype=np.int64)


def static_values(sizes, probs, orders, dtype=np.float64) -> np.ndarray:
    """E[sojourn of successful jobs] of each static order in ``orders``.

    With ``d[k, j]`` job j's realized size in combination k, ``s[k, i]``
    whether job i succeeds and ``l_k`` the number that do, job i completes
    at the sum of ``d[k, j]`` over the jobs served up to and including it,
    so the expectation is ``sum over (i, j) with pos(j) <= pos(i)`` of
    ``A[i, j] = sum_k w_k s[k, i] d[k, j] / l_k``.
    """
    n, m = sizes.shape
    stops, w = combinations(probs)
    d = sizes[np.arange(n)[None, :], stops].astype(dtype)
    succ = stops == m - 1
    cnt = succ.sum(axis=1)
    wl = np.where(cnt > 0, w / np.maximum(cnt, 1), 0.0).astype(dtype)
    a = (succ.astype(dtype) * wl[:, None]).T @ d  # (N, N)
    pos = np.argsort(np.asarray(orders), axis=1)  # pos[p, j]: position of job j
    out = np.empty(len(pos), dtype)
    for lo in range(0, len(pos), 4096):
        p = pos[lo : lo + 4096]
        before = (p[:, None, :] <= p[:, :, None]).astype(dtype)  # [p, i, j]
        out[lo : lo + 4096] = (before * a[None]).reshape(len(p), -1).sum(axis=1)
    return out


def index_table(sizes: np.ndarray, probs: np.ndarray, policy: str) -> np.ndarray:
    """``idx[i, s]``: job i's priority after surviving ``s`` checkpoints.

    The remaining job is the conditional one: sizes re-based at the
    checkpoint passed, probabilities renormalized over the checkpoints
    ahead.  SERPT is its expected remaining size; SR (Sevcik's smallest
    rank, the Gittins index of the paper's Eq. 2) is the least, over the
    checkpoints ahead, of the expected service until that checkpoint over
    the probability of stopping by it.
    """
    n, m = sizes.shape
    table = np.empty((n, m))
    for i in range(n):
        for s in range(m):
            base = sizes[i, s - 1] if s else 0.0
            x = sizes[i, s:] - base
            p = probs[i, s:] / probs[i, s:].sum()
            if policy == "serpt":
                table[i, s] = np.dot(x, p)
            elif policy == "sr":
                stop_by = np.cumsum(p)
                service = np.cumsum(x * p) + x * (1.0 - stop_by)
                table[i, s] = np.min(service / stop_by)
            else:
                raise ValueError(f"no index policy {policy!r}")
    return table


def dynamic_value(sizes, probs, table, dtype=np.float64) -> float:
    """E[sojourn of successful jobs] under a stage-level index policy.

    One server; at every checkpoint it serves next the unfinished job
    with the least ``table[job, checkpoints passed]``, ties to the lower
    job number, for one stage.  A job that reaches its stop checkpoint
    completes there.
    """
    n, m = sizes.shape
    seg = np.diff(sizes, axis=1, prepend=0.0).astype(dtype)
    table = np.concatenate([table, np.full((n, 1), np.inf)], axis=1).astype(dtype)
    total = 0.0
    for lo in range(0, m**n, BLOCK):
        stops, w = combinations(probs, lo, lo + BLOCK)
        rows = np.arange(len(stops))
        stage = np.zeros(stops.shape, np.int64)
        # Each job's priority now; +inf once it has completed.
        prio = np.broadcast_to(table[:, 0], stops.shape).copy()
        done_at = np.zeros(stops.shape, dtype)
        clock = np.zeros(len(stops), dtype)
        for _ in range(n * m):
            j = np.argmin(prio, axis=1)
            busy = np.isfinite(prio[rows, j])
            st = stage[rows, j]
            clock = np.where(busy, clock + seg[j, st], clock).astype(dtype)
            fin = busy & (st == stops[rows, j])
            done_at[rows[fin], j[fin]] = clock[fin]
            nxt = busy & ~fin
            stage[rows[nxt], j[nxt]] += 1
            r, jb = rows[busy], j[busy]
            prio[r, jb] = table[jb, np.where(fin[busy], m, stage[r, jb])]  # column m: +inf
        succ = stops == m - 1
        cnt = succ.sum(axis=1)
        zero = np.zeros((), dtype)  # no Python scalars beside bfloat16 arrays
        tot = np.where(succ, done_at, zero).sum(axis=1, dtype=dtype)
        mean = np.where(cnt > 0, tot / np.maximum(cnt, 1).astype(dtype), zero)
        total += float((w.astype(dtype) * mean).sum(dtype=dtype))
    return total
