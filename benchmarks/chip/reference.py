"""Plain reference for the answers of the numerical study.

Expected sojourn time of the successful jobs of one job group, written
straight from the paper's definitions (arXiv 2205.12891, Eqs. 7-9) over
every outcome combination: a combination fixes the checkpoint at which
each job stops, its weight is the product of those stop probabilities,
and it contributes the mean completion time of its successful jobs (0
when none succeeds).  It imports nothing of the scheduler and takes only
the job group's sizes and probabilities, so it shares no table, formula
or code path with the program it checks:

* static orders (OPTIMAL, RANK, RANDOM) on one server through the
  bilinear form below, not the per-combination prefix sums of the fused
  kernels;
* stage-level index policies (SR, SERPT, and the online conditional RANK
  of the paper's Section V) by an event-by-event simulation on
  ``n_servers`` identical servers, vectorized over combinations, with
  index tables computed here from the textbook definitions.

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
    the probability of stopping by it; RANK (the online RANK of Section
    V, Eq. 23 of the remaining job) is its expected remaining size over
    its probability of success, ``+inf`` where that probability is 0.
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
            elif policy == "rank":
                table[i, s] = np.dot(x, p) / p[-1] if p[-1] > 0.0 else np.inf
            else:
                raise ValueError(f"no index policy {policy!r}")
    return table


def _pair_ranks(table: np.ndarray) -> np.ndarray:
    """``rank[i, s]``: the place of job i's index after ``s`` checkpoints
    among all ``N * M`` (job, checkpoints passed) pairs, ascending, ties to
    the lower job number.  A job's pairs never meet in one queue, so the
    queued job with the least rank is the one with the least index."""
    n, m = table.shape
    by_index = np.lexsort((np.repeat(np.arange(n), m), table.astype(np.float64).ravel()))
    rank = np.empty(n * m, np.int64)
    rank[by_index] = np.arange(n * m)
    return rank.reshape(n, m)


def _served(stops, events, rank, seg, n_servers, dtype) -> np.ndarray:
    """Completion time ``(K', N)`` of every job in each of the combinations
    ``stops``, which come sorted by their count of ``events``, most first.

    The queue and the servers hold (job, checkpoints passed) pairs by
    their rank.  Row ``n`` of the per-job arrays is a spare that an idle
    server and an empty queue point at, so no step needs a mask: rank
    ``p`` is no pair, takes forever to serve and is never queued.
    """
    b, n = stops.shape
    m = rank.shape[1]
    p = n * m
    small = np.min_scalar_type(p)
    pair = np.argsort(rank.ravel())  # pair[r]: flat (job, stage) of rank r
    stage = pair % m
    job_row = np.append(pair // m, n) * b  # the queue row of each rank's job
    seg_of = np.append(seg.ravel()[pair], np.inf).astype(dtype)
    next_of = np.append(np.where(stage < m - 1, rank.ravel()[np.minimum(pair + 1, p - 1)], p),
                        p).astype(small)
    rows = np.arange(b)
    queue = np.empty((n + 1, b), small)  # each job's rank while queued, else p
    queue[:n] = rank[:, :1]
    queue[n] = p
    last = np.empty((n + 1, b), small)  # the rank at which each job stops
    last[:n] = rank.ravel().take(stops + np.arange(n) * m).T
    last[n] = p
    done = np.zeros((n + 1, b), dtype)
    qf, lf, df = queue.reshape(-1), last.reshape(-1), done.reshape(-1)
    w = min(n_servers, n)
    srv_rank = np.full((w, b), p, small)
    srv_until = np.full((w, b), np.inf, dtype)
    rf, uf = srv_rank.reshape(-1), srv_until.reshape(-1)

    def seat(a, clock):
        """The queued job with the least index, off the queue: its rank
        and the time its stage will end."""
        r = queue[:, :a].min(axis=0)
        qf[job_row.take(r) + rows[:a]] = p
        return r, clock + seg_of.take(r)

    zero = np.zeros(b, dtype)
    for s in range(w):
        srv_rank[s], srv_until[s] = seat(b, zero)
    # After event e only the first ``active[e]`` rows have events left.
    active = b - np.searchsorted(events[::-1], np.arange(events[0]), side="right")
    for a in active:
        # The running job whose stage ends first, ties to the lower job.
        t, s = srv_until[0, :a], np.zeros(a, np.int64)
        for k in range(1, w):
            uk = srv_until[k, :a]
            earlier = uk < t
            tie = (uk == t) & np.isfinite(uk)  # idle servers tie harmlessly
            if tie.any():
                here = job_row.take(rf.take(s * b + rows[:a]))
                earlier |= tie & (job_row.take(srv_rank[k, :a]) < here)
            t = np.minimum(t, uk)
            s += earlier * (k - s)
        at = s * b + rows[:a]
        r = rf.take(at)
        f = job_row.take(r) + rows[:a]
        df[f] = t  # its completion time, unless it stages on
        nxt = next_of.take(r)
        qf[f] = nxt + (r == lf.take(f)) * (p - nxt)  # back in the queue, or done
        rf[at], uf[at] = seat(a, t)
    return np.ascontiguousarray(done[:n].T)


def dynamic_value(sizes, probs, table, dtype=np.float64, n_servers: int = 1) -> float:
    """E[sojourn of successful jobs] under a stage-level index policy.

    ``n_servers`` identical servers serve the N jobs, all present at t=0.
    At t=0 the ``n_servers`` jobs with the least ``table[job, 0]`` are
    seated.  At each event the running job whose stage ends first ends
    it: it completes if it has reached its stop checkpoint, and otherwise
    rejoins the queue at ``table[job, checkpoints passed]``.  The freed
    server then takes the queued job with the least index.  Ties, in time
    and in index, go to the lower job number.  A running job is preempted
    only at its own checkpoints (the paper's Section V); with one server
    this is the stage-level policy of Section III-A.
    """
    n, m = sizes.shape
    seg = np.diff(sizes, axis=1, prepend=0.0).astype(dtype)
    rank = _pair_ranks(table.astype(dtype))
    total = 0.0
    for lo in range(0, m**n, BLOCK):
        stops, w = combinations(probs, lo, lo + BLOCK)
        events = stops.sum(axis=1) + n
        by_events = np.argsort(-events, kind="stable")
        stops = stops[by_events]
        done_at = _served(stops, events[by_events], rank, seg, n_servers, dtype)
        succ = stops == m - 1
        cnt = succ.sum(axis=1)
        zero = np.zeros((), dtype)  # no Python scalars beside bfloat16 arrays
        tot = np.where(succ, done_at, zero).sum(axis=1, dtype=dtype)
        mean = np.empty(len(stops), dtype)
        mean[by_events] = np.where(cnt > 0, tot / np.maximum(cnt, 1).astype(dtype), zero)
        total += float((w.astype(dtype) * mean).sum(dtype=dtype))
    return total
