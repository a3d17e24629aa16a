"""Exact and Monte-Carlo evaluation of expected sojourn time of successful jobs.

The paper (Section IV-A1) evaluates a schedule *exactly* by enumerating all
combinations of per-job outcomes (which checkpoint each job stops at),
weighting each combination by its probability.  We reproduce that scheme,
fused and vectorized.  Outcomes reach the fused ops from one of two
sources, never as a table:

* **Exact enumeration** (``K <= MAX_EXACT_COMBOS = 2**26``): the ops
  decode every combination on the fly inside their tiles, so no
  ``(K, N)`` outcome matrix exists anywhere.
* **Streamed Monte Carlo** (``samples=(seed, n_samples)``, what
  :func:`evaluate_many` takes past the exact cap): outcomes are generated
  inside the fused kernels from a counter-based Threefry stream keyed by
  ``(seed, sample, job)``, so no (S, N) sample table is ever
  materialized and all policies under one seed share identical outcome
  streams (common random numbers; see ``docs/streaming_mc.md``).

The entry points:

* :func:`expected_sojourn_static` — a batch of static non-preemptive orders
  (Theorem III.1 justifies restricting to these for RANK/OPTIMAL/RANDOM)
  through :func:`repro.kernels.sojourn_eval.sojourn_eval`.
* :func:`expected_sojourn_dynamic` — stage-level policies (SR / SERPT /
  conditional-RANK) through :mod:`repro.kernels.sojourn_eval.dynamic`,
  which runs the W-server stage-boundary preemption simulation *inside*
  each tile.
* :func:`optimal_order` — exhaustive search over permutations (N <= 9).
* :func:`evaluate` / :func:`evaluate_many` — the paper's policies by name,
  on one server or, with ``n_servers=W``, in Section V's online setting
  on W servers (RANK, SR and SERPT as index policies).

One reference tier stays beside them: the seed's materialized path
(:func:`enumerate_outcomes`, :func:`_realized_arrays`,
:func:`_static_batch`, :func:`_dynamic_batch`, capped at
``MAX_MATERIALIZED_COMBOS = 2**21``).  Tests compare the fused ops
against it; the program does not call it.

Evaluation runs under :func:`repro.runtime.x64`, so host-side tables and
the XLA path are float64 (<=1e-9 agreement with the seed path).  On a
TPU, ``impl="auto"`` runs the Pallas kernels, which compute in float32
within ``ops.CHIP_RTOL`` of that.
Enumeration metadata (mixed-radix strides, combination counts) and padded
workload arrays are cached per workload via
:func:`repro.core.policies.workload_cached`, so the DES and cluster
manager reuse them across policy x trial sweeps.

Conventions: a combination with zero successful jobs contributes 0 (the
paper's Eqs. (7)-(9) sum from l >= 1 successes).
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import policies
from repro.core.jobs import Workload
from repro.kernels.sojourn_eval import rng as kernel_rng
from repro.kernels.sojourn_eval import sojourn_eval, sojourn_eval_dynamic
from repro.kernels.sojourn_eval.ref import mixed_radix_strides
from repro.obs import profiling
from repro.runtime import x64

__all__ = [
    "enumerate_outcomes",
    "expected_sojourn_static",
    "expected_sojourn_dynamic",
    "optimal_order",
    "evaluate",
    "evaluate_many",
]

#: Above this many outcome combinations, exact *static-order* evaluation
#: (which streams combinations through the fused kernel without ever
#: materializing them) falls back to Monte Carlo.
MAX_EXACT_COMBOS = 1 << 26

#: Above this many combinations, the reference tier's (K, N) outcome
#: table is too large to materialize.
MAX_MATERIALIZED_COMBOS = 1 << 21


# ---------------------------------------------------------------------------
# Outcome enumeration
# ---------------------------------------------------------------------------


def _enum_meta(jobs: Workload) -> tuple[int, np.ndarray, np.ndarray]:
    """Cached (K, strides, num_stages) mixed-radix enumeration metadata."""

    def compute():
        _, _, num_stages = policies.padded_arrays(jobs)
        k_total = int(np.prod(num_stages, dtype=np.int64))
        return k_total, mixed_radix_strides(num_stages), num_stages

    return policies.workload_cached("enum_meta", jobs, compute)


def _check_exact(jobs: Workload) -> None:
    """Refuse exact evaluation past ``MAX_EXACT_COMBOS``."""
    k_total, _, _ = _enum_meta(jobs)
    if k_total > MAX_EXACT_COMBOS:
        raise ValueError(
            f"{k_total} combinations exceed MAX_EXACT_COMBOS; use "
            "samples=(seed, n_samples)"
        )


def enumerate_outcomes(jobs: Workload) -> tuple[np.ndarray, np.ndarray]:
    """All outcome combinations, materialized: the reference tier.

    Returns:
      outcomes: (K, N) int32 — for each combination, the stage index at
        which each job stops (M_i - 1 == success).
      weights:  (K,) float64 — probability of each combination.

    Only valid up to ``MAX_MATERIALIZED_COMBOS``; the fused evaluator
    handles larger exact enumerations without materialization.  Tests
    compare against it; the program does not call it.
    """
    _, probs, _ = policies.padded_arrays(jobs)
    k_total, strides, num_stages = _enum_meta(jobs)
    if k_total > MAX_MATERIALIZED_COMBOS:
        raise ValueError(
            f"{k_total} combinations exceed MAX_MATERIALIZED_COMBOS; "
            "expected_sojourn_static enumerates inside the fused kernel"
        )
    # Single vectorized mixed-radix decode + gathered weight product (the
    # seed looped over jobs for both the meshgrid and the product).
    k = np.arange(k_total, dtype=np.int64)
    outcomes = ((k[:, None] // strides[None, :]) % num_stages[None, :]).astype(
        np.int32
    )
    weights = np.prod(
        probs[np.arange(len(jobs))[None, :], outcomes], axis=1, dtype=np.float64
    )
    return outcomes, weights


def _realized_arrays(jobs: Workload, outcomes: np.ndarray):
    """Per-combination realized durations and success masks (reference
    tier: tests compare against it; the program does not call it)."""
    sizes, _, num_stages = policies.padded_arrays(jobs)
    durations = sizes[np.arange(len(jobs)), outcomes]  # (K, N) fancy gather
    success = outcomes == (num_stages[None, :] - 1)
    return durations, success


# ---------------------------------------------------------------------------
# Static non-preemptive orders (fused sojourn_eval op)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("also_all_jobs",))
def _static_batch(durations, success, weights, orders, also_all_jobs=False):
    """Seed reference path: E[sojourn of successful jobs] per order.

    Retained as a parity reference for the fused op: tests compare
    against it; the program does not call it, and goes through
    :func:`repro.kernels.sojourn_eval.sojourn_eval`.

    durations: (K, N)  realized total service per job per combination
    success:   (K, N)  bool
    weights:   (K,)
    orders:    (P, N)  job permutations
    """

    def one_order(order):
        d = jnp.take(durations, order, axis=1)  # (K, N)
        s = jnp.take(success, order, axis=1)
        t = jnp.cumsum(d, axis=1)  # completion times
        cnt = jnp.sum(s, axis=1)
        tot = jnp.sum(t * s, axis=1)
        mean_succ = jnp.where(cnt > 0, tot / jnp.maximum(cnt, 1), 0.0)
        e_succ = jnp.dot(weights, mean_succ)
        if also_all_jobs:
            e_all = jnp.dot(weights, jnp.mean(t, axis=1))
            return e_succ, e_all
        return e_succ

    return jax.vmap(one_order)(orders)


def expected_sojourn_static(
    jobs: Workload,
    orders: np.ndarray,
    also_all_jobs: bool = False,
    impl: str = "auto",
    samples: tuple[int, int] | None = None,
):
    """Expected sojourn of successful jobs for static order(s), fused.

    ``orders`` may be (N,) for a single order or (P, N) for a batch.
    By default the evaluation is exact: all ``prod(M_i)`` combinations
    are enumerated *inside* the fused kernel (up to ``MAX_EXACT_COMBOS``,
    never materializing a (K, N) array).  Passing
    ``samples=(seed, n_samples)`` instead runs *streaming* Monte Carlo:
    outcomes are generated inside the op from the counter-based RNG
    stream, so no (S, N) sample table is ever materialized and every
    order/policy under one seed sees identical outcomes.
    """
    orders = np.asarray(orders, dtype=np.int32)
    single = orders.ndim == 1
    if single:
        orders = orders[None]
    sizes, probs, num_stages = policies.padded_arrays(jobs)
    if samples is None:
        _check_exact(jobs)
    with x64():
        e_succ, e_all = sojourn_eval(
            sizes, probs, num_stages, orders, samples=samples, impl=impl
        )
    if also_all_jobs:
        return (e_succ[0], e_all[0]) if single else (e_succ, e_all)
    return float(e_succ[0]) if single else e_succ


# ---------------------------------------------------------------------------
# Dynamic stage-level policies (JAX lockstep simulation over combinations)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("total_stages",))
def _dynamic_batch(idx_table, stage_durs, outcomes, success, weights, total_stages):
    """Simulate a stage-level index policy for every outcome combination.

    Retained as the ``<= MAX_MATERIALIZED_COMBOS`` single-server reference
    tier for the fused streaming op in
    :mod:`repro.kernels.sojourn_eval.dynamic`: the differential suite
    checks the two against each other and the dense oracle; the program
    does not call it.

    idx_table:  (N, M)   priority after surviving s checkpoints (+inf pad)
    stage_durs: (N, M)   duration of executing checkpoint segment s
    outcomes:   (K, N)   stop-stage per combination
    success:    (K, N)   bool
    """
    k, n = outcomes.shape

    def sim(outcome, succ):
        def body(_, state):
            stage, clock, tdone, done = state
            alive = ~done
            idx = jnp.where(
                alive, idx_table[jnp.arange(n), jnp.minimum(stage, idx_table.shape[1] - 1)],
                jnp.inf,
            )
            any_alive = jnp.any(alive)
            j = jnp.argmin(idx)
            dur = jnp.where(any_alive, stage_durs[j, stage[j]], 0.0)
            clock = clock + dur
            fin = stage[j] >= outcome[j]
            stage = stage.at[j].add(jnp.where(any_alive, 1, 0))
            newly_done = any_alive & fin
            tdone = jnp.where(newly_done, tdone.at[j].set(clock), tdone)
            done = done.at[j].set(done[j] | newly_done)
            return stage, clock, tdone, done

        stage0 = jnp.zeros((n,), dtype=jnp.int32)
        tdone0 = jnp.zeros((n,))
        done0 = jnp.zeros((n,), dtype=bool)
        _, _, tdone, _ = jax.lax.fori_loop(
            0, total_stages, body, (stage0, 0.0, tdone0, done0)
        )
        cnt = jnp.sum(succ)
        tot = jnp.sum(tdone * succ)
        return jnp.where(cnt > 0, tot / jnp.maximum(cnt, 1), 0.0)

    means = jax.vmap(sim)(outcomes, success)
    return jnp.dot(weights, means)


def expected_sojourn_dynamic(
    jobs: Workload,
    policy: str,
    impl: str = "auto",
    samples: tuple[int, int] | None = None,
    n_servers: int = 1,
) -> float:
    """Exact expected sojourn of successful jobs for a stage-level policy.

    By default the evaluation is exact: all ``prod(M_i)`` combinations
    are decoded and *simulated* inside the fused dynamic kernel (up to
    ``MAX_EXACT_COMBOS``, no (K, N) outcome table).  Passing
    ``samples=(seed, n_samples)`` runs streaming Monte Carlo through the
    same fused op — outcomes are generated in-tile from the
    counter-based RNG stream shared with the static op, so no (S, N)
    table exists at any sample count.  ``n_servers=W`` evaluates the
    paper's online multi-server setting, exactly or by streamed MC.
    """
    _, probs, num_stages = policies.padded_arrays(jobs)
    idx_table = policies.index_table(jobs, policy)
    stage_durs = policies.stage_durations(jobs)
    if samples is None:
        _check_exact(jobs)
    with x64():
        e_succ, _ = sojourn_eval_dynamic(
            probs, stage_durs, num_stages, idx_table,
            samples=samples, n_servers=n_servers, impl=impl,
        )
    return float(e_succ[0])


# ---------------------------------------------------------------------------
# Exhaustive OPTIMAL (N <= 9) and the public entry point
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _all_orders(n: int) -> np.ndarray:
    """The read-only ``(n!, n)`` int32 table of every order of ``n`` jobs.

    Rows are in :func:`itertools.permutations`' lexicographic order, so
    an argmin over the table breaks ties to the first order in it.  Built
    once per ``n`` and shared by every later call in the process; each
    build counts as ``prof.optimal.order_builds``.  :func:`optimal_order`
    bounds ``n`` (9 by default), where the table takes 13.1 MB and all
    ``n <= 9`` together about 14.5 MB of host memory.
    """
    profiling.count("optimal.order_builds", 1)
    orders = np.array(list(itertools.permutations(range(n))), dtype=np.int32)
    orders.setflags(write=False)
    return orders


def optimal_order(jobs: Workload, max_n: int = 9) -> tuple[np.ndarray, float]:
    """Exhaustive search over all N! non-preemptive orders (Thm III.1).

    Fetching the ``(N!, N)`` order table (:func:`_all_orders`, built on
    the first call for each N) is the ``prof.optimal.orders`` span of
    :mod:`repro.obs.profiling`.  The returned order is a copy, never a
    view into the shared table.
    """
    n = len(jobs)
    if n > max_n:
        raise ValueError(f"exhaustive search with N={n} > {max_n} is too expensive")
    with profiling.span("optimal.orders"):
        orders = _all_orders(n)
    vals = expected_sojourn_static(jobs, orders)
    best = int(np.argmin(vals))
    return orders[best].copy(), float(vals[best])


#: Policies scored on W > 1 servers: the stage-level index policies of
#: Section V.  OPTIMAL and RANDOM are static orders, optimal on one server only.
ONLINE_POLICIES = ("rank", "sr", "serpt")


def _check_servers(policy: str, n_servers: int) -> None:
    if n_servers < 1:
        raise ValueError(f"n_servers must be >= 1; got {n_servers}")
    if n_servers > 1 and policy not in ONLINE_POLICIES:
        raise ValueError(
            f"no {n_servers}-server evaluation of policy {policy!r}: static orders "
            f"are optimal on one server only (Theorem III.1); on W > 1 servers the "
            f"index policies {ONLINE_POLICIES} are scored"
        )


def evaluate(
    jobs: Workload,
    policy: str,
    rng: np.random.Generator | None = None,
    samples: tuple[int, int] | None = None,
    n_servers: int = 1,
) -> float:
    """Expected sojourn time of successful jobs under ``policy``.

    Policies: 'rank' | 'serpt' | 'sr' | 'random' | 'optimal'.
    On one server, RANK and RANDOM are static orders (Theorem III.1);
    SERPT and SR are stage-level index policies as in the paper's
    Section III-A examples.  ``samples=(seed, n_samples)`` runs streaming
    Monte Carlo with a shared counter stream (common random numbers
    across policies).

    ``n_servers=W > 1`` is the online setting of Section V: W identical
    servers, each taking the alive job of least index at every
    checkpoint.  There RANK is the online, conditional RANK
    (``policies.rank_index_table``: E[remaining size] / P(success |
    survived)), an index policy like SR and SERPT, and all three go
    through :func:`expected_sojourn_dynamic` on W servers, each call the
    ``prof.evaluate.online`` span.  OPTIMAL and RANDOM raise
    ``ValueError``: static orders are optimal on one server only.
    """
    _check_servers(policy, n_servers)
    if n_servers > 1:
        with profiling.span("evaluate.online"):
            return expected_sojourn_dynamic(
                jobs, policy, samples=samples, n_servers=n_servers
            )
    if policy == "rank":
        return expected_sojourn_static(
            jobs, policies.rank_order(jobs), samples=samples
        )
    if policy == "random":
        if rng is None:
            raise ValueError("random policy needs an rng")
        return expected_sojourn_static(
            jobs, policies.random_order(jobs, rng), samples=samples
        )
    if policy == "optimal":
        _, val = optimal_order(jobs)
        return val
    if policy in ("serpt", "sr"):
        return expected_sojourn_dynamic(jobs, policy, samples=samples)
    raise ValueError(f"unknown policy {policy!r}")


def exact_combination_count(jobs: Workload) -> int:
    return _enum_meta(jobs)[0]


def evaluate_many(
    jobs: Workload,
    algs: tuple[str, ...],
    rng: np.random.Generator,
    mc_samples: int = 4096,
    n_servers: int = 1,
) -> dict[str, float]:
    """Evaluate several policies on one job group, sharing random numbers.

    Two regimes by combination count K (static *and* dynamic policies
    stream through fused kernels, so no policy ever needs a materialized
    (K, N) outcome table):
      * K <= MAX_EXACT_COMBOS: everything is exact — static orders via
        :func:`repro.kernels.sojourn_eval.sojourn_eval`, SR/SERPT via
        :func:`repro.kernels.sojourn_eval.sojourn_eval_dynamic`.
      * otherwise: *streaming* Monte Carlo with one seed drawn from
        ``rng`` and shared by every policy (common random numbers) — the
        counter-based stream is keyed by original job id, so all
        policies see the identical outcome sequence without any (S, N)
        sample table ever existing.

    ``n_servers=W > 1`` scores each policy on W servers as :func:`evaluate`
    does (Section V: RANK, SR and SERPT as index policies), in both
    regimes, one op call per policy as on one server; OPTIMAL or RANDOM
    among ``algs`` raises ``ValueError`` before any policy is scored.

    The call is the ``prof.evaluate_many`` span of :mod:`repro.obs.profiling`.
    """
    for alg in algs:
        _check_servers(alg, n_servers)
    with profiling.span("evaluate_many"):
        k_total = exact_combination_count(jobs)
        if k_total <= MAX_EXACT_COMBOS:
            return {alg: evaluate(jobs, alg, rng=rng, n_servers=n_servers) for alg in algs}
        seed = int(rng.integers(0, kernel_rng.MAX_SEED))
        return {
            alg: evaluate(jobs, alg, rng=rng, samples=(seed, mc_samples), n_servers=n_servers)
            for alg in algs
        }
