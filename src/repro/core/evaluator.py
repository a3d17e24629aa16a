"""Exact and Monte-Carlo evaluation of expected sojourn time of successful jobs.

The paper (Section IV-A1) evaluates a schedule *exactly* by enumerating all
combinations of per-job outcomes (which checkpoint each job stops at),
weighting each combination by its probability.  We reproduce that scheme,
fused and vectorized:

* :func:`expected_sojourn_static` — a batch of static non-preemptive orders
  (Theorem III.1 justifies restricting to these for RANK/OPTIMAL/RANDOM)
  evaluated by the fused :mod:`repro.kernels.sojourn_eval` op, which
  decodes outcome combinations on the fly inside the kernel instead of
  materializing the ``(K, N)`` outcome matrix host-side.  Exact
  evaluation scales to ``MAX_EXACT_COMBOS = 2**26`` combinations in
  bounded memory; explicit outcome tables (Monte-Carlo samples or a
  shared exact table) ride the same op's streaming path.
* :func:`expected_sojourn_dynamic` — stage-level policies (SR / SERPT /
  conditional-RANK) evaluated by the fused
  :mod:`repro.kernels.sojourn_eval.dynamic` op, which decodes outcome
  combinations on the fly and runs the single-server stage-boundary
  preemption simulation *inside* each tile, so exact dynamic evaluation
  also scales to ``MAX_EXACT_COMBOS`` with no outcome table.  Explicit
  outcome tables (Monte-Carlo samples) ride the legacy
  :func:`_dynamic_batch` lockstep simulation, which is retained as the
  ``<= MAX_MATERIALIZED_COMBOS`` reference tier for differential tests.
* :func:`optimal_order` — exhaustive search over permutations (N <= 9).
* Beyond ``MAX_EXACT_COMBOS``, both ops switch to *streaming* Monte
  Carlo via ``samples=(seed, n_samples)``: outcomes are generated
  inside the fused kernels from a counter-based Threefry stream keyed
  by ``(seed, sample, job)``, so no (S, N) sample table is ever
  materialized and all policies under one seed share identical outcome
  streams (common random numbers; see ``docs/streaming_mc.md``).
  :func:`sample_outcomes` + explicit tables remain as the legacy
  materialized tier.

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
from repro.runtime import x64

__all__ = [
    "enumerate_outcomes",
    "sample_outcomes",
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

#: Above this many combinations, a (K, N) outcome table is too large to
#: materialize (dynamic-policy lockstep simulation and shared exact tables).
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


def enumerate_outcomes(jobs: Workload) -> tuple[np.ndarray, np.ndarray]:
    """All outcome combinations, materialized.

    Returns:
      outcomes: (K, N) int32 — for each combination, the stage index at
        which each job stops (M_i - 1 == success).
      weights:  (K,) float64 — probability of each combination.

    Only valid up to ``MAX_MATERIALIZED_COMBOS``; the fused evaluator
    handles larger exact enumerations without materialization.
    """
    _, probs, _ = policies.padded_arrays(jobs)
    k_total, strides, num_stages = _enum_meta(jobs)
    if k_total > MAX_MATERIALIZED_COMBOS:
        raise ValueError(
            f"{k_total} combinations exceed MAX_MATERIALIZED_COMBOS; use "
            "sample_outcomes, or expected_sojourn_static(outcomes=None) "
            "which enumerates inside the fused kernel"
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


def sample_outcomes(
    jobs: Workload, n_samples: int, rng: np.random.Generator
) -> tuple[np.ndarray, np.ndarray]:
    """Monte-Carlo outcome sampling; weights are uniform 1/S.

    Vectorized inverse-CDF sampling over the whole (S, N) matrix in one
    shot (the seed drew per-job ``rng.choice`` columns in a Python loop).
    """
    _, probs, num_stages = policies.padded_arrays(jobs)
    cdf = np.cumsum(probs, axis=1)  # (N, M); padded stages add 0 mass
    u = rng.random((n_samples, len(jobs)))
    outcomes = np.sum(u[:, :, None] >= cdf[None, :, :], axis=2)
    outcomes = np.minimum(outcomes, num_stages[None, :] - 1).astype(np.int32)
    weights = np.full((n_samples,), 1.0 / n_samples)
    return outcomes, weights


def _realized_arrays(jobs: Workload, outcomes: np.ndarray):
    """Per-combination realized durations and success masks."""
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

    Retained as the parity oracle for the fused op (tests and the
    ``table_eval_perf`` benchmark); production calls go through
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
    outcomes: np.ndarray | None = None,
    weights: np.ndarray | None = None,
    also_all_jobs: bool = False,
    impl: str = "auto",
    samples: tuple[int, int] | None = None,
):
    """Expected sojourn of successful jobs for static order(s), fused.

    ``orders`` may be (N,) for a single order or (P, N) for a batch.
    With ``outcomes=None`` the evaluation is exact: all ``prod(M_i)``
    combinations are enumerated *inside* the fused kernel (up to
    ``MAX_EXACT_COMBOS``, never materializing a (K, N) array).  Passing
    explicit ``outcomes``/``weights`` (Monte-Carlo samples or a shared
    exact table) streams them through the same op.  Passing
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
    if outcomes is None and samples is None:
        k_total, _, _ = _enum_meta(jobs)
        if k_total > MAX_EXACT_COMBOS:
            raise ValueError(
                f"{k_total} combinations exceed MAX_EXACT_COMBOS; use "
                "samples=(seed, n_samples) or sample_outcomes"
            )
    with x64():
        e_succ, e_all = sojourn_eval(
            sizes,
            probs,
            num_stages,
            orders,
            outcomes=outcomes,
            weights=weights,
            samples=samples,
            impl=impl,
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

    Retained as the ``<= MAX_MATERIALIZED_COMBOS`` reference tier (and the
    Monte-Carlo path) for the fused streaming op in
    :mod:`repro.kernels.sojourn_eval.dynamic`; the differential suite
    checks the two against each other and the dense oracle.

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
    outcomes: np.ndarray | None = None,
    weights: np.ndarray | None = None,
    impl: str = "auto",
    samples: tuple[int, int] | None = None,
    n_servers: int = 1,
) -> float:
    """Exact expected sojourn of successful jobs for a stage-level policy.

    With ``outcomes=None`` the evaluation is exact: all ``prod(M_i)``
    combinations are decoded and *simulated* inside the fused dynamic
    kernel (up to ``MAX_EXACT_COMBOS``, no (K, N) outcome table).
    Passing ``samples=(seed, n_samples)`` runs streaming Monte Carlo
    through the same fused op — outcomes are generated in-tile from the
    counter-based RNG stream shared with the static op, so no (S, N)
    table exists at any sample count.  ``n_servers=W`` evaluates the
    paper's online multi-server setting exactly (or by streamed MC);
    both fused entry modes support it.  Passing explicit
    ``outcomes``/``weights`` (a materialized table) runs the legacy
    lockstep simulation, retained as the single-server reference tier.
    """
    _, probs, num_stages = policies.padded_arrays(jobs)
    idx_table = policies.index_table(jobs, policy)
    stage_durs = policies.stage_durations(jobs)
    if samples is not None:
        with x64():
            e_succ, _ = sojourn_eval_dynamic(
                probs, stage_durs, num_stages, idx_table,
                samples=samples, n_servers=n_servers, impl=impl,
            )
        return float(e_succ[0])
    if outcomes is None:
        k_total, _, _ = _enum_meta(jobs)
        if k_total > MAX_EXACT_COMBOS:
            raise ValueError(
                f"{k_total} combinations exceed MAX_EXACT_COMBOS; use "
                "samples=(seed, n_samples) or sample_outcomes"
            )
        with x64():
            e_succ, _ = sojourn_eval_dynamic(
                probs, stage_durs, num_stages, idx_table,
                n_servers=n_servers, impl=impl,
            )
        return float(e_succ[0])
    if n_servers != 1:
        raise ValueError(
            "the materialized outcomes/weights tier is single-server; "
            "use the fused path (outcomes=None or samples=) for n_servers > 1"
        )
    _, success = _realized_arrays(jobs, outcomes)
    total_stages = int(num_stages.sum())
    with x64():
        val = _dynamic_batch(
            jnp.asarray(np.asarray(idx_table, np.float64)),
            jnp.asarray(np.asarray(stage_durs, np.float64)),
            jnp.asarray(outcomes),
            jnp.asarray(success),
            jnp.asarray(np.asarray(weights, np.float64)),
            total_stages,
        )
        return float(val)


# ---------------------------------------------------------------------------
# Exhaustive OPTIMAL (N <= 9) and the public entry point
# ---------------------------------------------------------------------------


def optimal_order(jobs: Workload, max_n: int = 9) -> tuple[np.ndarray, float]:
    """Exhaustive search over all N! non-preemptive orders (Thm III.1)."""
    n = len(jobs)
    if n > max_n:
        raise ValueError(f"exhaustive search with N={n} > {max_n} is too expensive")
    orders = np.array(list(itertools.permutations(range(n))), dtype=np.int32)
    vals = expected_sojourn_static(jobs, orders)
    best = int(np.argmin(vals))
    return orders[best], float(vals[best])


def evaluate(
    jobs: Workload,
    policy: str,
    rng: np.random.Generator | None = None,
    outcomes: np.ndarray | None = None,
    weights: np.ndarray | None = None,
    samples: tuple[int, int] | None = None,
) -> float:
    """Expected sojourn time of successful jobs under ``policy``.

    Policies: 'rank' | 'serpt' | 'sr' | 'random' | 'optimal'.
    RANK and RANDOM are static orders (Theorem III.1); SERPT and SR are
    stage-level index policies as in the paper's Section III-A examples.
    ``samples=(seed, n_samples)`` runs streaming Monte Carlo with a
    shared counter stream (common random numbers across policies).
    """
    if policy == "rank":
        return expected_sojourn_static(
            jobs, policies.rank_order(jobs), outcomes, weights, samples=samples
        )
    if policy == "random":
        if rng is None:
            raise ValueError("random policy needs an rng")
        return expected_sojourn_static(
            jobs, policies.random_order(jobs, rng), outcomes, weights,
            samples=samples,
        )
    if policy == "optimal":
        _, val = optimal_order(jobs)
        return val
    if policy in ("serpt", "sr"):
        return expected_sojourn_dynamic(
            jobs, policy, outcomes, weights, samples=samples
        )
    raise ValueError(f"unknown policy {policy!r}")


def exact_combination_count(jobs: Workload) -> int:
    return _enum_meta(jobs)[0]


def evaluate_many(
    jobs: Workload,
    algs: tuple[str, ...],
    rng: np.random.Generator,
    mc_samples: int = 4096,
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
    """
    k_total = exact_combination_count(jobs)
    if k_total <= MAX_EXACT_COMBOS:
        return {alg: evaluate(jobs, alg, rng=rng) for alg in algs}
    seed = int(rng.integers(0, kernel_rng.MAX_SEED))
    return {
        alg: evaluate(jobs, alg, rng=rng, samples=(seed, mc_samples))
        for alg in algs
    }
