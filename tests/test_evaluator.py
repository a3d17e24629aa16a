"""Exact evaluator invariants + oracle cross-checks."""

import itertools

import numpy as np
import pytest

from repro.core import evaluator, policies
from repro.core.jobs import JobSpec, generate_workload
from repro.kernels.sojourn_eval import rng as kernel_rng
from repro.kernels.sojourn_eval.ref import ref_sojourn
from repro.obs import get_registry, profiling


def _oracle_static(jobs, order):
    """Direct (slow) enumeration oracle for a static order, pure Python."""
    total = 0.0
    for combo in itertools.product(*[range(j.num_stages) for j in jobs]):
        w = np.prod([jobs[i].probs[c] for i, c in enumerate(combo)])
        t = 0.0
        comp = {}
        for pos in order:
            t += jobs[pos].sizes[combo[pos]]
            comp[pos] = t
        succ = [i for i, c in enumerate(combo) if c == jobs[i].num_stages - 1]
        if succ:
            total += w * np.mean([comp[i] for i in succ])
    return total


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("stages", [2, 3])
def test_static_evaluator_matches_oracle(seed, stages):
    rng = np.random.default_rng(seed)
    jobs = generate_workload(rng, 5, stages, 1)
    order = rng.permutation(5)
    got = evaluator.expected_sojourn_static(jobs, order)
    want = _oracle_static(jobs, order)
    assert got == pytest.approx(want, rel=1e-5)


def test_weights_sum_to_one():
    rng = np.random.default_rng(3)
    jobs = generate_workload(rng, 6, 3, 4)
    _, weights = evaluator.enumerate_outcomes(jobs)
    assert weights.sum() == pytest.approx(1.0)


def test_optimal_lower_bounds_all_policies():
    rng = np.random.default_rng(4)
    for _ in range(5):
        jobs = generate_workload(rng, 6, 2, 1)
        _, e_opt = evaluator.optimal_order(jobs)
        for pol in ("rank", "serpt", "sr"):
            assert evaluator.evaluate(jobs, pol) >= e_opt - 1e-6


def _lex_orders(n):
    return np.array(list(itertools.permutations(range(n))), np.int32)


@pytest.mark.parametrize("n", range(1, 9))
def test_order_table_is_lexicographic_and_read_only(n):
    table = evaluator._all_orders(n)
    assert table.dtype == np.int32
    assert not table.flags.writeable
    np.testing.assert_array_equal(table, _lex_orders(n))
    assert evaluator._all_orders(n) is table


def test_optimal_order_is_float64_argmin_and_builds_table_once():
    rng = np.random.default_rng(9)
    groups = [generate_workload(rng, 6, 2, 2) for _ in range(2)]
    was = profiling.enabled()
    reg = get_registry()
    reg.clear()
    evaluator._all_orders.cache_clear()
    profiling.enable(True)
    try:
        got = [evaluator.optimal_order(jobs) for jobs in groups]
        builds = reg.snapshot()["counters"]["prof.optimal.order_builds"]
    finally:
        profiling.enable(was)
        reg.clear()
    assert builds == 1
    orders = _lex_orders(6)
    for jobs, (order, val) in zip(groups, got):
        want, _ = ref_sojourn(*policies.padded_arrays(jobs), orders)
        best = int(np.argmin(want))
        np.testing.assert_array_equal(order, orders[best])
        assert val == pytest.approx(want[best], rel=1e-9)


def test_optimal_order_returns_a_copy():
    jobs = generate_workload(np.random.default_rng(10), 5, 2, 1)
    order, val = evaluator.optimal_order(jobs)
    kept = order.copy()
    assert order.flags.writeable
    assert not np.shares_memory(order, evaluator._all_orders(5))
    order[:] = order[::-1]
    again, val_again = evaluator.optimal_order(jobs)
    np.testing.assert_array_equal(again, kept)
    assert val_again == val
    np.testing.assert_array_equal(evaluator._all_orders(5), _lex_orders(5))


def test_rank_near_optimal_small_n():
    # Paper Tables IV-VIII: RANK within ~0.2% of OPTIMAL on average;
    # check a loose per-instance bound (max CR <= ~1.12 in paper Table IX).
    rng = np.random.default_rng(5)
    ratios = []
    for _ in range(40):
        jobs = generate_workload(rng, 6, 2, 1)
        _, e_opt = evaluator.optimal_order(jobs)
        ratios.append(evaluator.evaluate(jobs, "rank") / e_opt)
    assert np.mean(ratios) < 1.01
    assert np.max(ratios) < 1.15


def test_relabeling_invariance():
    rng = np.random.default_rng(6)
    jobs = generate_workload(rng, 6, 2, 2)
    perm = rng.permutation(6)
    relabeled = [jobs[p] for p in perm]
    # order in the original labeling vs the same physical order relabeled
    order = rng.permutation(6)
    inv = np.argsort(perm)
    e1 = evaluator.expected_sojourn_static(jobs, order)
    e2 = evaluator.expected_sojourn_static(relabeled, inv[order])
    assert e1 == pytest.approx(e2, rel=1e-6)


def test_dynamic_fixed_order_matches_static():
    """A dynamic index table encoding a fixed priority == static order."""
    rng = np.random.default_rng(7)
    jobs = generate_workload(rng, 5, 2, 1)
    order = rng.permutation(5)
    # index[i, s] = position of i in order (constant over stages) -> jobs run
    # in exactly that sequence (no preemption: running job keeps min index).
    table = np.zeros((5, 2))
    for pos, i in enumerate(order):
        table[i, :] = pos
    got = evaluator.expected_sojourn_dynamic(jobs, "sr")  # warm policy path
    dyn = evaluator._dynamic_batch  # reuse internals with a custom table
    import jax.numpy as jnp

    from repro.core.jobs import pad_workload

    sizes, _, num_stages = pad_workload(jobs)
    outcomes, weights = evaluator.enumerate_outcomes(jobs)
    _, success = evaluator._realized_arrays(jobs, outcomes)
    val = float(
        dyn(
            jnp.asarray(table),
            jnp.asarray(np.diff(sizes, axis=1, prepend=0.0)),
            jnp.asarray(outcomes),
            jnp.asarray(success),
            jnp.asarray(weights),
            int(num_stages.sum()),
        )
    )
    want = evaluator.expected_sojourn_static(jobs, order)
    assert val == pytest.approx(want, rel=1e-5)
    assert np.isfinite(got)


def test_monte_carlo_approaches_exact():
    rng = np.random.default_rng(8)
    jobs = generate_workload(rng, 6, 2, 1)
    exact = evaluator.evaluate(jobs, "rank")
    seed = int(rng.integers(0, kernel_rng.MAX_SEED))
    mc = evaluator.expected_sojourn_static(
        jobs, policies.rank_order(jobs), samples=(seed, 30_000)
    )
    assert mc == pytest.approx(exact, rel=0.05)


def test_no_success_contributes_zero():
    # A workload where all jobs always fail at stage 1 -> E = 0.
    jobs = [
        JobSpec(sizes=[1.0, 2.0], probs=[1.0 - 1e-12, 1e-12], job_id=i)
        for i in range(3)
    ]
    val = evaluator.expected_sojourn_static(jobs, np.arange(3))
    assert val == pytest.approx(0.0, abs=1e-6)
