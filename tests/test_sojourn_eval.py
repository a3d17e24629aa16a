"""Parity tests for the fused sojourn evaluator (repro.kernels.sojourn_eval).

Acceptance bar from the paper repro plan: the fused op must match both the
dense oracle (``ref.py``) and the seed materialized path
(``evaluator._static_batch``) to <= 1e-9 *relative* error on paper-style
workloads.  Everything runs on CPU: the Pallas kernels in interpret mode,
the XLA streaming path compiled; both under x64.
"""

import itertools

import numpy as np
import pytest

from repro.core import evaluator, policies
from repro.core.jobs import JobSpec, generate_workload
from repro.kernels.sojourn_eval import sojourn_eval, sojourn_eval_dynamic
from repro.kernels.sojourn_eval.ops import CHIP_RTOL
from repro.kernels.sojourn_eval.ref import (
    mixed_radix_strides,
    ref_decode,
    ref_mc_outcomes,
    ref_sojourn,
    ref_sojourn_dynamic,
)
from repro.runtime import x64

RTOL = 1e-9
IMPLS = ("xla", "interpret")
#: The op's two sources of outcomes: exact enumeration and streamed draws.
SOURCES = ("enum", "mc")
MC_SEED = 0x5EED_CAFE
MC_SAMPLES = 1000  # the 1,024-sample Pallas tile, partly masked


def _orders(n, rng, p=6):
    perms = np.array(list(itertools.permutations(range(n))), dtype=np.int32)
    take = rng.choice(len(perms), size=min(p, len(perms)), replace=False)
    return perms[take]


def _ref(jobs, orders, outcomes=None, weights=None):
    sizes, probs, num_stages = policies.padded_arrays(jobs)
    return ref_sojourn(sizes, probs, num_stages, orders, outcomes, weights)


def _relerr(a, b):
    return np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-300))


def _source_parity(jobs, orders, source, impl, n_samples=MC_SAMPLES):
    """The fused op on ``source`` against the dense oracle on the same
    outcomes: every combination, or the host replay of the streamed draws."""
    samples = (MC_SEED, n_samples) if source == "mc" else None
    es, ea = sojourn_eval_x64(jobs, orders, samples=samples, impl=impl)
    table = ()
    if samples is not None:
        _, probs, num_stages = policies.padded_arrays(jobs)
        table = ref_mc_outcomes(probs, num_stages, *samples)
    r_es, r_ea = _ref(jobs, orders, *table)
    assert _relerr(es, r_es) < RTOL
    assert _relerr(ea, r_ea) < RTOL
    return es, ea


# ---------------------------------------------------------------------------
# Decode / enumeration
# ---------------------------------------------------------------------------


def test_mixed_radix_strides_match_meshgrid():
    num_stages = np.array([2, 3, 2, 4])
    k_total = int(np.prod(num_stages))
    grids = np.meshgrid(*[np.arange(m) for m in num_stages], indexing="ij")
    mesh = np.stack([g.reshape(-1) for g in grids], axis=1)
    np.testing.assert_array_equal(ref_decode(num_stages, k_total), mesh)
    strides = mixed_radix_strides(num_stages)
    assert strides.tolist() == [24, 8, 4, 1]


def test_enumerate_outcomes_vectorized_weights_sum_to_one():
    rng = np.random.default_rng(0)
    jobs = generate_workload(rng, 6, num_stages=3)
    outcomes, weights = evaluator.enumerate_outcomes(jobs)
    assert outcomes.shape == (3**6, 6)
    np.testing.assert_allclose(weights.sum(), 1.0, rtol=1e-12)
    # weights really are the product of per-job stop probabilities
    _, probs, _ = policies.padded_arrays(jobs)
    k = 137
    expect = np.prod([probs[i, outcomes[k, i]] for i in range(6)])
    np.testing.assert_allclose(weights[k], expect, rtol=1e-12)


# ---------------------------------------------------------------------------
# Fused op vs dense oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("n", range(2, 10))
def test_enum_parity_vs_ref(impl, n):
    rng = np.random.default_rng(n)
    jobs = generate_workload(rng, n)  # paper default M=2
    orders = _orders(n, rng)
    es, ea = sojourn_eval_x64(jobs, orders, impl=impl)
    r_es, r_ea = _ref(jobs, orders)
    assert _relerr(es, r_es) < RTOL
    assert _relerr(ea, r_ea) < RTOL


@pytest.mark.parametrize("source", SOURCES)
@pytest.mark.parametrize("impl", IMPLS)
def test_enum_parity_ragged_stages(impl, source):
    """Jobs with different checkpoint counts (padded M axis exercised)."""
    rng = np.random.default_rng(7)
    jobs = [
        JobSpec(sizes=np.array([1.0, 2.5]), probs=np.array([0.3, 0.7])),
        JobSpec(
            sizes=np.array([0.5, 1.0, 4.0, 6.0]),
            probs=np.array([0.1, 0.2, 0.3, 0.4]),
        ),
        JobSpec(sizes=np.array([2.0]), probs=np.array([1.0])),
        JobSpec(
            sizes=np.array([0.2, 0.9, 1.1]), probs=np.array([0.5, 0.25, 0.25])
        ),
    ]
    orders = _orders(4, rng)
    _source_parity(jobs, orders, source, impl)


@pytest.mark.parametrize("impl", IMPLS)
def test_single_order_matches_batched(impl):
    rng = np.random.default_rng(3)
    jobs = generate_workload(rng, 5, num_stages=3)
    orders = _orders(5, rng)
    batched = evaluator.expected_sojourn_static(jobs, orders, impl=impl)
    for i, order in enumerate(orders):
        single = evaluator.expected_sojourn_static(jobs, order, impl=impl)
        assert isinstance(single, float)
        np.testing.assert_allclose(single, batched[i], rtol=RTOL)


# ---------------------------------------------------------------------------
# Edge cases (interpret mode so the Pallas kernels run in CI)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("source", SOURCES)
def test_enum_parity_partial_tail_tile(source):
    """K = 3^7 = 2187: two full (8x128) combination tiles plus a ragged
    tail that must be weight-masked, not evaluated.  The streamed case
    draws as many samples: two full sample tiles and a masked tail."""
    rng = np.random.default_rng(23)
    jobs = generate_workload(rng, 7, num_stages=3)
    orders = _orders(7, rng, p=3)
    _source_parity(jobs, orders, source, "interpret", n_samples=3**7)


@pytest.mark.parametrize("source", SOURCES)
def test_enum_parity_n1(source):
    """A single job: the only 'order' is the identity."""
    jobs = [JobSpec(sizes=np.array([1.0, 3.0]), probs=np.array([0.4, 0.6]))]
    orders = np.zeros((1, 1), dtype=np.int32)
    es, ea = _source_parity(jobs, orders, source, "interpret")
    if source == "enum":
        # E[sojourn | success] = p_succ * full size
        np.testing.assert_allclose(es[0], 0.6 * 3.0, rtol=RTOL)
        np.testing.assert_allclose(ea[0], 0.4 * 1.0 + 0.6 * 3.0, rtol=RTOL)


@pytest.mark.parametrize("source", SOURCES)
def test_enum_parity_single_stage_jobs(source):
    """Always-successful single-checkpoint jobs: K = 1 combination, every
    job succeeds, and the padded stage axis degenerates to M = 1."""
    jobs = [
        JobSpec(sizes=np.array([2.0]), probs=np.array([1.0])),
        JobSpec(sizes=np.array([0.5]), probs=np.array([1.0])),
        JobSpec(sizes=np.array([1.25]), probs=np.array([1.0])),
    ]
    orders = np.array([[0, 1, 2], [2, 1, 0]], dtype=np.int32)
    es, _ = _source_parity(jobs, orders, source, "interpret")
    # deterministic, whatever the source: mean of the prefix sums
    np.testing.assert_allclose(es[0], np.mean([2.0, 2.5, 3.75]), rtol=RTOL)


@pytest.mark.parametrize("source", SOURCES)
@pytest.mark.parametrize("impl", IMPLS)
def test_enum_parity_zero_probability_row(impl, source):
    """A job that can never stop early (p = 0 at an interior checkpoint):
    combinations selecting that row carry zero weight and must not
    contribute, even though their durations are still decoded; the
    streamed draws never select it."""
    rng = np.random.default_rng(29)
    jobs = [
        JobSpec(sizes=np.array([1.0, 2.0]), probs=np.array([0.0, 1.0])),
        JobSpec(sizes=np.array([0.5, 1.5, 3.0]), probs=np.array([0.2, 0.0, 0.8])),
        JobSpec(sizes=np.array([1.0, 4.0]), probs=np.array([0.3, 0.7])),
    ]
    orders = _orders(3, rng)
    _source_parity(jobs, orders, source, impl)


def _random_orders(n, p, seed):
    rng = np.random.default_rng(seed)
    return np.array([rng.permutation(n) for _ in range(p)], dtype=np.int32)


def _ragged_jobs(seed):
    # Stage counts 2, 4, 1, 3, 5, 3, 2, 4: K = 2880, three combination
    # tiles (the last partial), stages padded to M = 5.
    rng = np.random.default_rng(seed)
    return [generate_workload(rng, 1, num_stages=m)[0] for m in (2, 4, 1, 3, 5, 3, 2, 4)]


# The static kernel scores a block of orders per grid step against one
# decoded combination tile (kernel.enum_order_block).  (jobs, orders) per case.
ORDER_BLOCK_CASES = {
    # One tile (K = 128); 1,001 orders in blocks of 504 and 497, so the
    # tail block ends inside a trip of the order loop.
    "one_tile_tail_block": lambda: (generate_workload(np.random.default_rng(41), 7),
                                    _random_orders(7, 1001, 41)),
    "one_tile_one_order": lambda: (generate_workload(np.random.default_rng(43), 8),
                                   _random_orders(8, 1, 43)),
    # K = 2048 spans two tiles: per-order Kahan sums across them.
    "tiles_one_order": lambda: (generate_workload(np.random.default_rng(47), 11),
                                _random_orders(11, 1, 47)),
    "tiles_tail_block": lambda: (generate_workload(np.random.default_rng(53), 11),
                                 _random_orders(11, 70, 53)),
    "tiles_ragged_stages": lambda: (_ragged_jobs(59), _random_orders(8, 40, 59)),
    "all_orders_n5": lambda: (generate_workload(np.random.default_rng(61), 5, num_stages=3),
                              np.array(list(itertools.permutations(range(5))), np.int32)),
    # All 7! = 5,040 orders, more than the 4,096 an XLA call takes: one
    # kernel call of ten blocks of 504 over one tile (K = 128).
    "one_tile_all_orders_n7": lambda: (generate_workload(np.random.default_rng(71), 7),
                                       np.array(list(itertools.permutations(range(7))),
                                                np.int32)),
    # Eight stages a job (Table XIV's largest M): K = 8**4 = 4096 spans four
    # tiles, and all 24 orders are one block carrying Kahan sums across them.
    "tiles_m8_all_orders": lambda: (generate_workload(np.random.default_rng(67), 4, num_stages=8),
                                    np.array(list(itertools.permutations(range(4))), np.int32)),
}


@pytest.mark.parametrize("case", sorted(ORDER_BLOCK_CASES))
def test_enum_order_blocks_parity(case):
    jobs, orders = ORDER_BLOCK_CASES[case]()
    es, ea = sojourn_eval_x64(jobs, orders, impl="interpret")
    r_es, r_ea = _ref(jobs, orders)
    assert _relerr(es, r_es) < RTOL
    assert _relerr(ea, r_ea) < RTOL
    x_es, x_ea = sojourn_eval_x64(jobs, orders, impl="xla")
    assert _relerr(es, x_es) < RTOL
    assert _relerr(ea, x_ea) < RTOL


# ---------------------------------------------------------------------------
# Fused op vs the seed materialized path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,m", [(4, 2), (6, 2), (5, 3), (8, 2), (3, 5)])
def test_parity_vs_seed_static_batch(n, m):
    rng = np.random.default_rng(n * 10 + m)
    jobs = generate_workload(rng, n, num_stages=m)
    orders = _orders(n, rng)
    outcomes, weights = evaluator.enumerate_outcomes(jobs)
    durations, success = evaluator._realized_arrays(jobs, outcomes)
    with x64():
        seed_es, seed_ea = evaluator._static_batch(
            np.asarray(durations, np.float64), success, np.asarray(weights, np.float64), orders,
            also_all_jobs=True,
        )
    seed_es, seed_ea = np.asarray(seed_es), np.asarray(seed_ea)
    for impl in IMPLS:
        es, ea = sojourn_eval_x64(jobs, orders, impl=impl)
        assert _relerr(es, seed_es) < RTOL, impl
        assert _relerr(ea, seed_ea) < RTOL, impl


def test_evaluator_static_entry_uses_fused_path():
    rng = np.random.default_rng(11)
    jobs = generate_workload(rng, 7)
    orders = _orders(7, rng)
    vals = evaluator.expected_sojourn_static(jobs, orders)
    r_es, _ = _ref(jobs, orders)
    assert _relerr(np.asarray(vals), r_es) < RTOL


# ---------------------------------------------------------------------------
# Large-K capability (no (K, N) materialization)
# ---------------------------------------------------------------------------


def test_exact_beyond_materialization_cap():
    """K = 2^22 > MAX_MATERIALIZED_COMBOS: enumerate_outcomes refuses but
    the fused static path evaluates exactly, in bounded memory."""
    rng = np.random.default_rng(13)
    jobs = generate_workload(rng, 22)  # 2^22 combinations
    assert evaluator.exact_combination_count(jobs) == 2**22
    assert evaluator.MAX_EXACT_COMBOS >= 2**26
    with pytest.raises(ValueError, match="MAX_MATERIALIZED_COMBOS"):
        evaluator.enumerate_outcomes(jobs)
    order = policies.rank_order(jobs)
    val = evaluator.expected_sojourn_static(jobs, order)
    assert np.isfinite(val) and val > 0
    # cross-check against an independent MC estimate (loose tolerance)
    mc = evaluator.expected_sojourn_static(jobs, order, samples=(MC_SEED, 20_000))
    assert abs(mc - val) / val < 0.05


def test_evaluate_many_optimal_and_rank_at_eight_stages():
    """Table XIV's comparison at M = 8: OPTIMAL is the least value over all
    N! orders, and RANK the value of its own order."""
    jobs = generate_workload(np.random.default_rng(71), 4, num_stages=8)
    res = evaluator.evaluate_many(jobs, ("optimal", "rank"), np.random.default_rng(0))
    all_orders = np.array(list(itertools.permutations(range(4))), np.int32)
    r_all, _ = _ref(jobs, all_orders)
    (r_rank,), _ = _ref(jobs, policies.rank_order(jobs)[None])
    np.testing.assert_allclose(res["optimal"], r_all.min(), rtol=RTOL)
    np.testing.assert_allclose(res["rank"], r_rank, rtol=RTOL)


def test_evaluate_many_tiering():
    """Static policies stay exact past the materialization cap; dynamic
    ones fall back to MC."""
    rng = np.random.default_rng(17)
    jobs = generate_workload(rng, 22)
    res = evaluator.evaluate_many(jobs, ("rank", "sr"), rng, mc_samples=512)
    assert set(res) == {"rank", "sr"}
    exact = evaluator.expected_sojourn_static(jobs, policies.rank_order(jobs))
    np.testing.assert_allclose(res["rank"], exact, rtol=RTOL)


# ---------------------------------------------------------------------------
# Workload-keyed cache
# ---------------------------------------------------------------------------


def test_workload_cache_hits_and_readonly():
    rng = np.random.default_rng(19)
    jobs = generate_workload(rng, 5)
    a = policies.index_table(jobs, "sr")
    b = policies.index_table(jobs, "sr")
    assert a is b  # same workload content -> cached object
    assert not a.flags.writeable
    # equal content in *different* JobSpec objects also hits
    clones = [
        JobSpec(sizes=j.sizes.copy(), probs=j.probs.copy(), arrival=j.arrival)
        for j in jobs
    ]
    assert policies.index_table(clones, "sr") is a
    # different content misses
    other = generate_workload(rng, 5)
    assert policies.index_table(other, "sr") is not a


def sojourn_eval_x64(jobs, orders, samples=None, impl="xla"):
    sizes, probs, num_stages = policies.padded_arrays(jobs)
    with x64():
        es, ea = sojourn_eval(
            sizes, probs, num_stages, np.asarray(orders, np.int32),
            samples=samples, impl=impl,
        )
    return np.asarray(es), np.asarray(ea)


# ---------------------------------------------------------------------------
# Float32 kernels, as compiled for the chip, against the float64 oracle
# ---------------------------------------------------------------------------

F32_SEED = 0x5EED
F32_SAMPLES = 5000  # five sample tiles, the last one partial


def _f32_workload():
    # K = 3**8 = 6561: seven combination tiles, the last one partial;
    # Weibull stage sizes (workload set 5) give the widest spread of terms.
    jobs = generate_workload(np.random.default_rng(23), 8, num_stages=3, workload_set=5)
    sizes, probs, num_stages = policies.padded_arrays(jobs)
    return jobs, sizes, probs, num_stages


@pytest.mark.parametrize("mode", SOURCES)
def test_static_float32_kernels_within_chip_tolerance(mode):
    jobs, sizes, probs, num_stages = _f32_workload()
    orders = _orders(8, np.random.default_rng(29))
    outcomes = weights = None
    if mode == "mc":
        outcomes, weights = ref_mc_outcomes(probs, num_stages, F32_SEED, F32_SAMPLES)
    # Outside the x64 scope the interpreted kernels compute in float32.
    es, ea = sojourn_eval(
        sizes, probs, num_stages, orders,
        samples=(F32_SEED, F32_SAMPLES) if mode == "mc" else None,
        impl="interpret",
    )
    assert es.dtype == np.float32
    want_s, want_a = ref_sojourn(sizes, probs, num_stages, orders, outcomes, weights)
    assert _relerr(es, want_s) <= CHIP_RTOL[mode]
    assert _relerr(ea, want_a) <= CHIP_RTOL[mode]


@pytest.mark.parametrize("n_servers", (1, 3))
@pytest.mark.parametrize("mode", ("enum", "mc"))
def test_dynamic_float32_kernels_within_chip_tolerance(mode, n_servers):
    jobs, _, probs, num_stages = _f32_workload()
    durs = policies.stage_durations(jobs)
    tables = np.stack([policies.index_table(jobs, p) for p in ("sr", "serpt")])
    outcomes = weights = None
    if mode == "mc":
        outcomes, weights = ref_mc_outcomes(probs, num_stages, F32_SEED, F32_SAMPLES)
    es, ea = sojourn_eval_dynamic(
        probs, durs, num_stages, tables, n_servers=n_servers, impl="interpret",
        samples=(F32_SEED, F32_SAMPLES) if mode == "mc" else None,
    )
    assert es.dtype == np.float32
    for p, table in enumerate(tables):
        want_s, want_a = ref_sojourn_dynamic(
            probs, durs, num_stages, table, outcomes, weights, n_servers
        )
        assert _relerr(es[p], want_s) <= CHIP_RTOL[mode]
        assert _relerr(ea[p], want_a) <= CHIP_RTOL[mode]
