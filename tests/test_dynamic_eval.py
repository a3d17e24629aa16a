"""Parity suite for the fused dynamic-policy evaluator.

Four mutually independent implementations of "exact expected sojourn of
successful jobs under a stage-level index policy" must agree to <= 1e-9:

1. the fused streaming op (``sojourn_eval_dynamic``), XLA scan path and
   Pallas kernel in interpret mode;
2. the seed materialized lockstep simulation (``evaluator._dynamic_batch``,
   retained as the <= 2^21 reference tier);
3. the dense pure-Python oracle (``ref.ref_sojourn_dynamic``);
4. an exhaustive run of the unified discrete-event simulator
   (``simulate(..., n_servers=W)``) over every enumerated outcome.

All four implementations take ``n_servers``: the multi-server cases pin
the fused evaluator's W-server lockstep (busy-until registers, one
dispatch per completion) against the dict-of-finish-times oracle and
the DES engine's batched event heap.  Deterministic seeded cases run
here unconditionally; the hypothesis property-based version lives in
``test_differential.py``.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import evaluator, policies, simulator
from repro.core.jobs import JobSpec, generate_workload
from repro.kernels.sojourn_eval import sojourn_eval_dynamic
from repro.kernels.sojourn_eval.ref import ref_mc_outcomes, ref_sojourn_dynamic
from repro.runtime import x64

RTOL = 1e-9
IMPLS = ("xla", "interpret")
POLICIES = ("sr", "serpt")
#: The op's two sources of outcomes: exact enumeration and streamed draws.
SOURCES = ("enum", "mc")
MC_SEED = 0x5EED_CAFE


def _relerr(a, b):
    return abs(a - b) / max(abs(b), 1e-300)


def _tables(jobs, policy):
    _, probs, num_stages = policies.padded_arrays(jobs)
    durs = policies.stage_durations(jobs)
    idx = policies.index_table(jobs, policy)
    return probs, durs, num_stages, idx


def fused(jobs, policy, impl, n_servers=1, samples=None):
    probs, durs, num_stages, idx = _tables(jobs, policy)
    with x64():
        es, ea = sojourn_eval_dynamic(
            probs, durs, num_stages, idx, samples=samples, n_servers=n_servers,
            impl=impl,
        )
    return float(es[0]), float(ea[0])


def seed_batch(jobs, policy):
    """The materialized reference tier, fed the enumerated exact table."""
    probs, durs, num_stages, idx = _tables(jobs, policy)
    outcomes, weights = evaluator.enumerate_outcomes(jobs)
    _, success = evaluator._realized_arrays(jobs, outcomes)
    with x64():
        return float(
            evaluator._dynamic_batch(
                jnp.asarray(np.asarray(idx, np.float64)),
                jnp.asarray(np.asarray(durs, np.float64)),
                jnp.asarray(outcomes),
                jnp.asarray(success),
                jnp.asarray(np.asarray(weights, np.float64)),
                int(num_stages.sum()),
            )
        )


def oracle(jobs, policy, n_servers=1, samples=None):
    """The dense oracle over every combination, or over the host replay of
    the streamed draws ``samples=(seed, n_samples)``."""
    probs, durs, num_stages, idx = _tables(jobs, policy)
    table = () if samples is None else ref_mc_outcomes(probs, num_stages, *samples)
    return ref_sojourn_dynamic(probs, durs, num_stages, idx, *table, n_servers=n_servers)


def des_exhaustive(jobs, policy, n_servers=1):
    """Weight-average ``simulate(..., n_servers=W)`` over every outcome."""
    outcomes, weights = evaluator.enumerate_outcomes(jobs)
    total = 0.0
    for outcome, w in zip(outcomes, weights):
        fixed = [
            dataclasses.replace(j, outcome_stage=int(s))
            for j, s in zip(jobs, outcome)
        ]
        res = simulator.simulate(fixed, n_servers, policy)
        total += w * res.mean_sojourn_successful
    return total


# ---------------------------------------------------------------------------
# Four-way differential agreement (seeded; hypothesis version separately)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("seed,n,m", [(0, 3, 2), (1, 4, 3), (2, 5, 2), (3, 6, 3)])
def test_four_way_agreement(policy, seed, n, m):
    rng = np.random.default_rng(seed)
    jobs = generate_workload(rng, n, num_stages=m)
    ref_es, _ = oracle(jobs, policy)
    batch = seed_batch(jobs, policy)
    des = des_exhaustive(jobs, policy)
    assert _relerr(batch, ref_es) < RTOL
    assert _relerr(des, ref_es) < RTOL
    for impl in IMPLS:
        es, _ = fused(jobs, policy, impl)
        assert _relerr(es, ref_es) < RTOL, (impl, es, ref_es)
    # and the public evaluator entry rides the fused path
    assert _relerr(evaluator.expected_sojourn_dynamic(jobs, policy), ref_es) < RTOL


@pytest.mark.parametrize("policy", POLICIES)
def test_four_way_agreement_ragged(policy):
    """Ragged stage counts, a single-stage always-successful job, and a
    zero-probability outcome row, through all four implementations."""
    jobs = [
        JobSpec(sizes=np.array([1.0, 2.5]), probs=np.array([0.3, 0.7])),
        JobSpec(
            sizes=np.array([0.5, 1.0, 4.0, 6.0]),
            probs=np.array([0.1, 0.2, 0.3, 0.4]),
        ),
        JobSpec(sizes=np.array([2.0]), probs=np.array([1.0])),
        JobSpec(sizes=np.array([0.2, 0.9, 1.1]), probs=np.array([0.0, 0.6, 0.4])),
    ]
    ref_es, _ = oracle(jobs, policy)
    assert _relerr(seed_batch(jobs, policy), ref_es) < RTOL
    assert _relerr(des_exhaustive(jobs, policy), ref_es) < RTOL
    for impl in IMPLS:
        es, _ = fused(jobs, policy, impl)
        assert _relerr(es, ref_es) < RTOL, impl


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("n_servers", (2, 3))
@pytest.mark.parametrize("seed,n,m", [(0, 4, 2), (1, 5, 3), (2, 6, 2)])
def test_multi_server_four_way_agreement(policy, n_servers, seed, n, m):
    """W-server parity: fused (xla + interpret) vs dense oracle vs an
    exhaustive run of the unified DES, and the evaluator entry point."""
    rng = np.random.default_rng(seed)
    jobs = generate_workload(rng, n, num_stages=m)
    ref_es, _ = oracle(jobs, policy, n_servers=n_servers)
    des = des_exhaustive(jobs, policy, n_servers=n_servers)
    assert _relerr(des, ref_es) < RTOL
    for impl in IMPLS:
        es, _ = fused(jobs, policy, impl, n_servers=n_servers)
        assert _relerr(es, ref_es) < RTOL, (impl, es, ref_es)
    got = evaluator.expected_sojourn_dynamic(jobs, policy, n_servers=n_servers)
    assert _relerr(got, ref_es) < RTOL


@pytest.mark.parametrize("policy", POLICIES)
def test_servers_exceed_jobs_matches_parallel_service(policy):
    """W >= N: every job runs alone, so E[sojourn | success] is the
    probability-weighted mean over success patterns of per-job total
    sizes — checked against the oracle and monotonicity in W."""
    rng = np.random.default_rng(9)
    jobs = generate_workload(rng, 4, num_stages=3)
    ref_es, ref_ea = oracle(jobs, policy, n_servers=4)
    for w in (4, 6):  # saturated: more servers change nothing
        for impl in IMPLS:
            es, ea = fused(jobs, policy, impl, n_servers=w)
            assert _relerr(es, ref_es) < RTOL
            assert _relerr(ea, ref_ea) < RTOL
    # adding servers never hurts the all-jobs mean sojourn
    prev = float("inf")
    for w in (1, 2, 3, 4):
        _, ea = fused(jobs, policy, "xla", n_servers=w)
        assert ea <= prev + 1e-12
        prev = ea


@pytest.mark.parametrize("n_servers", (2, 3))
def test_multi_server_streamed_mc_matches_host_replay(n_servers):
    """samples= mode at W>1: the streamed outcomes evaluated in-kernel
    must match the host Threefry replay fed to the W-server oracle."""
    rng = np.random.default_rng(23)
    jobs = generate_workload(rng, 5, num_stages=2)
    probs, durs, num_stages, idx = _tables(jobs, "sr")
    seed, n_samples = 77, 512
    outcomes, weights = ref_mc_outcomes(probs, num_stages, seed, n_samples)
    want_es, want_ea = ref_sojourn_dynamic(
        probs, durs, num_stages, idx,
        outcomes=outcomes, weights=weights, n_servers=n_servers,
    )
    with x64():
        for impl in IMPLS:
            es, ea = sojourn_eval_dynamic(
                probs, durs, num_stages, idx,
                samples=(seed, n_samples), n_servers=n_servers, impl=impl,
            )
            assert _relerr(float(es[0]), want_es) < RTOL, impl
            assert _relerr(float(ea[0]), want_ea) < RTOL, impl


# ---------------------------------------------------------------------------
# Kernel-level properties
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", IMPLS)
def test_policy_batch_matches_single(impl):
    """A (P, N, M) stacked table call == per-policy calls."""
    rng = np.random.default_rng(5)
    jobs = generate_workload(rng, 5, num_stages=3)
    probs, durs, num_stages, _ = _tables(jobs, "sr")
    tabs = np.stack(
        [np.asarray(policies.index_table(jobs, p)) for p in POLICIES]
    )
    with x64():
        es_b, ea_b = sojourn_eval_dynamic(probs, durs, num_stages, tabs, impl=impl)
        for i, p in enumerate(POLICIES):
            es, ea = sojourn_eval_dynamic(
                probs, durs, num_stages, tabs[i], impl=impl
            )
            np.testing.assert_allclose(es[0], es_b[i], rtol=RTOL)
            np.testing.assert_allclose(ea[0], ea_b[i], rtol=RTOL)


@pytest.mark.parametrize("impl", IMPLS)
def test_fixed_priority_table_matches_static_order(impl):
    """An index table constant over stages == the static order it encodes
    (no preemption ever pays off), tying the dynamic kernel to the static
    fused evaluator."""
    rng = np.random.default_rng(7)
    jobs = generate_workload(rng, 5, num_stages=2)
    order = rng.permutation(5)
    table = np.zeros((5, 2))
    for pos, i in enumerate(order):
        table[i, :] = pos
    probs, durs, num_stages, _ = _tables(jobs, "sr")
    with x64():
        es, ea = sojourn_eval_dynamic(probs, durs, num_stages, table, impl=impl)
    want = evaluator.expected_sojourn_static(jobs, order, also_all_jobs=True)
    np.testing.assert_allclose(float(es[0]), float(want[0]), rtol=RTOL)
    np.testing.assert_allclose(float(ea[0]), float(want[1]), rtol=RTOL)


@pytest.mark.parametrize("source", SOURCES)
def test_multi_tile_grid_and_tail_masking(source):
    """K = 3^7 = 2187 spans 3 combination tiles with a ragged tail; the
    streamed case draws as many samples, over 3 sample tiles."""
    rng = np.random.default_rng(11)
    jobs = generate_workload(rng, 7, num_stages=3)
    samples = (MC_SEED, 3**7) if source == "mc" else None
    ref_es, ref_ea = oracle(jobs, "serpt", samples=samples)
    for impl in IMPLS:
        es, ea = fused(jobs, "serpt", impl, samples=samples)
        assert _relerr(es, ref_es) < RTOL, impl
        assert _relerr(ea, ref_ea) < RTOL, impl


@pytest.mark.parametrize("source", SOURCES)
def test_n1_single_job(source):
    jobs = [JobSpec(sizes=np.array([1.0, 3.0]), probs=np.array([0.4, 0.6]))]
    samples = (MC_SEED, 1000) if source == "mc" else None
    ref_es, ref_ea = oracle(jobs, "sr", samples=samples)
    for impl in IMPLS:
        es, ea = fused(jobs, "sr", impl, samples=samples)
        assert _relerr(es, ref_es) < RTOL
        assert _relerr(ea, ref_ea) < RTOL
    if source == "enum":
        # single job: E[sojourn | success] is its full size
        np.testing.assert_allclose(ref_es, 0.6 * 3.0, rtol=RTOL)


# ---------------------------------------------------------------------------
# Tiering: exactness beyond the materialization cap
# ---------------------------------------------------------------------------


def test_dynamic_exact_beyond_materialization_cap():
    """K = 2^22 > MAX_MATERIALIZED_COMBOS: enumerate_outcomes refuses, but
    the fused dynamic path evaluates exactly in bounded memory, and
    evaluate_many keeps SR exact instead of falling back to MC."""
    rng = np.random.default_rng(13)
    jobs = generate_workload(rng, 22)  # 2^22 combinations
    assert evaluator.exact_combination_count(jobs) == 2**22
    with pytest.raises(ValueError, match="MAX_MATERIALIZED_COMBOS"):
        evaluator.enumerate_outcomes(jobs)
    val = evaluator.expected_sojourn_dynamic(jobs, "sr")
    assert np.isfinite(val) and val > 0
    # cross-check against an independent MC estimate (loose tolerance)
    mc = evaluator.expected_sojourn_dynamic(jobs, "sr", samples=(MC_SEED, 20_000))
    assert abs(mc - val) / val < 0.05


def test_dynamic_rejects_beyond_exact_cap():
    rng = np.random.default_rng(17)
    jobs = generate_workload(rng, 27)  # 2^27 > MAX_EXACT_COMBOS
    with pytest.raises(ValueError, match="MAX_EXACT_COMBOS"):
        evaluator.expected_sojourn_dynamic(jobs, "sr")


def test_evaluate_many_all_exact_within_cap():
    """At K <= MAX_EXACT_COMBOS no policy uses MC: repeated calls with
    different rngs give identical values."""
    rng = np.random.default_rng(19)
    jobs = generate_workload(rng, 6, num_stages=3)
    a = evaluator.evaluate_many(jobs, ("rank", "sr", "serpt"), np.random.default_rng(0))
    b = evaluator.evaluate_many(jobs, ("rank", "sr", "serpt"), np.random.default_rng(1))
    assert a == b
    assert _relerr(a["sr"], oracle(jobs, "sr")[0]) < RTOL


# ---------------------------------------------------------------------------
# The entry point on W servers (Section V's online setting)
# ---------------------------------------------------------------------------

ONLINE = ("rank", "sr", "serpt")


@pytest.fixture
def entry_impl(request, monkeypatch):
    """The impl ``"auto"`` resolves to inside the evaluator's dynamic op."""
    from repro.kernels.sojourn_eval import dynamic

    impl = request.param
    resolve = dynamic.resolve_impl
    monkeypatch.setattr(dynamic, "resolve_impl", lambda i: impl if i == "auto" else resolve(i))
    return impl


@pytest.mark.parametrize("entry_impl", IMPLS, indirect=True)
@pytest.mark.parametrize("n_servers", (2, 3))
@pytest.mark.parametrize("seed,n,m", [(0, 4, 2), (1, 5, 3), (2, 6, 2), (3, 4, 3)])
def test_evaluate_many_on_servers_matches_references(entry_impl, n_servers, seed, n, m):
    """``evaluate_many(..., n_servers=W)`` scores RANK (the online,
    conditional RANK), SR and SERPT on W servers: it agrees with the dense
    oracle and an exhaustive run of the DES on W servers, on the XLA path
    and in interpret mode (both float64 under the evaluator's x64)."""
    jobs = generate_workload(np.random.default_rng(seed), n, num_stages=m)
    got = evaluator.evaluate_many(jobs, ONLINE, np.random.default_rng(seed), n_servers=n_servers)
    assert list(got) == list(ONLINE)
    for policy in ONLINE:
        want, _ = oracle(jobs, policy, n_servers=n_servers)
        assert _relerr(got[policy], want) < RTOL, (entry_impl, policy, got[policy], want)
        assert _relerr(des_exhaustive(jobs, policy, n_servers=n_servers), want) < RTOL
    # On W > 1 servers RANK is an index policy, not the static RANK order.
    static_rank = evaluator.evaluate(jobs, "rank")
    assert got["rank"] != static_rank


@pytest.mark.parametrize("n_servers", (2, 3))
def test_evaluate_on_servers_streamed_mc_matches_host_replay(n_servers):
    """The streamed-MC path of ``evaluate`` on W servers: the same draws
    replayed on the host and fed to the W-server oracle."""
    jobs = generate_workload(np.random.default_rng(29), 5, num_stages=2)
    samples = (MC_SEED, 512)
    for policy in ONLINE:
        got = evaluator.evaluate(jobs, policy, samples=samples, n_servers=n_servers)
        want, _ = oracle(jobs, policy, n_servers=n_servers, samples=samples)
        assert _relerr(got, want) < RTOL, policy


@pytest.mark.parametrize("n_servers", (2, 3))
@pytest.mark.parametrize("policy", ("optimal", "random"))
def test_static_orders_refused_on_servers(policy, n_servers):
    jobs = generate_workload(np.random.default_rng(31), 4)
    rng = np.random.default_rng(0)
    with pytest.raises(ValueError, match="one server only"):
        evaluator.evaluate(jobs, policy, rng=rng, n_servers=n_servers)
    with pytest.raises(ValueError, match="one server only"):
        evaluator.evaluate_many(jobs, ("sr", policy), rng, n_servers=n_servers)


@pytest.mark.parametrize("n_servers", (0, -1))
def test_fewer_than_one_server_refused(n_servers):
    jobs = generate_workload(np.random.default_rng(37), 4)
    with pytest.raises(ValueError, match="n_servers must be >= 1"):
        evaluator.evaluate(jobs, "sr", n_servers=n_servers)
    with pytest.raises(ValueError, match="n_servers must be >= 1"):
        evaluator.evaluate_many(jobs, ("sr",), np.random.default_rng(0), n_servers=n_servers)


@pytest.mark.parametrize("samples", [None, (MC_SEED, 512)], ids=["exact", "mc"])
@pytest.mark.parametrize("servers", [{}, {"n_servers": 1}], ids=["default", "one"])
def test_one_server_answers_as_before(samples, servers):
    """On one server every policy is scored by the calls the evaluator
    made before it took a server count, bit for bit."""
    jobs = generate_workload(np.random.default_rng(41), 5, num_stages=3)
    before = {
        "rank": evaluator.expected_sojourn_static(
            jobs, policies.rank_order(jobs), samples=samples),
        "random": evaluator.expected_sojourn_static(
            jobs, policies.random_order(jobs, np.random.default_rng(3)), samples=samples),
        "optimal": evaluator.optimal_order(jobs)[1],
        "sr": evaluator.expected_sojourn_dynamic(jobs, "sr", samples=samples),
        "serpt": evaluator.expected_sojourn_dynamic(jobs, "serpt", samples=samples),
    }
    rng = np.random.default_rng(3)
    got = {p: evaluator.evaluate(jobs, p, rng=rng, samples=samples, **servers) for p in before}
    assert got == before
    if samples is None:
        many = evaluator.evaluate_many(jobs, tuple(before), np.random.default_rng(3), **servers)
        assert many == before
