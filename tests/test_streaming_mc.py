"""Streaming Monte-Carlo mode: stream parity, CLT accuracy, CRN sharing.

Three claims are tested, matching the design note in
``docs/streaming_mc.md``:

* **Bitwise replay parity** — the counter-based Threefry stream decoded
  inside the kernels (xla / Pallas-interpret) is bit-identical to the
  NumPy host replay in ``ref.ref_mc_outcomes``: evaluating the streamed
  mode and evaluating the replayed dense table give the same estimate
  up to float summation order.
* **CLT accuracy** — on a small-K control the streamed estimate agrees
  with the exact fused enumeration within 3-sigma CLT bounds (sigma
  estimated from the replayed per-sample values).
* **Common random numbers** — the stream is keyed by *original* job id,
  so every order and every policy evaluated under one seed sees the
  identical outcome sequence; ``evaluate_many`` draws one shared seed
  past the exact cap and is reproducible from the caller's rng state.

Plus the satellite guards: zero-survival clamping in
``policies._conditional_arrays`` and the ``REPRO_CACHE_DIR`` disk memo.
"""

import os

import numpy as np
import pytest

from repro.core import evaluator, policies
from repro.core.jobs import JobSpec, generate_workload
from repro.kernels.sojourn_eval import rng, sojourn_eval
from repro.kernels.sojourn_eval.ref import ref_mc_outcomes
from repro.runtime import x64

IMPLS = ("xla", "interpret")
SEED = 0x5EED_CAFE
RTOL = 1e-9


def _padded(jobs):
    return policies.padded_arrays(jobs)


# ---------------------------------------------------------------------------
# RNG stream
# ---------------------------------------------------------------------------


def test_threefry_numpy_vs_jax_bitwise():
    import jax.numpy as jnp

    k0, k1 = rng.split_seed(SEED)
    x0 = np.arange(1024, dtype=np.uint32).reshape(8, 128)
    x1 = (x0 * np.uint32(2654435761)) % np.uint32(977)
    a0, a1 = rng.threefry2x32(np, (k0, k1), x0, x1)
    b0, b1 = rng.threefry2x32(
        jnp, (jnp.uint32(k0), jnp.uint32(k1)), jnp.asarray(x0), jnp.asarray(x1)
    )
    np.testing.assert_array_equal(a0, np.asarray(b0))
    np.testing.assert_array_equal(a1, np.asarray(b1))


def test_threefry_matches_jax_prng_family():
    """Our block is the same Threefry-2x32 as jax.random's base PRNG."""
    import jax._src.prng as jax_prng

    k0, k1 = 7, 13
    x0 = np.arange(256, dtype=np.uint32)
    x1 = np.zeros(256, dtype=np.uint32)
    ours0, ours1 = rng.threefry2x32(np, (k0, k1), x0, x1)
    theirs = jax_prng.threefry_2x32(
        np.array([k0, k1], dtype=np.uint32),
        np.concatenate([x0, x1]),
    )
    np.testing.assert_array_equal(ours0, np.asarray(theirs[:256]))
    np.testing.assert_array_equal(ours1, np.asarray(theirs[256:]))


def test_split_seed_range_validation():
    assert rng.split_seed(0) == (0, 0)
    lo, hi = rng.split_seed(rng.MAX_SEED - 1)
    assert lo == 0x7FFFFFFF and hi == 0x7FFFFFFF
    with pytest.raises(ValueError):
        rng.split_seed(-1)
    with pytest.raises(ValueError):
        rng.split_seed(rng.MAX_SEED)


def test_host_outcomes_match_stop_distribution():
    g = np.random.default_rng(3)
    jobs = generate_workload(g, 4, num_stages=3)
    _, probs, num_stages = _padded(jobs)
    outcomes = rng.host_outcomes(SEED, 200_000, probs, num_stages)
    for i in range(4):
        freq = np.bincount(outcomes[:, i], minlength=3) / 200_000
        np.testing.assert_allclose(freq, probs[i, :3], atol=5e-3)


# ---------------------------------------------------------------------------
# Bitwise replay parity: streamed kernels vs dense host replay
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", IMPLS)
def test_streamed_static_matches_host_replay(impl):
    g = np.random.default_rng(11)
    jobs = generate_workload(g, 5, num_stages=3)
    sizes, probs, num_stages = _padded(jobs)
    orders = np.stack([np.arange(5), np.argsort(-np.arange(5))]).astype(np.int32)
    n_samples = 2048
    outcomes, weights = ref_mc_outcomes(probs, num_stages, SEED, n_samples)
    with x64():
        want = sojourn_eval(
            sizes, probs, num_stages, orders,
            outcomes=outcomes, weights=weights, impl="xla",
        )
        got = sojourn_eval(
            sizes, probs, num_stages, orders,
            samples=(SEED, n_samples), impl=impl,
        )
    # Same outcomes, same weights; only the summation order differs.
    np.testing.assert_allclose(got[0], want[0], rtol=RTOL)
    np.testing.assert_allclose(got[1], want[1], rtol=RTOL)


@pytest.mark.parametrize("impl", IMPLS)
def test_streamed_dynamic_matches_host_replay(impl):
    g = np.random.default_rng(12)
    jobs = generate_workload(g, 4, num_stages=3)
    _, probs, num_stages = _padded(jobs)
    n_samples = 1024
    outcomes, weights = ref_mc_outcomes(probs, num_stages, SEED, n_samples)
    for policy in ("sr", "serpt"):
        want = evaluator.expected_sojourn_dynamic(
            jobs, policy, outcomes=outcomes, weights=weights
        )
        got = evaluator.expected_sojourn_dynamic(
            jobs, policy, samples=(SEED, n_samples), impl=impl
        )
        np.testing.assert_allclose(got, want, rtol=RTOL)


def test_streamed_non_pow2_sample_count_tail_masked():
    """Tail lanes past n_samples must carry zero weight."""
    g = np.random.default_rng(13)
    jobs = generate_workload(g, 4, num_stages=3)
    sizes, probs, num_stages = _padded(jobs)
    order = np.arange(4, dtype=np.int32)[None]
    n_samples = 1000  # not a multiple of any tile shape
    outcomes, weights = ref_mc_outcomes(probs, num_stages, SEED, n_samples)
    with x64():
        want = sojourn_eval(
            sizes, probs, num_stages, order,
            outcomes=outcomes, weights=weights, impl="xla",
        )
        for impl in IMPLS:
            got = sojourn_eval(
                sizes, probs, num_stages, order,
                samples=(SEED, n_samples), impl=impl,
            )
            np.testing.assert_allclose(got[0], want[0], rtol=RTOL)


# ---------------------------------------------------------------------------
# CLT accuracy against the exact path (small-K control)
# ---------------------------------------------------------------------------


def test_streamed_static_within_clt_of_exact():
    g = np.random.default_rng(21)
    jobs = generate_workload(g, 6, num_stages=3)  # K = 729, exact is cheap
    sizes, probs, num_stages = _padded(jobs)
    order = policies.rank_order(jobs)
    exact = evaluator.expected_sojourn_static(jobs, order)
    n_samples = 1 << 15
    est = evaluator.expected_sojourn_static(jobs, order, samples=(SEED, n_samples))
    # sigma from the replayed per-sample values (exactly what was streamed)
    outcomes, _ = ref_mc_outcomes(probs, num_stages, SEED, n_samples)
    d = sizes[np.arange(len(jobs))[None, :], outcomes]
    succ = outcomes == num_stages[None, :] - 1
    t = np.cumsum(d[:, order], axis=1)
    cnt = succ.sum(axis=1)
    vals = np.where(
        cnt > 0, (t * succ[:, order]).sum(axis=1) / np.maximum(cnt, 1), 0.0
    )
    sigma = vals.std(ddof=1) / np.sqrt(n_samples)
    assert abs(est - exact) <= 3.0 * sigma + 1e-12
    np.testing.assert_allclose(est, vals.mean(), rtol=RTOL)


def test_streamed_dynamic_within_clt_of_exact():
    g = np.random.default_rng(22)
    jobs = generate_workload(g, 5, num_stages=3)  # K = 243
    _, probs, num_stages = _padded(jobs)
    n_samples = 1 << 15
    for policy in ("sr", "serpt"):
        exact = evaluator.expected_sojourn_dynamic(jobs, policy)
        est = evaluator.expected_sojourn_dynamic(
            jobs, policy, samples=(SEED, n_samples)
        )
        # conservative sigma bound: per-sample values live in [0, sum durs]
        outcomes, weights = ref_mc_outcomes(probs, num_stages, SEED, n_samples)
        mc_table = evaluator.expected_sojourn_dynamic(
            jobs, policy, outcomes=outcomes, weights=weights
        )
        # the streamed estimate IS the table estimate (parity), and the
        # table estimate is an unbiased S-sample MC mean of the exact value
        np.testing.assert_allclose(est, mc_table, rtol=RTOL)
        span = float(policies.stage_durations(jobs).sum())
        sigma = span / np.sqrt(n_samples)  # worst-case bound on std
        assert abs(est - exact) <= 3.0 * sigma


# ---------------------------------------------------------------------------
# Common random numbers
# ---------------------------------------------------------------------------


def test_common_random_numbers_across_orders_and_policies():
    """The stream is keyed by original job id: every order and policy
    under one seed sees the same outcome table."""
    g = np.random.default_rng(31)
    jobs = generate_workload(g, 5, num_stages=3)
    sizes, probs, num_stages = _padded(jobs)
    n_samples = 4096
    outcomes, weights = ref_mc_outcomes(probs, num_stages, SEED, n_samples)
    # two different static orders against the shared replayed table
    for order in (np.arange(5), np.array([4, 2, 0, 3, 1])):
        want = evaluator.expected_sojourn_static(
            jobs, order, outcomes=outcomes, weights=weights
        )
        got = evaluator.expected_sojourn_static(
            jobs, order, samples=(SEED, n_samples)
        )
        np.testing.assert_allclose(got, want, rtol=RTOL)
    # dynamic policies against the same table under the same seed
    for policy in ("sr", "serpt"):
        want = evaluator.expected_sojourn_dynamic(
            jobs, policy, outcomes=outcomes, weights=weights
        )
        got = evaluator.expected_sojourn_dynamic(
            jobs, policy, samples=(SEED, n_samples)
        )
        np.testing.assert_allclose(got, want, rtol=RTOL)


def test_evaluate_many_beyond_cap_streams_one_shared_seed():
    g = np.random.default_rng(41)
    jobs = generate_workload(g, 14, num_stages=4)  # K = 4^14 = 2^28 > cap
    assert evaluator.exact_combination_count(jobs) > evaluator.MAX_EXACT_COMBOS
    res = evaluator.evaluate_many(
        jobs, ("rank", "serpt", "sr"), np.random.default_rng(99), mc_samples=2048
    )
    # reproducible purely from the caller's rng state: one seed, shared
    g2 = np.random.default_rng(99)
    seed = int(g2.integers(0, rng.MAX_SEED))
    for alg in ("rank", "serpt", "sr"):
        want = evaluator.evaluate(jobs, alg, samples=(seed, 2048))
        assert res[alg] == want
    # CRN: same seed means identical outcomes, so the rank-vs-serpt gap
    # is measured on common random numbers (no sampling-noise cross-term)
    assert set(res) == {"rank", "serpt", "sr"}
    assert all(np.isfinite(v) and v > 0 for v in res.values())


def test_evaluate_many_within_cap_still_exact():
    g = np.random.default_rng(42)
    jobs = generate_workload(g, 5, num_stages=3)
    r1 = evaluator.evaluate_many(jobs, ("rank", "sr"), np.random.default_rng(1))
    r2 = evaluator.evaluate_many(jobs, ("rank", "sr"), np.random.default_rng(2))
    assert r1 == r2  # exact tier: rng must not influence results


# ---------------------------------------------------------------------------
# Satellite guards
# ---------------------------------------------------------------------------


def test_conditional_arrays_zero_survival_is_finite():
    # All stop mass on stage 0: surviving it has probability 0 and the
    # conditional tables must clamp instead of emitting inf/nan.
    jobs = [
        JobSpec(sizes=[1.0, 2.0, 3.0], probs=[1.0, 0.0, 0.0], job_id=0),
        JobSpec(sizes=[1.0, 4.0], probs=[0.5, 0.5], job_id=1),
    ]
    for table_fn in (policies.serpt_index_table, policies.sr_index_table):
        table = table_fn(jobs)
        assert not np.isnan(table).any()
        assert np.isfinite(table[0, 0])
    # rank_index_table divides by the conditional success probability and
    # may legitimately be +inf for a job that cannot succeed, but nan is
    # a bug in any table.
    assert not np.isnan(policies.rank_index_table(jobs)).any()


def test_conditional_arrays_rounding_survival():
    # Prefix mass sums to exactly 1.0 in float64 while a positive tail
    # remains (legal within JobSpec's 1e-9 tolerance); the clamp
    # renormalizes by the tail mass so the conditional probs stay a
    # distribution instead of dividing by zero.
    jobs = [JobSpec(sizes=[1.0, 2.0, 3.0], probs=[0.5, 0.5, 1e-10], job_id=0)]
    for _, s, _, rem_probs in policies._conditional_arrays(jobs):
        assert np.isfinite(rem_probs).all()
        if s == 2:
            np.testing.assert_allclose(rem_probs.sum(), 1.0, rtol=1e-12)


def test_disk_cache_roundtrip_and_counters(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    g = np.random.default_rng(51)
    jobs = generate_workload(g, 5)
    policies.clear_workload_cache()
    policies.reset_cache_stats()

    t1 = policies.index_table(jobs, "sr")  # mem miss + disk miss: computes
    policies.clear_workload_cache()  # drop memory, keep disk
    t2 = policies.index_table(jobs, "sr")  # mem miss + disk hit: loads
    np.testing.assert_array_equal(t1, t2)
    assert not t2.flags.writeable  # loaded entries are frozen too
    t3 = policies.index_table(jobs, "sr")  # mem hit: disk untouched
    np.testing.assert_array_equal(t1, t3)

    stats = policies.cache_stats()
    assert stats["disk_hits"] == 1 and stats["disk_misses"] == 1
    assert stats["by_kind"]["idx_table:sr"] == {
        "hits": 1, "misses": 2, "disk_hits": 1, "disk_misses": 1,
    }
    files = list(tmp_path.iterdir())
    assert len(files) == 1 and files[0].name.endswith(".npz")
    assert ":" not in files[0].name  # kind is sanitized for filenames


def test_disk_cache_tuple_entries_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    g = np.random.default_rng(52)
    jobs = generate_workload(g, 4, num_stages=3)
    policies.clear_workload_cache()
    k1, strides1, radix1 = evaluator._enum_meta(jobs)
    policies.clear_workload_cache()
    k2, strides2, radix2 = evaluator._enum_meta(jobs)  # from disk
    assert isinstance(k2, int) and k1 == k2 == 3**4
    np.testing.assert_array_equal(strides1, strides2)
    np.testing.assert_array_equal(radix1, radix2)


def test_disk_cache_off_keeps_legacy_stats_shape():
    g = np.random.default_rng(53)
    jobs = generate_workload(g, 4)
    policies.clear_workload_cache()
    policies.reset_cache_stats()
    policies.index_table(jobs, "sr")
    policies.index_table(jobs, "sr")
    stats = policies.cache_stats()
    assert stats["by_kind"]["idx_table:sr"] == {"hits": 1, "misses": 1}
    assert "disk_hits" not in stats


def test_disk_cache_lru_eviction_and_counter(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    g = np.random.default_rng(54)
    w_a, w_b, w_c = (generate_workload(g, 5) for _ in range(3))
    policies.clear_workload_cache()
    policies.reset_cache_stats()

    policies.index_table(w_a, "sr")
    (file_a,) = tmp_path.iterdir()
    entry = file_a.stat().st_size
    # bound fits two entries; the third store must evict the stalest
    monkeypatch.setenv("REPRO_CACHE_DISK_BYTES", str(int(2.5 * entry)))
    policies.index_table(w_b, "sr")
    file_b = next(f for f in tmp_path.iterdir() if f != file_a)
    assert "disk_evictions" not in policies.cache_stats()  # still under bound

    # pin recency: a is fresh, b is stale -> b is the LRU victim
    os.utime(file_a, (1_000, 1_000))
    os.utime(file_b, (500, 500))
    policies.index_table(w_c, "sr")
    names = {f.name for f in tmp_path.iterdir()}
    assert file_a.name in names and file_b.name not in names
    assert len(names) == 2
    assert policies.cache_stats()["disk_evictions"] == 1

    # a disk *hit* refreshes the entry's mtime (loads count as uses)
    policies.clear_workload_cache()
    policies.index_table(w_a, "sr")
    assert file_a.stat().st_mtime > 1_000

    policies.reset_cache_stats()
    assert "disk_evictions" not in policies.cache_stats()


def test_disk_cache_unbounded_opt_out(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("REPRO_CACHE_DISK_BYTES", "none")
    g = np.random.default_rng(55)
    policies.clear_workload_cache()
    policies.reset_cache_stats()
    for _ in range(4):
        policies.index_table(generate_workload(g, 5), "sr")
    assert len(list(tmp_path.iterdir())) == 4  # nothing evicted
    assert "disk_evictions" not in policies.cache_stats()


def test_ensure_cache_dir_respects_explicit_setting(tmp_path, monkeypatch):
    explicit = tmp_path / "explicit"
    monkeypatch.setenv("REPRO_CACHE_DIR", str(explicit))
    assert policies.ensure_cache_dir() == str(explicit)
    assert explicit.is_dir()
    # unset: falls back to the default location (created on demand)
    monkeypatch.delenv("REPRO_CACHE_DIR")
    monkeypatch.setenv("XDG_CACHE_HOME", str(tmp_path / "xdg"))
    got = policies.ensure_cache_dir()
    assert got == str(tmp_path / "xdg" / "repro-workloads")
    assert os.environ["REPRO_CACHE_DIR"] == got
