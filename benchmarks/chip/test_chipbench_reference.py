"""The benchmark's reference and generator, against the scheduler's own
dense oracles at small sizes on the CPU.

The reference shares no code with the program; here it is checked
against ``repro.kernels.sojourn_eval.ref`` and ``repro.core.policies``,
which the program's own tests tie to the kernels, on one server and on
W servers.
"""

from __future__ import annotations

import json

import ml_dtypes
import numpy as np
import pytest

import reference
import workgen
from harness import HERE

CONFIG = json.loads((HERE / "configs" / "paper-iv-n8-m2.json").read_text())


def group(n, m, trial, seed=2**40 + 3):
    return workgen.trial_group(dict(CONFIG, n_jobs=n, num_stages=m), seed, trial)


def as_jobs(sizes, probs):
    from repro.core.jobs import JobSpec

    return [JobSpec(sizes=sizes[i], probs=probs[i], job_id=i) for i in range(len(sizes))]


def test_generator_is_seeded_and_rotates_sets():
    a, b = group(5, 2, 3), group(5, 2, 3)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(group(5, 2, 4)[0], a[0])
    sizes, probs = a
    assert np.all(np.diff(sizes, axis=1) > 0) and np.allclose(probs.sum(axis=1), 1.0)
    # Set 2 (trial 1) draws success probabilities from distribution I.
    _, p = group(50, 2, 1)
    assert set(np.round(p[:, -1], 9)) <= {0.1, 0.2, 0.3, 0.4, 0.6, 0.7, 0.8, 0.9}


@pytest.mark.parametrize("trial", range(5))
@pytest.mark.parametrize("m", [2, 3])
def test_reference_matches_the_program_oracles(trial, m):
    from repro.core import policies
    from repro.kernels.sojourn_eval.ref import ref_sojourn, ref_sojourn_dynamic

    sizes, probs = group(5, m, trial)
    jobs = as_jobs(sizes, probs)
    padded = policies.padded_arrays(jobs)
    orders = reference.all_orders(5)
    want, _ = ref_sojourn(*padded, orders)
    np.testing.assert_allclose(reference.static_values(sizes, probs, orders), want, rtol=1e-12)
    np.testing.assert_array_equal(reference.rank_order(sizes, probs), policies.rank_order(jobs))
    for pol in ("sr", "serpt"):
        table = reference.index_table(sizes, probs, pol)
        np.testing.assert_allclose(table, policies.index_table(jobs, pol), rtol=1e-12)
        want, _ = ref_sojourn_dynamic(padded[1], policies.stage_durations(jobs), padded[2],
                                      policies.index_table(jobs, pol))
        assert reference.dynamic_value(sizes, probs, table) == pytest.approx(want, rel=1e-12)


def test_blocks_do_not_change_the_dynamic_value(monkeypatch):
    sizes, probs = group(9, 2, 4)
    table = reference.index_table(sizes, probs, "sr")
    whole = reference.dynamic_value(sizes, probs, table)
    monkeypatch.setattr(reference, "BLOCK", 64)
    assert reference.dynamic_value(sizes, probs, table) == pytest.approx(whole, rel=1e-13)


def test_bfloat16_reference_is_far_from_float64():
    sizes, probs = group(8, 2, 0)
    table = reference.index_table(sizes, probs, "sr")
    f64 = reference.dynamic_value(sizes, probs, table)
    bf16 = reference.dynamic_value(sizes, probs, table, ml_dtypes.bfloat16)
    assert abs(bf16 - f64) / f64 > 1e-4


@pytest.mark.parametrize("servers", [1, 2, 3])
@pytest.mark.parametrize("trial", range(5))
@pytest.mark.parametrize("m", [2, 3])
def test_w_server_reference_matches_the_program_oracles(trial, m, servers):
    from repro.core import policies
    from repro.kernels.sojourn_eval.ref import ref_sojourn_dynamic

    sizes, probs = group(5, m, trial)
    jobs = as_jobs(sizes, probs)
    _, padded_probs, num_stages = policies.padded_arrays(jobs)
    table = reference.index_table(sizes, probs, "rank")
    np.testing.assert_allclose(table, policies.rank_index_table(jobs), rtol=1e-12)
    for pol in ("sr", "serpt", "rank"):
        want, _ = ref_sojourn_dynamic(padded_probs, policies.stage_durations(jobs), num_stages,
                                      policies.index_table(jobs, pol), n_servers=servers)
        got = reference.dynamic_value(sizes, probs, reference.index_table(sizes, probs, pol),
                                      n_servers=servers)
        assert got == pytest.approx(want, rel=1e-12)


def single_server_value(sizes, probs, table, dtype=np.float64) -> float:
    """The one-server simulation the reference ran before it took W servers,
    kept verbatim: at every checkpoint the unfinished job with the least
    index (ties to the lower job) is served for one stage."""
    n, m = sizes.shape
    seg = np.diff(sizes, axis=1, prepend=0.0).astype(dtype)
    table = np.concatenate([table, np.full((n, 1), np.inf)], axis=1).astype(dtype)
    total = 0.0
    for lo in range(0, m**n, reference.BLOCK):
        stops, w = reference.combinations(probs, lo, lo + reference.BLOCK)
        rows = np.arange(len(stops))
        stage = np.zeros(stops.shape, np.int64)
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
            prio[r, jb] = table[jb, np.where(fin[busy], m, stage[r, jb])]
        succ = stops == m - 1
        cnt = succ.sum(axis=1)
        zero = np.zeros((), dtype)
        tot = np.where(succ, done_at, zero).sum(axis=1, dtype=dtype)
        mean = np.where(cnt > 0, tot / np.maximum(cnt, 1).astype(dtype), zero)
        total += float((w.astype(dtype) * mean).sum(dtype=dtype))
    return total


@pytest.mark.parametrize("n, m, trial", [(5, 3, 0), (9, 2, 1), (12, 2, 2), (8, 3, 3), (6, 4, 4)])
def test_one_server_is_bit_for_bit_the_single_server_loop(n, m, trial):
    sizes, probs = group(n, m, trial)
    for pol in ("sr", "serpt"):
        table = reference.index_table(sizes, probs, pol)
        for dtype in (np.float64, ml_dtypes.bfloat16):
            want = single_server_value(sizes, probs, table, dtype)
            assert reference.dynamic_value(sizes, probs, table, dtype) == want
            assert reference.dynamic_value(sizes, probs, table, dtype, n_servers=1) == want


def test_blocks_do_not_change_the_w_server_value(monkeypatch):
    sizes, probs = group(9, 2, 4)
    table = reference.index_table(sizes, probs, "rank")
    whole = reference.dynamic_value(sizes, probs, table, n_servers=3)
    monkeypatch.setattr(reference, "BLOCK", 64)
    assert reference.dynamic_value(sizes, probs, table, n_servers=3) == pytest.approx(
        whole, rel=1e-13)


def test_bfloat16_w_server_reference_is_far_from_float64():
    sizes, probs = group(8, 2, 0)
    for pol in ("sr", "rank"):
        table = reference.index_table(sizes, probs, pol)
        f64 = reference.dynamic_value(sizes, probs, table, n_servers=3)
        bf16 = reference.dynamic_value(sizes, probs, table, ml_dtypes.bfloat16, n_servers=3)
        assert abs(bf16 - f64) / f64 > 1e-2


def test_rank_index_is_infinite_where_success_is_impossible():
    sizes, probs = group(3, 3, 0)
    probs[1] = [0.5, 0.5, 0.0]
    with np.errstate(invalid="ignore"):  # no mass is left after job 1's second checkpoint
        table = reference.index_table(sizes, probs, "rank")
    assert np.all(np.isinf(table[1])) and np.all(np.isfinite(table[[0, 2]]))
