"""The benchmark's reference and generator, against the scheduler's own
dense oracles at small sizes on the CPU.

The reference shares no code with the program; here it is checked
against ``repro.kernels.sojourn_eval.ref`` and ``repro.core.policies``,
which the program's own tests tie to the kernels.
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
