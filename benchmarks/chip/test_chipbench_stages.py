"""The stage-sweep cell (``paper-n5-m8-stages``, Table XIV at N = 5, M = 8):
its files as the harness loads them, its job groups, its readers, and a
run at a tiny size on the CPU."""

from __future__ import annotations

import math
from types import SimpleNamespace

import numpy as np
import pytest

import harness
import workgen
from harness import HERE, ROOT, load_reader

CELL = "paper-n5-m8-stages"
# Each tiled reader and the reader of the same quantity in the other cells.
TILED = {
    "kernel_ms_per_trial.static_enum_tiled": "kernel_ms_per_trial.static_enum",
    "static_enum_tiled_roofline": "static_enum_roofline",
    "op_ms_per_trial.static_tiled": "op_ms_per_trial.static",
}


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(ROOT, CELL)


def test_cell_loads(cell):
    assert cell.chips == 1
    assert (cell.config["n_jobs"], cell.config["num_stages"]) == (5, 8)
    assert [ws["set"] for ws in cell.config["workload_sets"]] == [1]
    assert cell.traffic["algorithms"] == ["optimal", "rank"]
    assert [m["name"] for m in cell.end_to_end] == ["trials_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == list(TILED)
    # 2**19 combinations of reference work are 16 trials at K = 8**5.
    assert harness.check_count(cell) == 16


def test_job_groups_at_eight_stages(cell):
    for trial in range(4):
        sizes, probs = workgen.trial_group(cell.config, 2**31 + 11, trial)
        assert sizes.shape == probs.shape == (5, 8)
        assert np.all(np.diff(sizes, axis=1) > 0)
        assert np.all(probs >= 0)
        np.testing.assert_allclose(probs.sum(axis=1), 1.0, rtol=1e-12)
        assert np.all((probs[:, -1] > 1e-5) & (probs[:, -1] < 1 - 1e-5))


def _ctx(cell, spans, kernels):
    return SimpleNamespace(
        trials=4, spans=spans, trace=SimpleNamespace(op_seconds=lambda: kernels),
        config=cell.config, algorithms=tuple(cell.traffic["algorithms"]),
        peaks=cell.peaks["TPU v5 lite"], notes={},
    )


def test_tiled_readers_read_what_their_siblings_read(cell):
    spans = {"sojourn_eval.static.enum.pallas": 0.02, "sojourn_eval.dynamic.enum.pallas": 1.0,
             "op_phase.static.pallas.sync": 0.01}
    ctx = _ctx(cell, spans, {"sojourn_enum": 0.004, "dynamic_sojourn_enum": 1.0})
    values = {name: load_reader(HERE, name)(ctx) for name in TILED}
    for name, base in TILED.items():
        assert values[name] == load_reader(HERE, base)(ctx)
    assert values["kernel_ms_per_trial.static_enum_tiled"] == pytest.approx(1.0)
    assert values["op_ms_per_trial.static_tiled"] == pytest.approx(5.0)
    # 5! orders and RANK's, 8**5 combinations, 5 positions, two adds each.
    ops = 2 * (math.factorial(5) + 1) * 8**5 * 5
    least = ops * 4 / cell.peaks["TPU v5 lite"]["vpu_ops_per_s"]
    assert values["static_enum_tiled_roofline"] == pytest.approx(100 * least / 0.004)
    assert ctx.notes["static_enum_tiled_roofline"] == "ops"


def test_tiled_readers_silent_without_the_kernel_or_spans(cell):
    ctx = _ctx(cell, {"sojourn_eval.static.enum.xla": 0.02}, {})
    assert all(load_reader(HERE, name)(ctx) is None for name in TILED)


def test_tiny_run_is_correct(cell):
    # N = 3 keeps the run small (K = 8**3 = 512); M = 8, set 1 and the
    # traffic are the cell's own.
    tiny = SimpleNamespace(**{**vars(cell), "config": {**cell.config, "n_jobs": 3}})
    result, _ = harness.run(tiny, 2**31 + 5, 0.2, False, require_tpu=False)
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["checks"]) == {"optimal_rel_err", "static_rel_err"}
