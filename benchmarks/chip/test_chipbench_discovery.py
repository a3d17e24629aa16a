"""The harness finds a configuration, traffic mix and per-layer metric
that a later change adds as new files, by their names in BENCHMARK.json."""

from __future__ import annotations

import json
import shutil

import harness


def test_new_files_are_found_by_name(tiny_root, tmp_path):
    root = tmp_path
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    shutil.copytree(tiny_root / "bench", root / "bench")
    config = json.loads((root / "bench" / "configs" / "tiny.json").read_text())
    config.update(name="tiny-m3", num_stages=3)
    (root / "bench" / "configs" / "tiny-m3.json").write_text(json.dumps(config))
    (root / "bench" / "traffic" / "rank-only.json").write_text(json.dumps(
        {"algorithms": ["rank"], "warmup_trials": 1, "check_combinations": 64}))
    (root / "bench" / "metrics" / "trials_counted.py").write_text(
        "def read(ctx):\n    return float(ctx.trials)\n")
    spec["configs"].append({"name": "tiny-m3", "source": "test", "reduced": [], "why": "test",
                            "file": "bench/configs/tiny-m3.json"})
    spec["workloads"].append({"name": "tiny-m3-rank", "config": "tiny-m3",
                              "traffic": "rank-only", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "trials_counted", "unit": "trials", "better": "higher",
                              "source": "host_clock", "layer": "device", "moves": "trials_per_s",
                              "workloads": ["tiny-m3-rank"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell(root, "tiny-m3-rank")
    assert cell.config["num_stages"] == 3
    assert cell.traffic["algorithms"] == ["rank"]
    assert [m["name"] for m in cell.per_layer] == ["trials_counted"]
    assert harness.check_count(cell) == len(cell.config["workload_sets"])
    reader = harness.load_reader(cell.bench_dir, "trials_counted")
    assert reader(type("ctx", (), {"trials": 7})) == 7.0
    # Cells that the new metric does not list do not report it.
    other = harness.load_cell(root, "tiny-no-optimal")
    assert "trials_counted" not in [m["name"] for m in other.per_layer]


def test_new_cell_runs(tiny_root):
    result, _ = harness.run(harness.load_cell(tiny_root, "tiny-no-optimal"), 5, 0.2, False,
                            require_tpu=False)
    assert result["correct"] is True
    assert set(result["checks"]) == {"static_rel_err", "dynamic_rel_err"}
