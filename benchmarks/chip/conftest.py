"""Fixtures of the chip benchmark's CPU tests: a small benchmark tree.

The tests drive the harness on the CPU at a size a test run can hold:
N = 4 jobs of M = 2 checkpoints under the real workload sets, traffic
files, metric readers, limits and peaks.  Nothing here touches a TPU.
"""

from __future__ import annotations

import json
import shutil
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent

TINY_CELLS = {"tiny-with-optimal": "study-with-optimal", "tiny-no-optimal": "study-no-optimal"}


def build_root(root: Path) -> Path:
    """A benchmark tree under ``root`` with one tiny configuration and two cells."""
    spec = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    bench = root / "bench"
    for sub in ("traffic", "metrics"):
        shutil.copytree(HERE / sub, bench / sub)
    for name in ("limits.json", "peaks.json"):
        shutil.copy(HERE / name, bench / name)
    config = json.loads((HERE / "configs" / "paper-iv-n8-m2.json").read_text())
    config.update(name="tiny", n_jobs=4)
    (bench / "configs").mkdir()
    (bench / "configs" / "tiny.json").write_text(json.dumps(config))
    cells = list(TINY_CELLS)
    spec.update(
        paths=["bench"],
        configs=[{"name": "tiny", "source": "test", "file": "bench/configs/tiny.json",
                  "reduced": ["n_jobs"], "why": "test"}],
        workloads=[{"name": c, "config": "tiny", "traffic": t, "chips": 1, "why": "test"}
                   for c, t in TINY_CELLS.items()],
    )
    for m in spec["per_layer"]:
        m["workloads"] = cells
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return build_root(tmp_path_factory.mktemp("bench"))
