"""The trace reduction on a small recorded trace.

``testdata/one_trial.xplane.pb`` is the profiler trace of one traced
trial of ``paper-n8-with-optimal`` on one TPU v5 lite: OPTIMAL's ten
order batches and RANK's and RANDOM's orders through the static kernel,
SR and SERPT through the dynamic one.
"""

from __future__ import annotations

from collections import Counter

import numpy as np
import pytest

import xplane
from harness import HERE

PATH = HERE / "testdata" / "one_trial.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return xplane.Trace(str(PATH))


def test_op_names():
    assert xplane.op_name("%sojourn_enum.1 = (f32[4096,1,1]) custom-call(...)") == "sojourn_enum"
    name = "%dynamic_sojourn_enum = (f32[1,1,1]) custom-call()"
    assert xplane.op_name(name) == "dynamic_sojourn_enum"
    assert xplane.op_name("%copy-start.2 = (s32[8]) copy-start(...)") == "copy-start"


def test_kernels_found(trace):
    assert trace.n_devices == 1
    counts = Counter(name for _, _, name in trace.device_ops["/device:TPU:0"])
    assert counts["sojourn_enum"] == 12
    assert counts["dynamic_sojourn_enum"] == 2
    seconds = trace.op_seconds()
    assert seconds["sojourn_enum"] == pytest.approx(0.047182167, abs=1e-9)
    assert seconds["dynamic_sojourn_enum"] == pytest.approx(5.103e-06, abs=1e-12)


def test_busy_is_the_union_of_operations(trace):
    ops = trace.device_ops["/device:TPU:0"]
    # An independent union: mark every microsecond some operation covers.
    us = np.zeros(int((trace.end_ns - trace.start_ns) / 1e3) + 2, bool)
    for s, e, _ in ops:
        us[int((s - trace.start_ns) / 1e3): int(np.ceil((e - trace.start_ns) / 1e3))] = True
    assert trace.busy_s() == pytest.approx(us.sum() * 1e-6, abs=len(ops) * 2e-6)
    assert trace.busy_s() == pytest.approx(0.048321646, abs=1e-9)
    assert trace.window_s == pytest.approx(0.129661684, abs=1e-9)


def test_idle_gaps_fill_the_rest_of_the_window(trace):
    gaps = trace.idle_gaps()
    assert sum(gaps.values()) + trace.busy_s() == pytest.approx(trace.window_s, abs=1e-9)
    assert {"alg.optimal", "alg.rank", "alg.serpt", "alg.sr", "alg.random"} <= set(gaps)
    assert max(gaps, key=gaps.get) == "alg.optimal"
