"""Work functions of the roofline readers at known shapes."""

from __future__ import annotations

import math
from types import SimpleNamespace

import pytest

from harness import HERE, load_reader

static_reader = load_reader(HERE, "static_enum_roofline")
dynamic_reader = load_reader(HERE, "dynamic_enum_roofline")
static_work = static_reader.__globals__["work"]
orders_per_trial = static_reader.__globals__["orders_per_trial"]
dynamic_work = dynamic_reader.__globals__["work"]


def test_static_work_counts_two_ops_per_order_combination_position():
    # N = 8, M = 2: K = 256 real combinations, 8 positions, 2 ops each.
    ops, nbytes = static_work(8, 2, 40322)
    assert ops == 2 * 40322 * 256 * 8
    assert nbytes == 4 * (2 * 8 * 2 + 40322 * (8 + 2))


def test_static_orders_per_trial():
    algs = ("optimal", "rank", "serpt", "sr", "random")
    assert orders_per_trial(8, algs) == math.factorial(8) + 2
    assert orders_per_trial(17, algs[1:]) == 2


def test_dynamic_work_counts_two_ops_per_policy_combination_event_job():
    # N = 17, M = 2: K = 131,072 combinations, 34 lockstep events, 17 jobs.
    ops, nbytes = dynamic_work(17, 2, 2)
    assert ops == 2 * 2 * 131072 * 34 * 17
    assert nbytes == 4 * (2 * 17 * 2 + 2 * (17 * 2 + 2))


@pytest.mark.parametrize("reader,kernel", [(static_reader, "sojourn_enum"),
                                           (dynamic_reader, "dynamic_sojourn_enum")])
def test_roofline_is_least_time_over_kernel_time(reader, kernel):
    peaks = {"vpu_ops_per_s": 4e12, "hbm_bytes_per_s": 8e11}
    trace = SimpleNamespace(op_seconds=lambda: {kernel: 2.0})
    ctx = SimpleNamespace(trials=10, trace=trace, peaks=peaks, notes={},
                          config={"n_jobs": 8, "num_stages": 2},
                          algorithms=("optimal", "rank", "sr", "serpt", "random"))
    value = reader(ctx)
    if kernel == "sojourn_enum":
        ops, nbytes = static_work(8, 2, math.factorial(8) + 2)
    else:
        ops, nbytes = dynamic_work(8, 2, 2)
    least = max(ops * 10 / 4e12, nbytes * 10 / 8e11)
    assert value == pytest.approx(100 * least / 2.0)
    assert list(ctx.notes.values()) == ["ops"]


def test_roofline_silent_without_its_kernel():
    ctx = SimpleNamespace(trials=10, trace=SimpleNamespace(op_seconds=dict), peaks={}, notes={},
                          config={"n_jobs": 8, "num_stages": 2}, algorithms=("rank",))
    assert static_reader(ctx) is None
    assert dynamic_reader(ctx) is None
