"""Profiling spans: the disabled no-op, self time, the sojourn ops' phase
spans and the workload cache's spans."""

from __future__ import annotations

import itertools

import numpy as np
import pytest

from repro.core import policies
from repro.core.jobs import generate_workload
from repro.kernels.sojourn_eval import ops, sojourn_eval, sojourn_eval_dynamic
from repro.obs import MetricsRegistry, get_registry, profiling

PHASES = ("prep", "put", "call", "sync")


@pytest.fixture
def profiled():
    """Profiling on, into a cleared default registry."""
    was = profiling.enabled()
    reg = get_registry()
    reg.clear()
    profiling.enable(True)
    try:
        yield reg
    finally:
        profiling.enable(was)
        reg.clear()


def _jobs(seed=5, n=4):
    return generate_workload(np.random.default_rng(seed), n)


def test_disabled_span_is_one_shared_noop():
    was = profiling.enabled()
    try:
        profiling.enable(False)
        reg = MetricsRegistry()
        off = profiling.span("a.case", registry=reg)
        assert profiling.span("b.case") is off
        assert profiling.phases("c.case", "prep") is off
        with off, profiling.phases("c.case", "prep") as phase:
            phase.to("put")
        assert reg.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}
        assert not any("c.case" in k for k in get_registry().snapshot()["histograms"])
    finally:
        profiling.enable(was)


def test_self_time_excludes_direct_children_only():
    was = profiling.enabled()
    reg = MetricsRegistry()
    try:
        profiling.enable(True)
        with profiling.span("top", registry=reg):
            with profiling.span("mid", registry=reg):
                with profiling.span("leaf", registry=reg):
                    sum(range(20000))
    finally:
        profiling.enable(was)
    h = reg.snapshot()["histograms"]
    top, mid, leaf = (h[f"prof.{n}.seconds"]["sum"] for n in ("top", "mid", "leaf"))
    assert leaf <= mid <= top
    assert h["prof.leaf.self_s"]["sum"] == pytest.approx(leaf)
    assert h["prof.mid.self_s"]["sum"] == pytest.approx(mid - leaf)
    assert h["prof.top.self_s"]["sum"] == pytest.approx(top - mid)


def _static_case(mode):
    jobs = _jobs()
    sizes, probs, num_stages = policies.padded_arrays(jobs)
    orders = np.array(list(itertools.permutations(range(len(jobs)))), np.int32)
    kwargs = {"samples": (2**40 + 3, 300)} if mode == "mc" else {}
    return (sizes, probs, num_stages, orders), kwargs


def _dynamic_case(mode):
    jobs = _jobs()
    _, probs, num_stages = policies.padded_arrays(jobs)
    tables = np.stack([policies.index_table(jobs, p) for p in ("sr", "serpt", "sr")])
    kwargs = {"samples": (2**40 + 3, 300)} if mode == "mc" else {}
    return (probs, policies.stage_durations(jobs), num_stages, tables), kwargs


CASES = [("static", m, i) for m in ("enum", "mc") for i in ("xla", "interpret")]
CASES += [("dynamic", m, i) for m in ("enum", "mc") for i in ("xla", "interpret")]


@pytest.mark.parametrize("kind,mode,impl", CASES)
def test_op_phases_tile_each_batch(profiled, monkeypatch, kind, mode, impl):
    if kind == "static":
        # 24 orders in batches of 7: four batches, but one call of the
        # enumeration kernel, which _order_batch does not govern.
        monkeypatch.setattr(ops, "_order_batch", lambda *_: 7)
        args, kwargs = _static_case(mode)
        op, batches = sojourn_eval, 1 if (mode, impl) == ("enum", "interpret") else 4
    else:
        args, kwargs = _dynamic_case(mode)
        op, batches = sojourn_eval_dynamic, 3 if impl == "xla" else 1
    profiling.enable(False)
    plain = op(*args, impl=impl, **kwargs)
    profiling.enable(True)
    traced = op(*args, impl=impl, **kwargs)
    for a, b in zip(plain, traced):
        np.testing.assert_array_equal(a, b)
    snap = profiled.snapshot()
    prefix = f"prof.op_phase.{kind}.{impl}"
    for phase in PHASES:
        assert snap["counters"][f"{prefix}.{phase}.calls"] == batches
    h = snap["histograms"]
    whole = h[f"prof.sojourn_eval.{kind}.{mode}.{impl}.seconds"]["sum"]
    assert sum(h[f"{prefix}.{p}.seconds"]["sum"] for p in PHASES) <= whole
    # No phase is named under the op spans' prefix, which
    # host_ms_per_trial subtracts from the evaluator's time.
    op_spans = [k for k in h if k.startswith("prof.sojourn_eval.") and k.endswith(".seconds")]
    assert op_spans == [f"prof.sojourn_eval.{kind}.{mode}.{impl}.seconds"]


# (jobs, orders, orders a batch or None for the op's own, order blocks):
# every case is one kernel call.  24 orders at K = 16 are one block even
# when _order_batch says 7; 33 orders at K = 2048 (two combination tiles)
# are two blocks; all 7! = 5,040 orders at K = 128, more than
# _order_batch's 4,096, are ten blocks of 504.
ENUM_COUNTS = [(4, 24, 7, 1), (11, 33, None, 2), (7, 5040, None, 10)]


@pytest.mark.parametrize("n,p,batch,blocks", ENUM_COUNTS)
def test_enum_counts_orders_and_order_blocks(profiled, monkeypatch, n, p, batch, blocks):
    sizes, probs, num_stages = policies.padded_arrays(_jobs(n=n))
    rng = np.random.default_rng(n)
    orders = np.array([rng.permutation(n) for _ in range(p)], np.int32)
    if batch is not None:
        monkeypatch.setattr(ops, "_order_batch", lambda *_: batch)
    names = ("prof.sojourn_enum.orders", "prof.sojourn_enum.order_blocks",
             "prof.sojourn_enum.calls")
    profiling.enable(False)
    sojourn_eval(sizes, probs, num_stages, orders, impl="interpret")
    assert not set(names) & set(profiled.snapshot()["counters"])
    profiling.enable(True)
    sojourn_eval(sizes, probs, num_stages, orders, impl="interpret")
    counters = profiled.snapshot()["counters"]
    assert [counters[k] for k in names] == [p, blocks, 1]


# (jobs, stages, orders, grid steps): 24 orders at K = 8**4 = 4096 (four
# combination tiles) are one block of four steps; 70 orders at K = 2**11 =
# 2048 (two tiles) are three blocks of two.
GRID_STEPS = [(4, 8, 24, 4), (11, 2, 70, 6)]


@pytest.mark.parametrize("n,m,p,steps", GRID_STEPS)
def test_enum_counts_grid_steps(profiled, n, m, p, steps):
    jobs = generate_workload(np.random.default_rng(n), n, num_stages=m)
    sizes, probs, num_stages = policies.padded_arrays(jobs)
    rng = np.random.default_rng(n)
    orders = np.array([rng.permutation(n) for _ in range(p)], np.int32)
    name = "prof.sojourn_enum.grid_steps"
    profiling.enable(False)
    sojourn_eval(sizes, probs, num_stages, orders, impl="interpret")
    assert name not in profiled.snapshot()["counters"]
    profiling.enable(True)
    sojourn_eval(sizes, probs, num_stages, orders, impl="interpret")
    assert profiled.snapshot()["counters"][name] == steps


def test_count_adds_only_when_enabled():
    was = profiling.enabled()
    reg = MetricsRegistry()
    try:
        profiling.enable(False)
        profiling.count("a.case", 5, registry=reg)
        assert reg.snapshot()["counters"] == {}
        profiling.enable(True)
        profiling.count("a.case", 5, registry=reg)
        profiling.count("a.case", registry=reg)
    finally:
        profiling.enable(was)
    assert reg.snapshot()["counters"] == {"prof.a.case": 6}


def test_nested_cache_spans_count_each_moment_once(profiled, monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    jobs = _jobs(seed=91)
    policies.clear_workload_cache()

    def inner():
        return np.arange(3.0)

    def outer():
        return policies.workload_cached("test.inner", jobs, inner) + 1.0

    first = policies.workload_cached("test.outer", jobs, outer)
    snap = profiled.snapshot()
    h, calls = snap["histograms"], snap["counters"]
    names = ("lookup", "disk_load", "miss_compute", "disk_store", "disk_evict")
    for name in names:
        assert calls[f"prof.cache.{name}.calls"] == 2, name
    whole = h["prof.cache.lookup.seconds"]["max"]  # the outer lookup
    self_total = sum(h[f"prof.cache.{n}.self_s"]["sum"] for n in names)
    assert self_total <= whole + 1e-9
    assert h["prof.cache.lookup.seconds"]["sum"] > whole  # inclusive sums double-count
    # A memory hit is one lookup with nothing nested.
    stats = policies.cache_stats()["by_kind"]["test.outer"]
    assert np.array_equal(policies.workload_cached("test.outer", jobs, outer), first)
    calls = profiled.snapshot()["counters"]
    assert calls["prof.cache.lookup.calls"] == 3
    assert calls["prof.cache.miss_compute.calls"] == 2
    assert policies.cache_stats()["by_kind"]["test.outer"]["hits"] == stats["hits"] + 1
