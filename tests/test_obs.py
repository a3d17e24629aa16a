"""Observability tests: trace recorder, metrics registry, profiling hooks.

The load-bearing properties:

* attaching a :class:`TraceRecorder` NEVER changes scheduling results
  (bit-identical sojourns, traced vs untraced);
* the per-record state snapshots satisfy the scheduler invariants at
  every event under fault / straggler / resize interleavings;
* observer batching is invisible (batch_size 1 and 4096 produce the
  identical record stream);
* the Chrome-trace export passes the schema validator and the Gantt
  lanes never overlap.
"""

import json
import warnings

import numpy as np
import pytest

from repro.cluster.faults import FaultConfig
from repro.cluster.manager import ClusterManager, TrainingJob
from repro.core.des.events import (
    EV_DISPATCH,
    EVENT_NAMES,
    RECORD_FIELDS,
    TraceEvent,
)
from repro.core.jobs import generate_workload
from repro.core.simulator import simulate
from repro.obs import (
    MetricsRegistry,
    TraceRecorder,
    format_snapshot,
    profiling,
    validate_chrome_trace,
)
from repro.core.trace import synthesize_trace


def _trace_jobs(n=200, seed=0):
    rng = np.random.default_rng(seed)
    return synthesize_trace(rng, n_jobs=n, duration_days=0.4)


def _faulty_manager(recorder=None, metrics=None, seed=12):
    rng = np.random.default_rng(seed)
    spec = generate_workload(
        rng, 80, num_stages=3, workload_set=1,
        arrivals=np.sort(rng.uniform(0, 50.0, 80)),
    )
    tj = [TrainingJob(spec=s) for s in spec]
    cm = ClusterManager(
        tj, 8, rng=np.random.default_rng(seed),
        fault_cfg=FaultConfig(mtbf_hours=0.004, restart_overhead=0.1,
                              straggler_prob=0.2, straggler_slowdown=5.0,
                              deadline_factor=2.0),
        nodes_per_server=8,
        resize_events=[(2.0, 16), (6.0, 3), (10.0, 10)],
    )
    return cm, cm.run(recorder=recorder, metrics=metrics)


# ---------------------------------------------------------------------------
# tracing never perturbs results
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_servers", [1, 2, 3])
def test_recorder_leaves_simulate_bit_identical(n_servers):
    jobs = _trace_jobs()
    rec = TraceRecorder()
    traced = simulate(jobs, n_servers, "rank", recorder=rec)
    plain = simulate(jobs, n_servers, "rank")
    assert traced.mean_sojourn_successful == pytest.approx(
        plain.mean_sojourn_successful, rel=1e-9, abs=0.0
    )
    assert traced.mean_sojourn_all == pytest.approx(
        plain.mean_sojourn_all, rel=1e-9, abs=0.0
    )
    assert traced.makespan == plain.makespan
    assert traced.n_success == plain.n_success
    assert len(rec) > 0 and rec.n_runs == 1


def test_recorder_leaves_manager_bit_identical_under_faults():
    _, traced = _faulty_manager(recorder=TraceRecorder())
    _, plain = _faulty_manager()
    assert traced.mean_sojourn_successful == plain.mean_sojourn_successful
    assert traced.makespan == plain.makespan
    assert traced.restarts == plain.restarts


# ---------------------------------------------------------------------------
# invariants from the per-record state snapshots
# ---------------------------------------------------------------------------


def test_record_invariants_under_faults_and_resize():
    rec = TraceRecorder()
    _, res = _faulty_manager(recorder=rec)
    assert res.restarts > 0  # faults really interleaved
    counts = rec.counts()
    assert counts["restart"] == res.restarts
    assert counts["resize"] == 3
    assert counts["complete"] + counts["cancel"] == res.n_jobs
    for ev in rec.events():
        assert ev.queue_len >= 0, ev
        assert ev.free >= 0, ev
        assert ev.busy + ev.free <= ev.target, ev
        assert ev.time >= 0.0, ev


def test_record_times_are_nondecreasing():
    rec = TraceRecorder()
    simulate(_trace_jobs(), 3, "serpt", recorder=rec)
    times = [r[0] for r in rec.records]
    assert all(a <= b for a, b in zip(times, times[1:]))


def test_batch_size_is_invisible():
    jobs = _trace_jobs(120, seed=3)
    small, big = TraceRecorder(batch_size=1), TraceRecorder(batch_size=4096)
    simulate(jobs, 2, "rank", recorder=small)
    simulate(jobs, 2, "rank", recorder=big)
    assert small.records == big.records


def test_typed_event_round_trip():
    rec = TraceRecorder()
    simulate(_trace_jobs(40, seed=5), 2, "rank", recorder=rec)
    for r, ev in zip(rec.records, rec.events()):
        assert ev.as_record() == r
        assert ev.name == EVENT_NAMES[r[1]]
    assert len(RECORD_FIELDS) == len(rec.records[0])
    assert TraceEvent.from_record(rec.records[0]).time == rec.records[0][0]


# ---------------------------------------------------------------------------
# exports: Gantt, time series, Chrome trace
# ---------------------------------------------------------------------------


def test_gantt_lanes_never_overlap():
    rec = TraceRecorder()
    _faulty_manager(recorder=rec)
    rows = rec.gantt()
    dispatches = sum(1 for r in rec.records if r[1] == EV_DISPATCH)
    assert len(rows) == dispatches  # every dispatched stage span closed
    by_lane = {}
    for row in rows:
        assert row["end"] >= row["start"]
        by_lane.setdefault(row["server"], []).append((row["start"], row["end"]))
    assert len(by_lane) <= 16  # lane count bounded by peak target
    for spans in by_lane.values():
        spans.sort()
        for (_, e0), (s1, _) in zip(spans, spans[1:]):
            assert s1 >= e0, "overlapping spans on one server lane"


def test_series_shapes_and_values():
    rec = TraceRecorder()
    simulate(_trace_jobs(60, seed=7), 2, "rank", recorder=rec)
    qd = rec.queue_depth_series()
    ut = rec.utilization_series()
    assert qd.shape == (len(rec), 2) and ut.shape == (len(rec), 4)
    assert (qd[:, 1] >= 0).all()
    assert (ut[:, 1] <= ut[:, 3]).all()  # busy <= target


def test_chrome_trace_schema_and_validator(tmp_path):
    rec = TraceRecorder()
    _faulty_manager(recorder=rec)
    path = tmp_path / "trace.json"
    obj = rec.write_chrome_trace(str(path))
    with open(path) as f:
        assert json.load(f) == obj
    report = validate_chrome_trace(obj)
    assert report["events"] == len(obj["traceEvents"])
    assert report["by_phase"]["X"] == len(rec.gantt())
    assert obj["otherData"]["schema"] == "repro.obs/chrome-trace/v1"
    assert obj["otherData"]["counts"] == rec.counts()
    # validator actually rejects malformed traces
    with pytest.raises(ValueError, match="missing"):
        validate_chrome_trace({"traceEvents": [{"ph": "X", "name": "x"}]})
    with pytest.raises(ValueError, match="unknown phase"):
        validate_chrome_trace({"traceEvents": [{"ph": "?"}]})
    with pytest.raises(ValueError, match="traceEvents array"):
        validate_chrome_trace({})


def test_recorder_accumulates_across_runs_and_clears():
    rec = TraceRecorder()
    jobs = _trace_jobs(30, seed=9)
    simulate(jobs, 2, "rank", recorder=rec)
    n1 = len(rec)
    simulate(jobs, 2, "sr", recorder=rec)
    assert len(rec) > n1 and rec.n_runs == 2
    rec.clear()
    assert len(rec) == 0 and rec.n_runs == 0


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_metrics_registry_basics(tmp_path):
    reg = MetricsRegistry()
    reg.counter("a").inc()
    reg.counter("a").inc(4)
    reg.gauge("b").set(2.5)
    h = reg.histogram("c")
    h.observe_many(np.arange(100.0))
    h.observe(100.0)
    snap = reg.snapshot()
    assert snap["counters"]["a"] == 5
    assert snap["gauges"]["b"] == 2.5
    hs = snap["histograms"]["c"]
    assert hs["count"] == 101 and hs["min"] == 0.0 and hs["max"] == 100.0
    assert hs["p50"] == pytest.approx(50.0)
    assert hs["p99"] == pytest.approx(99.0)
    with pytest.raises(TypeError):
        reg.gauge("a")  # name already bound to a Counter
    path = tmp_path / "m.json"
    reg.to_json(str(path), run={"note": 1})
    doc = json.loads(path.read_text())
    assert doc["run"] == {"note": 1} and doc["counters"]["a"] == 5
    text = format_snapshot(snap)
    assert "a" in text and "p50" in text


def test_metrics_timer_records_seconds():
    reg = MetricsRegistry()
    with reg.timer("op"):
        pass
    snap = reg.snapshot()["histograms"]["op.seconds"]
    assert snap["count"] == 1 and snap["max"] >= 0.0


def test_simulate_fills_standard_metrics():
    reg = MetricsRegistry()
    res = simulate(_trace_jobs(150, seed=11), 3, "rank", metrics=reg)
    snap = reg.snapshot()
    assert snap["counters"]["jobs.total"] == 150
    assert snap["counters"]["jobs.successful"] == res.n_success
    assert snap["counters"]["jobs.canceled"] == 150 - res.n_success
    assert snap["histograms"]["sojourn.successful"]["count"] == res.n_success
    assert snap["gauges"]["run.makespan"] == res.makespan
    assert 0.0 < snap["gauges"]["servers.busy_fraction"] <= 1.0
    # no faults: nothing aborted, waste is exactly canceled-job service
    assert snap["gauges"]["work.aborted_time"] == 0.0
    assert snap["gauges"]["work.wasted"] >= 0.0
    assert snap["gauges"]["work.wasted"] <= snap["gauges"]["work.busy_time"]


def test_manager_fills_metrics_with_fault_counters():
    reg = MetricsRegistry()
    _, res = _faulty_manager(metrics=reg)
    snap = reg.snapshot()
    assert snap["counters"]["jobs.restarts"] == res.restarts > 0
    assert snap["gauges"]["work.aborted_time"] > 0.0
    assert snap["gauges"]["work.wasted"] >= snap["gauges"]["work.aborted_time"]
    assert 0.0 < snap["gauges"]["servers.busy_fraction"] <= 1.0


# ---------------------------------------------------------------------------
# legacy observer shim + profiling
# ---------------------------------------------------------------------------


def test_legacy_observer_warns_but_still_works():
    seen = []
    with pytest.warns(DeprecationWarning, match="deprecated"):
        simulate(_trace_jobs(20, seed=13), 2, "rank",
                 recorder=lambda eng, now: seen.append(now))
    assert seen and all(a <= b for a, b in zip(seen, seen[1:]))


def test_recorder_is_not_shimmed():
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        simulate(_trace_jobs(20, seed=13), 2, "rank", recorder=TraceRecorder())


def test_profiling_spans_gate_on_enable():
    from repro.obs.metrics import get_registry

    was = profiling.enabled()
    try:
        profiling.enable(False)
        reg = MetricsRegistry()
        with profiling.span("off.case", registry=reg):
            pass
        assert reg.snapshot()["histograms"] == {}
        profiling.enable(True)
        with profiling.span("on.case", registry=reg):
            pass
        snap = reg.snapshot()
        assert snap["histograms"]["prof.on.case.seconds"]["count"] == 1
        assert snap["counters"]["prof.on.case.calls"] == 1
        with profiling.span("outer.case"):
            with profiling.span("probe.case"):
                pass
        d = get_registry().snapshot()
        assert d["counters"]["prof.probe.case.calls"] >= 1
        h = d["histograms"]
        outer = h["prof.outer.case.seconds"]["max"]
        assert 0.0 < h["prof.probe.case.seconds"]["max"] <= outer
        assert h["prof.outer.case.self_s"]["max"] < outer
        profiling.enable(False)
        with profiling.span("after.case"):
            pass
        assert "prof.after.case.calls" not in get_registry().snapshot()["counters"]
    finally:
        profiling.enable(was)


def test_profiled_sojourn_eval_records_span():
    from repro.core.evaluator import expected_sojourn_static
    from repro.core.policies import rank_order
    from repro.obs.metrics import get_registry

    jobs = generate_workload(np.random.default_rng(17), 5)
    was = profiling.enabled()
    try:
        profiling.enable(True)
        expected_sojourn_static(jobs, rank_order(jobs), impl="xla")
        snap = get_registry().snapshot()
        keys = [k for k in snap["histograms"]
                if k.startswith("prof.sojourn_eval.static.enum")]
        assert keys, snap["histograms"].keys()
    finally:
        profiling.enable(was)


# ---------------------------------------------------------------------------
# report CLI end-to-end
# ---------------------------------------------------------------------------


def test_report_cli_end_to_end(tmp_path, capsys):
    from repro.obs.report import main

    rc = main([
        "--jobs", "60", "--servers", "4", "--validate",
        "--resize", "20000", "2", "--out", str(tmp_path),
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "trace schema OK" in out and "run metrics" in out
    trace_obj = json.loads((tmp_path / "trace.json").read_text())
    validate_chrome_trace(trace_obj)
    doc = json.loads((tmp_path / "metrics.json").read_text())
    assert doc["counters"]["jobs.total"] == 60
    assert doc["run"]["counts"]["resize"] == 1
    assert "workload_cache" in doc and "hit_rate" in doc["workload_cache"]


def test_report_cli_overhead_bench_small(tmp_path, capsys):
    from repro.obs.report import main

    rc = main([
        "--jobs", "80", "--servers", "4", "--bench-overhead",
        "--out", str(tmp_path),
    ])
    assert rc == 0
    doc = json.loads((tmp_path / "metrics.json").read_text())
    ov = doc["run"]["overhead"]
    assert ov["events"] > 0 and ov["max_relerr"] <= 1e-9
