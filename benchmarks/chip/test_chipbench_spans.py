"""The program's spans on the profiler trace, and the readers of them.

A tiny ``evaluate_many`` under the JAX profiler on the CPU leaves the
program's ``prof.*`` annotations among the trace's host spans, properly
nested; each reader of them gives the expected number on a hand-built
context, and nothing where the program has no such span.
"""

from __future__ import annotations

from types import SimpleNamespace

import jax
import numpy as np
import pytest

import harness
import xplane
from harness import HERE


def read(metric, ctx):
    return harness.load_reader(HERE, metric)(ctx)


def _within(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_program_spans_nest_on_the_trace(tmp_path):
    from repro.core import evaluator
    from repro.core.jobs import generate_workload
    from repro.obs import profiling

    jobs = generate_workload(np.random.default_rng(2**33 + 1), 4)
    algorithms = ("optimal", "rank", "sr", "serpt", "random")
    evaluator.evaluate_many(jobs, algorithms, np.random.default_rng(1))  # compile
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    was = profiling.enabled()
    profiling.enable(True)
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
            evaluator.evaluate_many(jobs, algorithms, np.random.default_rng(1))
    finally:
        jax.profiler.stop_trace()
        profiling.enable(was)
    spans = [(s, e, n) for s, e, n in xplane.Trace(xplane.find(str(tmp_path))).host_spans
             if n.startswith("prof.")]

    def named(prefix):
        return [sp for sp in spans if sp[2].startswith(prefix)]

    (whole,) = named("prof.evaluate_many")
    ops_ = named("prof.sojourn_eval.")
    phases = named("prof.op_phase.")
    assert {n for _, _, n in ops_} == {"prof.sojourn_eval.static.enum.xla",
                                       "prof.sojourn_eval.dynamic.enum.xla"}
    assert len(ops_) == 5  # optimal, rank, random, sr, serpt
    assert named("prof.cache.lookup") and named("prof.optimal.orders")
    assert {n.rsplit(".", 1)[1] for _, _, n in phases} == {"prep", "put", "call", "sync"}
    assert all(_within(sp, whole) for sp in spans)
    for sp in phases:
        (op,) = [o for o in ops_ if _within(sp, o)]
        assert sp[2].split(".")[2] == op[2].split(".")[2]  # static or dynamic
    # The phases of one op call follow one another without overlap.
    for op in ops_:
        inside = sorted(sp for sp in phases if _within(sp, op))
        assert [sp[2].rsplit(".", 1)[1] for sp in inside][:4] == ["prep", "put", "call", "sync"]
        assert all(a[1] <= b[0] for a, b in zip(inside, inside[1:]))


def _ctx(spans=None, host=(), device=None, trials=2):
    trace = SimpleNamespace(
        start_ns=0, end_ns=1000, host_spans=[(0, 1000, xplane.WINDOW_SPAN), *host],
        device_ops={} if device is None else {"/device:TPU:0": device},
    )
    return SimpleNamespace(trials=trials, spans=spans or {}, trace=trace)


# Idle: [0, 100), [300, 600), [700, 1000).
DEVICE = [(100, 200, "sojourn_enum"), (150, 300, "sojourn_enum"), (600, 700, "copy")]
HOST = [
    (50, 900, "prof.evaluate_many"),
    (120, 450, "prof.sojourn_eval.static.enum.pallas"),
    (130, 140, "prof.op_phase.static.pallas.prep"),
    (500, 650, "prof.sojourn_eval.dynamic.enum.pallas"),
    (800, 850, "prof.optimal.orders"),
]


def test_idle_readers():
    ctx = _ctx(host=HOST, device=DEVICE)
    # Inside the ops: [300, 450) and [500, 600).
    assert read("idle_in_ops_ms_per_trial", ctx) == pytest.approx(250e-9 / 2 * 1e3)
    # Inside evaluate_many, outside the ops: [50, 100), [450, 500), [700, 900).
    assert read("idle_in_evaluator_ms_per_trial", ctx) == pytest.approx(300e-9 / 2 * 1e3)


@pytest.mark.parametrize("ctx", [
    _ctx(host=HOST),  # no device operations
    _ctx(device=DEVICE),  # no program spans: a program without them
    _ctx(host=HOST, device=DEVICE, trials=0),
], ids=["no-device", "no-spans", "no-trials"])
def test_idle_readers_give_nothing_without_their_inputs(ctx):
    assert read("idle_in_ops_ms_per_trial", ctx) is None
    assert read("idle_in_evaluator_ms_per_trial", ctx) is None


def test_span_sum_readers():
    spans = {
        "op_phase.static.pallas.prep": 0.004,
        "op_phase.dynamic.pallas.prep": 0.002,
        "op_phase.static.xla.prep": 9.0,
        "op_phase.static.pallas.sync": 0.010,
        "sojourn_eval.static.enum.pallas": 1.0,
        "optimal.orders": 0.01,
    }
    ctx = _ctx(spans=spans)
    assert read("phase_ms_per_trial.prep", ctx) == pytest.approx(3.0)
    assert read("phase_ms_per_trial.sync", ctx) == pytest.approx(5.0)
    assert read("phase_ms_per_trial.put", ctx) is None
    assert read("phase_ms_per_trial.call", ctx) is None
    assert read("optimal_orders_ms_per_trial", ctx) == pytest.approx(5.0)
    assert read("optimal_orders_ms_per_trial", _ctx(spans={"evaluate_many": 1.0})) is None
    assert read("phase_ms_per_trial.prep", _ctx(spans=spans, trials=0)) is None
