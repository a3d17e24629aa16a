"""The online setting on W servers through the real entry point, on the CPU.

A tiny configuration naming ``n_servers`` = 2 is driven through the
harness and ``evaluate_many(jobs, algorithms, rng, n_servers=2)`` with no
stub: the online RANK and SR agree with the float64 W-server reference.
The same run with the server count dropped at the harness scores one
server and is not correct.  The readers of the ``exact-n21-w3`` cell's
per-layer metrics give the expected numbers on hand-built contexts.
"""

from __future__ import annotations

import json
import shutil
from types import SimpleNamespace

import pytest

import harness
from harness import HERE

SECONDS = 0.3
CELL = "tiny-w2-rank-sr"
NEW_METRICS = ("kernel_ms_per_trial.dynamic_enum_servers", "dynamic_enum_servers_roofline",
               "op_ms_per_trial.dynamic_servers", "host_ms_per_trial.online")


@pytest.fixture(scope="module")
def online_root(tiny_root, tmp_path_factory):
    """The tiny tree with a two-server configuration under the
    ``online-rank-sr`` traffic, reporting the online cell's metrics."""
    root = tmp_path_factory.mktemp("online")
    shutil.copytree(tiny_root / "bench", root / "bench")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    config = json.loads((root / "bench" / "configs" / "tiny.json").read_text())
    config.update(name="tiny-w2", n_servers=2)
    (root / "bench" / "configs" / "tiny-w2.json").write_text(json.dumps(config))
    spec["configs"].append({"name": "tiny-w2", "source": "test", "reduced": ["n_jobs"],
                            "file": "bench/configs/tiny-w2.json", "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny-w2", "traffic": "online-rank-sr",
                              "chips": 1, "why": "test"})
    for m in spec["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"] = [CELL]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def run(root, trace=False, seed=2**31 + 13):
    result, _ = harness.run(harness.load_cell(root, CELL), seed, SECONDS, trace,
                            require_tpu=False)
    return result


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_online_run_through_evaluate_many_is_correct(online_root, trace):
    result = run(online_root, trace)
    assert result["correct"] is True and result["failed"] == 0
    assert list(result["checks"]) == ["dynamic_rel_err"]
    assert result["checks"]["dynamic_rel_err"]["value"] < 1e-9
    if trace:
        # On the CPU the op runs on the XLA path: only the evaluator's
        # online span is there to read.
        assert set(result["metrics"]) == {"host_ms_per_trial.online"}
        assert result["metrics"]["host_ms_per_trial.online"]["value"] > 0


def test_online_run_with_the_count_dropped_at_the_harness_is_not_correct(
        online_root, monkeypatch):
    init = harness.Bench.__init__

    def one_server(self, *args, **kwargs):
        init(self, *args, **kwargs)
        assert self.servers == {"n_servers": 2}
        self.servers = {}

    monkeypatch.setattr(harness.Bench, "__init__", one_server)
    result = run(online_root)
    assert result["correct"] is False and result["failed"] == 0
    check = result["checks"]["dynamic_rel_err"]
    assert check["value"] > check["limit"]


roofline = harness.load_reader(HERE, "dynamic_enum_servers_roofline").__globals__
PEAKS = {"vpu_ops_per_s": 4e12, "hbm_bytes_per_s": 8e11}


def _ctx(seconds=None, spans=None, trials=10, n_servers=3, algorithms=("rank", "sr")):
    config = {"n_jobs": 21, "num_stages": 2}
    if n_servers is not None:
        config["n_servers"] = n_servers
    ops = {} if seconds is None else {"dynamic_sojourn_enum": seconds}
    return SimpleNamespace(trials=trials, spans=spans or {}, peaks=PEAKS, notes={},
                           config=config, algorithms=algorithms,
                           trace=SimpleNamespace(op_seconds=lambda: ops))


def read(metric, ctx):
    return harness.load_reader(HERE, metric)(ctx)


def test_servers_work_counts_lockstep_and_seating():
    # N = 21, M = 2, W = 3: K = 2**21 combinations, 42 events of two
    # 21-job scans, and 3 seating scans of 21 jobs at t = 0.
    ops, nbytes = roofline["work"](21, 2, 2, 3)
    assert ops == 2 * 2**21 * (2 * 42 * 21 + 3 * 21)
    assert nbytes == 4 * (2 * 21 * 2 + 2 * (21 * 2 + 2))
    # More servers than jobs seat every job once.
    assert roofline["work"](4, 2, 1, 9)[0] == 2**4 * (2 * 8 * 4 + 4 * 4)


@pytest.mark.parametrize("algorithms,w,policies", [
    (("rank", "sr"), 3, 2), (("rank", "sr", "serpt"), 2, 3),
    (("optimal", "rank", "serpt", "sr", "random"), 1, 2), (("rank",), 1, 0),
])
def test_servers_index_policies(algorithms, w, policies):
    assert roofline["index_policies"](algorithms, w) == policies


def test_servers_roofline_is_least_time_over_kernel_time():
    ctx = _ctx(seconds=0.5)
    ops, nbytes = roofline["work"](21, 2, 2, 3)
    least = max(ops * 10 / PEAKS["vpu_ops_per_s"], nbytes * 10 / PEAKS["hbm_bytes_per_s"])
    assert read("dynamic_enum_servers_roofline", ctx) == pytest.approx(100 * least / 0.5)
    assert ctx.notes == {"dynamic_enum_servers_roofline": "ops"}
    # One server: RANK is a static order and not counted.
    one = _ctx(seconds=0.5, n_servers=None)
    ops1, _ = roofline["work"](21, 2, 1, 1)
    assert read("dynamic_enum_servers_roofline", one) == pytest.approx(
        100 * ops1 * 10 / PEAKS["vpu_ops_per_s"] / 0.5)


@pytest.mark.parametrize("ctx", [_ctx(), _ctx(seconds=0.5, trials=0),
                                 _ctx(seconds=0.5, n_servers=None, algorithms=("rank",))],
                         ids=["no-kernel", "no-trials", "no-index-policy"])
def test_servers_roofline_silent_without_its_inputs(ctx):
    assert read("dynamic_enum_servers_roofline", ctx) is None


def test_kernel_and_op_readers_on_servers():
    spans = {"sojourn_eval.dynamic.enum.pallas": 0.3, "sojourn_eval.dynamic.enum.xla": 9.0,
             "sojourn_eval.static.enum.pallas": 9.0}
    ctx = _ctx(seconds=0.2, spans=spans)
    assert read("kernel_ms_per_trial.dynamic_enum_servers", ctx) == pytest.approx(20.0)
    assert read("op_ms_per_trial.dynamic_servers", ctx) == pytest.approx(30.0)
    assert read("kernel_ms_per_trial.dynamic_enum_servers", _ctx()) is None
    assert read("op_ms_per_trial.dynamic_servers", _ctx(seconds=0.2)) is None


def test_host_online_reader():
    spans = {"evaluate_many": 1.0, "evaluate.online": 0.5, "cache.lookup": 0.01,
             "sojourn_eval.dynamic.enum.pallas": 0.3, "op_phase.dynamic.pallas.sync": 0.2}
    assert read("host_ms_per_trial.online", _ctx(spans=spans, trials=2)) == pytest.approx(100.0)
    # A program without the online span, or a window without trials.
    assert read("host_ms_per_trial.online", _ctx(spans={"evaluate_many": 1.0})) is None
    assert read("host_ms_per_trial.online", _ctx(spans=spans, trials=0)) is None
