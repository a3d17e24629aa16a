"""A configuration on W servers, driven through the harness on the CPU.

The configuration file names ``n_servers``; the harness hands it to
``evaluate_many`` and checks RANK, SR and SERPT as index policies on W
servers.  Until ``evaluate_many`` takes the server count, a stub scores
each policy through ``expected_sojourn_dynamic(..., n_servers=W)``, the
program's W-server path.  A configuration without the key calls
``evaluate_many`` as it always has.
"""

from __future__ import annotations

import json
import shutil

import pytest

import checks
import harness

SECONDS = 0.3
CELL = "tiny-w2-online"


@pytest.fixture(scope="module")
def w_root(tiny_root, tmp_path_factory):
    """The tiny tree with a configuration on two servers and a cell that
    scores the index policies on it."""
    root = tmp_path_factory.mktemp("servers")
    shutil.copytree(tiny_root / "bench", root / "bench")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    config = json.loads((root / "bench" / "configs" / "tiny.json").read_text())
    config.update(name="tiny-w2", n_servers=2)
    (root / "bench" / "configs" / "tiny-w2.json").write_text(json.dumps(config))
    (root / "bench" / "traffic" / "online.json").write_text(json.dumps(
        {"algorithms": ["rank", "serpt", "sr"], "warmup_trials": 1, "check_combinations": 64}))
    spec["configs"].append({"name": "tiny-w2", "source": "test", "reduced": ["n_jobs"],
                            "file": "bench/configs/tiny-w2.json", "why": "test"})
    spec["workloads"] += [
        {"name": CELL, "config": "tiny-w2", "traffic": "online", "chips": 1, "why": "test"},
        {"name": "tiny-w2-static", "config": "tiny-w2", "traffic": "study-with-optimal",
         "chips": 1, "why": "test"},
    ]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def _stub(keep_servers):
    """``evaluate_many`` as it will take ``n_servers``: every policy through
    the program's W-server path; with ``keep_servers`` false the count is
    dropped and the answers are one server's."""
    from repro.core import evaluator

    def evaluate_many(jobs, algs, rng, n_servers=1):
        w = n_servers if keep_servers else 1
        return {a: evaluator.expected_sojourn_dynamic(jobs, a, n_servers=w) for a in algs}
    return evaluate_many


def run(root, cell, seed=2**31 + 11):
    result, _ = harness.run(harness.load_cell(root, cell), seed, SECONDS, False, require_tpu=False)
    return result


def test_w_server_run_is_correct(w_root, monkeypatch):
    from repro.core import evaluator

    monkeypatch.setattr(evaluator, "evaluate_many", _stub(keep_servers=True))
    result = run(w_root, CELL)
    assert result["correct"] is True and result["failed"] == 0
    assert list(result["checks"]) == ["dynamic_rel_err"]
    assert result["checks"]["dynamic_rel_err"]["value"] < 1e-9


def test_w_server_run_with_the_count_dropped_is_not_correct(w_root, monkeypatch):
    from repro.core import evaluator

    monkeypatch.setattr(evaluator, "evaluate_many", _stub(keep_servers=False))
    result = run(w_root, CELL)
    assert result["correct"] is False
    check = result["checks"]["dynamic_rel_err"]
    assert check["value"] > check["limit"]


def test_one_server_configuration_calls_evaluate_many_as_before(tiny_root, monkeypatch):
    from repro.core import evaluator

    calls = []
    score = evaluator.evaluate_many

    def recording(*args, **kwargs):
        calls.append((len(args), tuple(sorted(kwargs))))
        return score(*args, **kwargs)

    monkeypatch.setattr(evaluator, "evaluate_many", recording)
    assert run(tiny_root, "tiny-no-optimal")["correct"] is True
    assert calls and set(calls) == {(3, ())}


def test_static_orders_on_servers_are_refused(w_root):
    with pytest.raises(harness.BenchError, match="optimal.*random.*on 2 servers"):
        harness.load_cell(w_root, "tiny-w2-static")


def test_bfloat16_control_fails_the_limit_on_servers(w_root):
    cell = harness.load_cell(w_root, CELL)
    numbers = checks.control_numbers(cell.config, 11, 10, cell.traffic["algorithms"],
                                     harness.check_count(cell))
    assert list(numbers) == ["dynamic_rel_err"]
    assert numbers["dynamic_rel_err"] > cell.limits["dynamic_rel_err"]


def test_servers_readings_hold_the_program_and_the_control_apart(tmp_path, capsys, monkeypatch):
    import servers_readings

    monkeypatch.setattr(harness, "use_compile_cache", lambda: None)
    servers_readings.main(["program", "--config", "benchmarks/chip/configs/paper-iv-n17-m2.json",
                           "--n-jobs", "5", "--servers", "2", "--algorithms", "sr", "rank",
                           "--seeds", str(2**33 + 3)])
    answers = tmp_path / "answers.jsonl"
    answers.write_text(capsys.readouterr().out)
    assert len(answers.read_text().splitlines()) == 5  # one group a workload set
    servers_readings.main(["compare", str(answers), "--control", "1"])
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    limit = json.loads((harness.HERE / "limits.json").read_text())["dynamic_rel_err"]
    assert last["lower"]["dynamic_rel_err"] < 1e-9
    assert last["upper"]["dynamic_rel_err"] > limit
