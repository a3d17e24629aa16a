"""The harness's run, driven on the CPU past its look for a chip.

A clean run is correct and prints the result line the contract asks for;
the same run with the timed path broken underneath is not correct, once
per fault the cells can have; the bfloat16 control fails the limits; and
without a TPU, or with a device kind missing from ``peaks.json``, the
harness stops before printing any result.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import jax
import pytest

import checks
import harness

SECONDS = 0.3


def run(root, cell, trace=False, seed=2**31 + 7):
    result, lines = harness.run(harness.load_cell(root, cell), seed, SECONDS, trace,
                                require_tpu=False)
    return result, lines


@pytest.mark.parametrize("trace", [False, True])
def test_clean_run_is_correct_and_well_formed(tiny_root, trace):
    result, lines = run(tiny_root, "tiny-with-optimal", trace)
    assert result["correct"] is True
    assert list(result)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(result)
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["checks"]) == {"optimal_rel_err", "static_rel_err", "dynamic_rel_err"}
    for c in result["checks"].values():
        assert c["value"] <= c["limit"]
    assert len(lines) == 3 and all(line.startswith("check ") for line in lines)
    dev = result["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    if trace:
        assert "trials_per_s" not in result["metrics"]
        assert "host_ms_per_trial" in result["metrics"]
        assert {"busy_s", "window_s"} <= set(dev)
        assert len(result["breakdown"]["device_ops"]) <= 10
        assert len(result["breakdown"]["idle_gaps"]) <= 10
    else:
        assert set(result["metrics"]) == {"trials_per_s", "setup_s"}
    json.loads(json.dumps(result))


def _scale(fn, factor):
    def broken(*args, **kwargs):
        e_succ, e_all = fn(*args, **kwargs)
        return e_succ * factor, e_all
    return broken


def _half_combinations(probs_arg):
    """Enumerate the first half of the combinations (job 0 stopping at its
    first checkpoint) and divide by their probability: the mean over the
    half kept.  ``probs_arg`` is the position of the probability table."""
    def breaker(fn):
        def broken(*args, k_total, **kwargs):
            e_succ, e_all = fn(*args, k_total=k_total // 2, **kwargs)
            return e_succ / args[probs_arg][0, 0], e_all
        return broken
    return breaker


def _faults():
    from repro.kernels.sojourn_eval import dynamic, ops

    return {
        "static answer altered": (ops, "_sojourn_eval", lambda f: _scale(f, 1 + 1e-2)),
        "dynamic answer altered": (dynamic, "_sojourn_eval_dynamic", lambda f: _scale(f, 1 - 1e-2)),
        "static half the combinations": (ops, "_enum_xla", _half_combinations(1)),
        "dynamic half the combinations": (dynamic, "_dynamic_enum_xla", _half_combinations(0)),
    }


@pytest.mark.parametrize("fault", sorted(_faults()))
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, fault):
    module, name, breaker = _faults()[fault]
    monkeypatch.setattr(module, name, breaker(getattr(module, name)))
    result, _ = run(tiny_root, "tiny-with-optimal")
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["checks"].values())


def test_bfloat16_control_fails_the_limits(tiny_root):
    cell = harness.load_cell(tiny_root, "tiny-with-optimal")
    numbers = checks.control_numbers(cell.config, 11, 10, cell.traffic["algorithms"],
                                     harness.check_count(cell))
    assert all(numbers[k] > cell.limits[k] for k in cell.limits)


def test_no_tpu_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(harness.HERE / "harness.py"), "--workload", "paper-n8-no-optimal",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "not a TPU" in proc.stderr


def test_unknown_device_kind_is_refused(tiny_root, monkeypatch):
    fake = SimpleNamespace(platform="tpu", device_kind="TPU v0 unknown")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    with pytest.raises(harness.BenchError, match="peaks.json"):
        harness.Bench(harness.load_cell(tiny_root, "tiny-no-optimal"))
