"""BENCHMARK.json keeps to the benchmark's contract, and every file it
names is there."""

from __future__ import annotations

import json
import re

import pytest

from harness import ROOT

SPEC_PATH = ROOT / "BENCHMARK.json"
SPEC = json.loads(SPEC_PATH.read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRIC_KEYS = {"name", "unit", "better", "source"}


def one_line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC_PATH.stat().st_size <= 64 * 1024
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    assert 1 <= len(SPEC["command"]) <= 32 and all(one_line(w) for w in SPEC["command"])
    for path in SPEC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./\-]{1,200}", path) and ".." not in path
        assert (ROOT / path).is_dir()
    assert (ROOT / SPEC["command"][1]).is_file()


def test_configs():
    names = [c["name"] for c in SPEC["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    used = {w["config"] for w in SPEC["workloads"]}
    files = set()
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert one_line(c["source"]) and one_line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])


def test_workloads():
    configs = {c["name"] for c in SPEC["configs"]}
    cells = SPEC["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4) and one_line(w["why"])
        assert (ROOT / SPEC["paths"][0] / "traffic" / f"{w['traffic']}.json").is_file()


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_metrics(kind):
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    names = [m["name"] for m in SPEC[kind]]
    assert len(set(names)) == len(names)
    for m in SPEC[kind]:
        extra = {"bound"} if kind == "end_to_end" else {"layer", "moves"}
        assert METRIC_KEYS | extra <= set(m) <= METRIC_KEYS | extra | {"workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
        if kind == "end_to_end":
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.25
        else:
            assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
            assert one_line(m["layer"]) and m["moves"] in e2e
            assert (ROOT / SPEC["paths"][0] / "metrics" / f"{m['name']}.py").is_file()
    assert kind == "per_layer" or "setup_s" in names


def test_every_cell_reports_enough():
    for w in SPEC["workloads"]:
        def reports(m):
            return w["name"] in m.get("workloads", [w["name"]])
        e2e = [m["name"] for m in SPEC["end_to_end"] if reports(m)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(reports(m) for m in SPEC["per_layer"])
