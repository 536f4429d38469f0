"""Every part of every cell is found by its name, and BENCHMARK.json keeps
to the form the harness reads."""

from __future__ import annotations

import json
import re
import shutil
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import cell  # noqa: E402

BENCH = json.loads((cell.REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    script = cell.REPO / BENCH["command"][1]
    assert script.is_file() and script.parent == HERE
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_units_and_keys():
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            assert NAME.match(entry["name"]), entry["name"]
            assert (group, entry["name"]) not in seen
            seen.add((group, entry["name"]))
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for entry in BENCH["configs"] + BENCH["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    for m in BENCH["per_layer"]:
        assert 1 <= len(m["layer"]) <= 200
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")


CONTRACT = ("make_data", "job_flops", "handoff", "reference", "summarize", "readings")


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_resolves_by_name(workload):
    c = cell.find(workload, BENCH)
    assert c.chips in (1, 4)
    for key in ("model", "model_file", "N", "M", "sampler", "warmup", "T", "burn_in", "step_size"):
        assert key in c.config, key
    for fn in CONTRACT:
        assert callable(getattr(c.model, fn)), fn
    assert c.traffic["loop"] == "closed" and c.traffic["trace_jobs"] >= 1
    numbers = c.model.NUMBERS
    assert all(n["layer"] in ("sampling", "combine") and n["scope"] in ("job", "window")
               for n in numbers.values())
    # the numbers compared cover the sampler layer and the combine layer
    assert set(c.limits) <= set(numbers)
    assert {numbers[k]["layer"] for k in c.limits} == {"sampling", "combine"}
    assert all(v > 0 for v in c.limits.values())
    assert {m["name"] for m in c.end_to_end} >= {"setup_s", "job_s"}
    assert c.per_layer, "every cell reports a per-layer metric"
    if c.chips == 4:
        assert c.config["M"] % 4 == 0


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_file_states_what_it_reduced(entry):
    cfg = json.loads((cell.REPO / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    assert (HERE / "configs" / cfg["model_file"]).is_file()
    used = {w["config"] for w in BENCH["workloads"]}
    assert entry["name"] in used


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    module = cell.reader(metric)
    assert callable(module.read)


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        cell.find("no-such-config.no-such-traffic", BENCH)


def test_a_limit_on_a_number_the_model_file_lacks_is_refused(tmp_path, monkeypatch):
    # a copy of the benchmark's own files, with one limit its model file lacks
    for part in ("configs", "traffic", "limits"):
        shutil.copytree(HERE / part, tmp_path / part)
    workload = WORKLOADS[0]
    path = tmp_path / "limits" / f"{workload}.json"
    path.write_text(json.dumps({**json.loads(path.read_text()), "no_such_number": 1.0}))
    monkeypatch.setattr(cell, "BENCH_DIR", tmp_path)
    with pytest.raises(ValueError, match="no_such_number"):
        cell.find(workload, BENCH)


def test_every_traffic_file_and_limit_file_is_used():
    traffic = {w["traffic"] for w in BENCH["workloads"]}
    assert {p.stem for p in (HERE / "traffic").glob("*.json")} == traffic
    assert {p.stem for p in (HERE / "limits").glob("*.json")} == set(WORKLOADS)
