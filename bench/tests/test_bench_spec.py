"""Configurations, traffic mixes and metric readers are found by the names
in BENCHMARK.json, and the file keeps to its contract's form."""
import json
import re

import pytest

from bench import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((spec.ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_bounds():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics] + CELLS + \
        [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_found_by_name(cell):
    c = spec.load_cell(cell)
    assert c.batch > 0 and c.traffic["pool"] >= 1
    assert c.config["entry"] and c.config["limits"]
    assert c.chips in (1, 4)
    reported = {m["name"] for m in c.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in reported
    for m in c.end_to_end + c.per_layer:
        assert callable(spec.metric_reader(m["name"]))


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_file_states_its_cuts(entry):
    path = spec.ROOT / entry["file"]
    assert path.is_relative_to(spec.ROOT / BENCH["paths"][0])
    cfg = json.loads(path.read_text())
    assert cfg["reduced"] == entry["reduced"]
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])


def test_an_unknown_name_is_an_error():
    with pytest.raises(KeyError):
        spec.load_cell("no_such_cell")
    with pytest.raises(FileNotFoundError):
        spec.metric_reader("no_such_metric")
