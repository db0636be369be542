"""BENCHMARK.json against the harness: every name is found as a file, the
names and units are well formed, and a full check fits its time."""
import json
import re

import pytest

from bench.harness import core

BENCH = json.loads((core.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_a_full_check_of_24_cells_fits():
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_every_metric_has_a_reader_and_well_formed_fields(metric):
    assert NAME.match(metric["name"])
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert (core.BENCH / "metrics" / f"{metric['name']}.py").exists()
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells


def test_end_to_end_metrics_and_bounds():
    names = [m["name"] for m in BENCH["end_to_end"]]
    assert "setup_s" in names and len(names) == len(set(names))
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}


def test_per_layer_metrics_move_an_end_to_end_metric_of_their_cells():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        reports = set(e2e[m["moves"]].get(
            "workloads", [w["name"] for w in BENCH["workloads"]]))
        assert set(m["workloads"]) <= reports


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_finds_its_files_and_reports_enough(cell):
    assert NAME.match(cell["name"]) and cell["chips"] in (1, 4)
    assert len(cell["why"]) <= 200
    assert (core.BENCH / "traffic" / f"{cell['traffic']}.json").exists()
    cfg = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert (core.ROOT / cfg["file"]).exists()
    traffic = json.loads(
        (core.BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    assert (core.BENCH / "kinds" / f"{traffic['kind']}.py").exists()
    e2e = core.metric_names(BENCH, cell["name"], False)
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert core.metric_names(BENCH, cell["name"], True)


def test_configs_are_used_and_have_files_of_their_own():
    used = {w["config"] for w in BENCH["workloads"]}
    assert {c["name"] for c in BENCH["configs"]} == used
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    for c in BENCH["configs"]:
        body = json.loads((core.ROOT / c["file"]).read_text())
        assert body["reduced"] == c["reduced"]
        assert body["source"] == c["source"]
