"""BENCHMARK.json names only parts that exist, in the allowed alphabet."""
import json
import re

import pytest

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BM = spec.benchmark()


def test_top_level_keys_and_command():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["paths"] == ["bench"]
    assert all(not w.startswith("/") and ".." not in w
               for w in BM["command"])
    assert 1 <= BM["run_seconds"] <= 51
    assert len(json.dumps(BM)) < 64 * 1024


@pytest.mark.parametrize("entry", BM["configs"], ids=lambda c: c["name"])
def test_config_files_exist(entry):
    assert NAME.match(entry["name"])
    assert entry["file"].startswith("bench/")
    cfg = json.loads((spec.ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    for key in entry["reduced"]:
        assert NAME.match(key) and key in cfg["reduced"]
        assert not key.endswith(("_dim", "_rank")) and key != "dim"


@pytest.mark.parametrize("cell", BM["workloads"], ids=lambda w: w["name"])
def test_cells_name_existing_parts(cell):
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["config"] in {c["name"] for c in BM["configs"]}
    assert (spec.BENCH / "traffic" / f"{cell['traffic']}.json").exists()
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    kinds = {m["name"] for m in spec.metrics_for(cell["name"], "end_to_end",
                                                 BM)}
    assert "setup_s" in kinds and len(kinds) >= 2
    assert spec.metrics_for(cell["name"], "per_layer", BM)


@pytest.mark.parametrize("metric", BM["end_to_end"] + BM["per_layer"],
                         ids=lambda m: m["name"])
def test_metrics_are_well_formed(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    cells = {w["name"] for w in BM["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if metric in BM["per_layer"]:
        assert metric["moves"] in {m["name"] for m in BM["end_to_end"]}
        for cell in metric.get("workloads", cells):
            assert metric["moves"] in {
                m["name"] for m in spec.metrics_for(cell, "end_to_end", BM)}
        reader = spec.metric_reader(metric["name"])
        assert callable(reader.read)
    else:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.0 < metric["bound"] <= 0.25
