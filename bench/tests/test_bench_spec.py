"""``BENCHMARK.json`` names only what the harness can find: each cell's
configuration and mix file, each per-layer metric's reader, each layer as
``PERF.md`` lists it."""
import json
import pathlib
import re

import pytest

from bench import harness, traffic

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_names_and_units():
    names = [e["name"] for key in ("configs", "workloads", "end_to_end", "per_layer")
             for e in SPEC[key]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
               for m in SPEC["end_to_end"] + SPEC["per_layer"])
    assert {m["name"] for m in SPEC["end_to_end"]} >= {"setup_s"}


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_files_exist(cell):
    c = harness.load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    assert len(cell["why"]) <= 200 and cell["chips"] in (1, 4)
    assert traffic.longest_request(mix) < c["replica"]["max_len"]
    if mix["loop"] == "open":
        assert mix["rate_rps"] > 0


@pytest.mark.parametrize("metric", SPEC["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader_and_a_layer(metric):
    assert callable(harness.load_reader(metric["name"]))
    assert metric["moves"] in {m["name"] for m in SPEC["end_to_end"]}
    perf = (ROOT / "PERF.md").read_text()
    assert f"| {metric['layer']} |" in perf


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file_matches_entry(config):
    c = harness.load_config(config["name"])
    assert (ROOT / config["file"]).exists()
    assert c["source"] == config["source"]
    assert c["reduced"] == config["reduced"]


def _of(metrics, cell):
    return {m["name"] for m in metrics if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_each_cell_reports_what_its_layers_move(cell):
    end_to_end = _of(SPEC["end_to_end"], cell["name"])
    per_layer = [m for m in SPEC["per_layer"] if cell["name"] in m.get("workloads", [cell["name"]])]
    assert "setup_s" in end_to_end and len(end_to_end) >= 2 and per_layer
    bases = [harness.base_name(n) for n in end_to_end]
    assert len(bases) == len(set(bases))
    assert {m["moves"] for m in per_layer} <= end_to_end
    assert {harness.base_name(n) for n in end_to_end} <= \
        {name for name, _ in harness.END_TO_END}
