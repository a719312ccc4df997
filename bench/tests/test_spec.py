"""BENCHMARK.json and the files it names hang together."""
import json
import os
import re

import pytest

import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))


def test_every_cell_loads_with_its_metrics(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        names = {m.name for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2, w["name"]
        assert cell.per_layer, w["name"]
        assert names <= e2e
        for m in cell.per_layer:
            entry = next(p for p in bench["per_layer"] if p["name"] == m.name)
            assert entry["moves"] in names, (w["name"], m.name)
        assert cell.traffic["slots"] % cell.chips == 0
        assert cell.traffic["slots"] // cell.chips <= 16   # SMEM ceiling


def test_names_units_and_sources(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]) and m["source"] in SOURCES
        assert m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert os.path.isfile(os.path.join(spec.BENCH, "metrics",
                                           m["name"] + ".py"))
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for w in bench["workloads"] + bench["configs"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
    for m in bench["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_configs_name_their_files_and_references(bench):
    for c in bench["configs"]:
        path = os.path.join(spec.ROOT, c["file"])
        config = spec.load_json(path)
        assert config["name"] == c["name"]
        assert config["source"] == c["source"]
        assert set(c["reduced"]) == set(config["reduced"])
        assert os.path.isfile(os.path.join(spec.BENCH, "references",
                                           config["reference"] + ".py"))
        graph = spec.layer_graph(config)
        for l in graph:                   # each entry reads what it names
            src = config["in_channels"] if l.input == spec.IMAGE else \
                graph[l.input].cout
            assert l.cin == src, (c["name"], l.label)
        json.dumps(config)


def test_peaks_know_the_v5e_and_refuse_others():
    p = spec.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")
