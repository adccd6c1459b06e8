"""The manifest and the data files it names."""
import json
import os
import re

import pytest
from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def man():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert man["paths"] == ["bench"]
    assert man["command"][1] == "bench/run.py"
    assert 1 <= man["run_seconds"] <= 51
    # a full check of 24 cells fits its time
    assert (2 + 14 * 24) * (man["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def test_names_and_units(man):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in man[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
                assert e["source"] in SOURCES
    assert len(names) == len(set(names))
    for w in man["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    for c in man["configs"]:
        for key in c["reduced"]:
            assert NAME.match(key)


def test_each_config_and_traffic_pair_once(man, checkout):
    with open(os.path.join(checkout, "BENCHMARK.json")) as f:
        grown = json.load(f)
    for m in (man, grown):
        pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
        assert len(pairs) == len(set(pairs)), pairs


def test_traffic_names_its_entry(man):
    from system import ENTRIES
    for w in man["workloads"]:
        with open(os.path.join(BENCH, "traffic", f"{w['traffic']}.json")) as f:
            traffic = json.load(f)
        assert traffic["name"] == w["traffic"]
        assert traffic["entry"] in ENTRIES
        assert int(traffic["periods_per_call"]) >= 1
        with open(os.path.join(BENCH, "cells", f"{w['name']}.json")) as f:
            assert not {"entry", "periods_per_call"} & set(json.load(f))


def test_every_cell_finds_its_files(man):
    configs = {c["name"]: c for c in man["configs"]}
    for w in man["workloads"]:
        c = configs[w["config"]]
        assert c["file"] == f"bench/configs/{w['config']}.json"
        for path in (c["file"], f"bench/traffic/{w['traffic']}.json",
                     f"bench/cells/{w['name']}.json"):
            assert os.path.isfile(os.path.join(ROOT, path)), path
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["name"] == w["config"]
        assert conf["assumed"], "a configuration lists what it assumes"
        assert conf["reduced"] == c["reduced"]
        assert set(c["reduced"]) <= set(conf["source_scale"]) & set(conf)
        assert len(c["source"]) <= 200
    sources = [c["source"] for c in man["configs"]]
    assert len(sources) == len(set(sources)), "a deployment each"
    used = {w["config"] for w in man["workloads"]}
    assert used == set(configs)


def test_metrics_have_readers(man):
    for m in man["per_layer"]:
        with open(os.path.join(BENCH, "metrics", f"{m['name']}.json")) as f:
            spec = json.load(f)
        assert os.path.isfile(os.path.join(BENCH, "readers",
                                           f"{spec['reader']}.py"))


def test_per_layer_cells_report_what_they_move(man):
    e2e = {m["name"]: m for m in man["end_to_end"]}
    cells = [w["name"] for w in man["workloads"]]
    for m in man["per_layer"]:
        moved = e2e[m["moves"]]
        reporting = moved.get("workloads", cells)
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in reporting, (m["name"], cell)
    for w in cells:
        reported = [m for m in man["end_to_end"]
                    if w in m.get("workloads", cells)]
        assert "setup_s" in [m["name"] for m in reported]
        assert len(reported) >= 2
        assert any(w in m.get("workloads", cells) for m in man["per_layer"])


def test_bounds(man):
    for m in man["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
