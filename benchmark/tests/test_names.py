"""Every name, unit and path of BENCHMARK.json within the contract's limits,
and every file it names present."""

import json
import os
import re

from benchmark import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _bench():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_units_and_keys():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    e2e = {m["name"] for m in b["end_to_end"]}
    cells = {w["name"] for w in b["workloads"]}
    assert "setup_s" in e2e
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and os.path.exists(
            os.path.join(harness.ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(
            harness.HERE, "traffic", w["traffic"] + ".json"))
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", [])) <= cells
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert set(m.get("workloads", [])) <= cells
        spec = harness.load_json("layer_metrics", m["name"] + ".json")
        assert os.path.exists(os.path.join(
            harness.HERE, "readers", spec["reader"] + ".py"))
        assert spec["source"] == m["source"]


def test_every_cell_reports_an_end_to_end_and_a_per_layer_metric():
    b = _bench()
    for w in b["workloads"]:
        e2e = [m["name"] for m in b["end_to_end"] if m["name"] != "setup_s"
               and w["name"] in m.get("workloads", [w["name"]])]
        layer = [m for m in b["per_layer"]
                 if w["name"] in m.get("workloads", [w["name"]])]
        assert e2e and layer
        for m in layer:  # a layer metric's cell reports what it moves
            assert m["moves"] in e2e
