import json
import os
import re

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_and_units():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    metrics = m["end_to_end"] + m["per_layer"]
    names = [x["name"] for x in metrics + m["configs"] + m["workloads"]]
    assert all(NAME.match(n) for n in names), names
    assert len({x["name"] for x in metrics}) == len(metrics)
    for x in metrics:
        assert UNIT.match(x["unit"]), x
        assert x["better"] in ("lower", "higher")
        assert x["source"] in SOURCES
    for x in m["end_to_end"]:
        assert set(x) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= x["bound"] <= 0.1
        assert x["source"] in ("host_clock", "device_trace")
    e2e = {x["name"] for x in m["end_to_end"]}
    assert "setup_s" in e2e
    for x in m["per_layer"]:
        assert set(x) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert x["moves"] in e2e
    for c in m["workloads"]:
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
        assert c["chips"] in (1, 4) and len(c["why"]) <= 200
        assert NAME.match(c["config"]) and NAME.match(c["traffic"])


def test_every_cell_has_its_files():
    m = manifest()
    configs = {c["name"]: c for c in m["configs"]}
    assert {c["config"] for c in m["workloads"]} == set(configs)
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert cfg["limits"], "a configuration sets its own limits"
    for cell in m["workloads"]:
        with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
            traffic = json.load(f)
        assert os.path.isfile(os.path.join(BENCH, "drivers", traffic["driver"] + ".py"))
    for x in m["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics", x["name"] + ".py"))
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in {x["layer"] for x in m["per_layer"]}:
        assert f"**{layer}**" in perf, f"PERF.md's list of layers lacks {layer!r}"
