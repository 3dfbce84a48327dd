"""BENCHMARK.json: exact keys, allowed characters, and every name resolves to a file."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["chipbench"] and 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_entries_have_just_their_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and w["chips"] in (1, 4)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}


def test_names_units_and_lines(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
            for key in ("why", "source", "layer"):
                if key in e and group in ("configs", "workloads", "per_layer") and key != "source":
                    assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] and "\t" not in e[key]
    assert len(names) == len(set(names))
    for c in bench["configs"]:
        assert 1 <= len(c["source"]) <= 200 and all(NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher") and m["source"] in SOURCES
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]


def test_every_name_resolves_to_a_file(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for c in bench["configs"]:
        assert c["file"].startswith("chipbench/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
        for sub, key in (("data", cfg["data"]["module"]), ("references", cfg["reference"]), ("work", cfg["work"])):
            assert os.path.exists(os.path.join(ROOT, "chipbench", sub, key + ".py")), (sub, key)
        assert cfg["guarantees"] and cfg["limits"]
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    for w in bench["workloads"]:
        with open(os.path.join(ROOT, "chipbench", "traffic", w["traffic"] + ".json")) as f:
            mix = json.load(f)
        assert os.path.exists(os.path.join(ROOT, "chipbench", "traffic", mix["generator"] + ".py"))
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "chipbench", "layer_metrics", m["name"] + ".py")), m["name"]
        assert m["moves"] in e2e and set(m.get("workloads", cells)) <= cells
    layers = {m["layer"] for m in bench["per_layer"]}
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert layer in perf, layer


def test_run_py_names_no_estimator_metric_cell_or_generator(bench):
    with open(os.path.join(ROOT, "chipbench", "run.py")) as f:
        src = f.read()
    for w in bench["workloads"] + bench["configs"] + bench["per_layer"]:
        assert w["name"] not in src, w["name"]
    assert "LogisticRegression" not in src and "LinearRegression" not in src
    assert "closed_loop" not in src and "matmul_precision" not in src
