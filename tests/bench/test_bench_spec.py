"""BENCHMARK.json against the benchmark's contract, and every name in
it resolving to its files."""

import json
import os
import re

import pytest
from tiny_cell import ROOT

from bench import run

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert SPEC["command"][1] == "bench/run.py"
    for p in SPEC["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25
               for m in SPEC["end_to_end"])


def test_names_units_and_lines():
    names = [c["name"] for c in SPEC["configs"]] + CELLS + \
        [m["name"] for m in METRICS]
    for n in names:
        assert NAME.fullmatch(n), n
    assert len(set(m["name"] for m in METRICS)) == len(METRICS)
    for m in METRICS:
        assert UNIT.fullmatch(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    ends = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in ends
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for cell in m.get("workloads", []):
            assert cell in CELLS
    for entry in SPEC["configs"] + SPEC["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = run.load_cell(cell)
    assert c["model"]["name"] == c["config"]
    assert c["chips"] in (1, 4)
    assert c["layout"]["dp"] * c["layout"]["tp"] == c["chips"]
    assert c["limits"] and all(v > 0 for v in c["limits"].values())
    assert {"batch", "seq", "optimizer"} <= set(c["traffic_mix"])
    assert any(n == "setup_s" for n, _ in c["end_to_end"])
    assert len(c["end_to_end"]) >= 2 and c["per_layer"]


@pytest.mark.parametrize("config", SPEC["configs"],
                         ids=lambda c: c["name"])
def test_config_file_states_its_cut(config):
    m = run.read_json(os.path.join(ROOT, config["file"]))
    assert m["name"] == config["name"]
    assert sorted(m["reduced"]) == sorted(config["reduced"])
    for key, cut in m["reduced"].items():
        assert m[key] == cut["here"] != cut["published"]
    assert m["precision"] == {"parameters": "float32", "matmul": "highest"}
    assert hasattr(run.load_arch(m["arch"]), "program")
    # a head size the source does not give is derived from the width
    if any(a.startswith("head_dim = hidden_size / num_attention_heads")
           for a in m["assumed"]):
        assert m["head_dim"] * m["num_attention_heads"] == m["hidden_size"]


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    path = os.path.join(ROOT, "bench", "metrics", metric["name"] + ".py")
    assert "def read(run)" in open(path).read()


def test_unknown_device_has_no_peaks():
    assert run.device_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert run.device_peaks("TPU v5 lite")["source"]
    with pytest.raises(run.BenchError):
        run.device_peaks("TPU v99")
