"""``BENCHMARK.json`` against the rules a driver refuses it by, and the files
its cells name."""

import json
import os
import re

import pytest
from pb_helpers import CELLS, MANIFEST, REPO

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$|head_size|head_dim|expansion|"
                   r"experts_per_tok")


def test_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(MANIFEST["run_seconds"], int) and 1 <= MANIFEST["run_seconds"] <= 51
    # the full check has to fit with all 24 cells a benchmark may grow to
    cells, secs = 24, MANIFEST["run_seconds"]
    assert (2 + 14 * cells) * (secs + 60) + cells * 2 * 90 + 1200 <= 43200
    assert 2 <= len(MANIFEST["workloads"]) <= 24 and 1 <= len(MANIFEST["configs"]) <= 24
    assert MANIFEST["command"][-1].startswith(tuple(MANIFEST["paths"]))


def test_names_are_well_formed_and_unique():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in MANIFEST[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names
    assert all(len(e["why"]) <= 200 for e in MANIFEST["configs"] + MANIFEST["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_metrics():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] == 0.1
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in SOURCES and m["better"] in ("lower", "higher")
    # an end-to-end metric is taken by the benchmark itself, never read from the program
    assert all(m["source"] in ("host_clock", "device_trace") for m in MANIFEST["end_to_end"])
    for m in MANIFEST["per_layer"]:
        assert "bound" not in m and m["source"] in SOURCES and m["moves"] in e2e and m["layer"]
        assert not m["name"].endswith("_roofline") or m["unit"] == "%"
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        # a reader of its own, found by the metric's name
        assert os.path.isfile(os.path.join(REPO, "perfbench", "layer_metrics", m["name"] + ".py"))


def test_four_chip_cells_are_a_quarter_at_most():
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in MANIFEST["workloads"])
    assert len(four) <= max(1, len(MANIFEST["workloads"]) // 4)


@pytest.mark.parametrize("config", MANIFEST["configs"], ids=lambda c: c["name"])
def test_configuration_files(config):
    assert config["file"].startswith("perfbench/configs/")
    assert config["source"].startswith("https://")
    assert any(w["config"] == config["name"] for w in MANIFEST["workloads"])
    with open(os.path.join(REPO, config["file"]), encoding="utf-8") as f:
        body = json.load(f)
    assert body["source"] == config["source"] and body["reduced"] == config["reduced"]
    assert not any(WIDTH.search(k) for k in config["reduced"]), "a width may never be reduced"
    assert os.path.isfile(os.path.join(REPO, "perfbench", "reference", body["reference"] + ".py"))
    # every depth a cell runs at is written in the file
    for w in MANIFEST["workloads"]:
        if w["config"] == config["name"] and "num_hidden_layers_at_chips" in body:
            assert str(w["chips"]) in body["num_hidden_layers_at_chips"]


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_traffic_files(cell):
    path = os.path.join(REPO, "perfbench", "traffic", cell["traffic"] + ".json")
    with open(path, encoding="utf-8") as f:
        traffic = json.load(f)
    assert os.path.isfile(os.path.join(REPO, "perfbench", "jobs", traffic["job"] + ".py"))
    assert traffic["in_flight"] >= 1 and traffic["batch"] >= 1 and traffic["trace_units"] >= 2
    mesh = traffic.get("mesh") or {}
    chips = 1
    for size in mesh.values():
        chips *= size
    assert chips == cell["chips"], "a cell takes four chips only for a mesh that needs them"
