"""BENCHMARK.json against the rules a benchmark manifest keeps, and every
file it names."""
import json
import os
import re

import pytest

from _bench_path import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= len(manifest["command"]) <= 32
    for p in manifest["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    # the command names no file outside the benchmark's paths
    for word in manifest["command"][1:]:
        assert any(word.startswith(p + "/") for p in manifest["paths"])


def test_names_and_units(manifest):
    names = ([c["name"] for c in manifest["configs"]]
             + [w["name"] for w in manifest["workloads"]]
             + [m["name"] for m in manifest["end_to_end"]]
             + [m["name"] for m in manifest["per_layer"]])
    assert all(NAME.match(n) for n in names), names
    for group in ("configs", "workloads"):
        ns = [e["name"] for e in manifest[group]]
        assert len(ns) == len(set(ns))
    metrics = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES


def test_configs_exist_and_match(manifest):
    files = set()
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert set(cfg["published"]) == set(c["reduced"])
        for key in c["reduced"]:
            assert NAME.match(key)
            assert cfg["published"][key] != cfg[key]
            assert not (key.endswith("_dim") or key.endswith("_size")
                        or key.endswith("_rank") or "heads" in key), key
        assert c["file"] not in files
        files.add(c["file"])
        assert os.path.isfile(os.path.join(ROOT, "bench", "reference",
                                           cfg["reference"] + ".py"))


def test_cells(manifest):
    configs = {c["name"] for c in manifest["configs"]}
    pairs = set()
    four = 0
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        four += w["chips"] == 4
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        with open(os.path.join(ROOT, "bench", "traffic",
                               w["traffic"] + ".json")) as f:
            tr = json.load(f)
        assert tr["nodes"] == w["chips"] and tr["mesh"] == [w["chips"], 1]
        with open(os.path.join(ROOT, "bench", "limits",
                               w["name"] + ".json")) as f:
            assert set(json.load(f)) == {"loss_gap", "grad_gap",
                                         "grad_median_gap", "change_gap"}
    assert four <= max(1, len(manifest["workloads"]) // 2)
    assert configs == {w["config"] for w in manifest["workloads"]}


def test_metrics(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in manifest["end_to_end"]:
        assert 0 < m["bound"] <= 0.25 and m["source"] in ("host_clock",
                                                           "device_trace")
    layers = {}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        assert set(m["workloads"]) <= cells if "workloads" in m else True
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
        assert "roofline" not in m["name"] or m["unit"] == "%"
    # one spelling per layer
    assert all(len(v) == 1 for v in layers.values())
    for cell in cells:
        assert any(cell in m.get("workloads", [cell])
                   for m in manifest["per_layer"])
    assert len(json.dumps(manifest)) <= 64 * 1024
