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

# A cut may take depth, and a chip's share of a deployment in which
# ``deployment_chips_per_layer`` chips share each layer: its slice of the
# vocabulary and its routed experts, down to the guide's floors.  No width
# is ever cut: a hidden, intermediate, latent or projection size, a head
# count or size, a rank, an expansion factor, the experts per token.
CHIPS_PER_LAYER = "deployment_chips_per_layer"
EXPERT_COUNTS = ("n_routed_experts", "num_local_experts", "num_experts")
WIDTH = re.compile(r"(_dim|_rank|_size)$|heads|per_tok|expan")


def reduction_faults(cfg: dict) -> list:
    """What breaks the rules of a cut in a configuration file's
    ``reduced`` keys, against its ``published`` values."""
    faults = []
    pub = cfg["published"]
    for key in cfg["reduced"]:
        if key == "vocab_size":
            n = cfg.get(CHIPS_PER_LAYER)
            if not isinstance(n, int) or n < 1:
                faults.append(f"vocab_size cut without {CHIPS_PER_LAYER}")
            elif cfg[key] * n != pub[key]:
                faults.append(f"vocab_size {cfg[key]} is not {pub[key]} "
                              f"over {n} chips")
            if cfg[key] * 8 < pub[key]:
                faults.append(f"vocab_size {cfg[key]} is under an eighth")
        elif key in EXPERT_COUNTS:
            if cfg[key] < 8 or cfg[key] * 8 < pub[key]:
                faults.append(f"{key} {cfg[key]} is under 8 or an eighth "
                              f"of {pub[key]}")
        elif WIDTH.search(key):
            faults.append(f"{key} is a width")
        if isinstance(pub[key], dict):
            faults += [f"{key}.{k} is a width" for k, v in pub[key].items()
                       if WIDTH.search(k) and cfg[key].get(k) != v]
    return faults


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
        assert reduction_faults(cfg) == []
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


# DeepSeek-V2-Lite as published (its config.json), the sizes a cut reads
DSV2_LITE = {"num_hidden_layers": 27, "hidden_size": 2048,
             "intermediate_size": 10944, "moe_intermediate_size": 1408,
             "num_attention_heads": 16, "kv_lora_rank": 512,
             "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
             "v_head_dim": 128, "n_routed_experts": 64, "n_shared_experts": 2,
             "num_experts_per_tok": 6, "vocab_size": 102400,
             "rope_scaling": {"type": "yarn", "factor": 40,
                              "original_max_position_embeddings": 4096}}


def _cut(chips=None, **changes):
    cfg = dict(DSV2_LITE, **changes)
    cfg["reduced"] = list(changes)
    cfg["published"] = {k: DSV2_LITE[k] for k in changes}
    if chips is not None:
        cfg[CHIPS_PER_LAYER] = chips
    return cfg


@pytest.mark.parametrize("cfg", [
    _cut(num_hidden_layers=5),
    _cut(chips=8, vocab_size=12800),
    _cut(chips=2, vocab_size=51200),
    _cut(chips=8, n_routed_experts=8),
    _cut(n_routed_experts=16),
    _cut(chips=8, num_hidden_layers=5, n_routed_experts=8, vocab_size=12800),
    _cut(rope_scaling={"type": "yarn", "factor": 40,
                       "original_max_position_embeddings": 2048}),
], ids=["depth", "vocab_eighth", "vocab_half", "experts_8_of_64",
        "experts_16_of_64", "deepseek_share", "nested_group"])
def test_cut_within_the_floors_is_taken(cfg):
    assert reduction_faults(cfg) == []


@pytest.mark.parametrize("cfg,fault", [
    (_cut(vocab_size=12800), "without deployment_chips_per_layer"),
    (_cut(chips=8, vocab_size=12000), "is not 102400 over 8 chips"),
    (_cut(chips=16, vocab_size=6400), "under an eighth"),
    (_cut(chips=8, n_routed_experts=4), "under 8"),
    (dict(_cut(n_routed_experts=8), published={"n_routed_experts": 128}),
     "under 8 or an eighth of 128"),
    (_cut(hidden_size=1024), "hidden_size is a width"),
    (_cut(intermediate_size=5472), "intermediate_size is a width"),
    (_cut(moe_intermediate_size=704), "moe_intermediate_size is a width"),
    (_cut(kv_lora_rank=256), "kv_lora_rank is a width"),
    (_cut(qk_rope_head_dim=32), "qk_rope_head_dim is a width"),
    (_cut(v_head_dim=64), "v_head_dim is a width"),
    (_cut(num_attention_heads=8), "num_attention_heads is a width"),
    (_cut(num_experts_per_tok=2), "num_experts_per_tok is a width"),
    (dict(DSV2_LITE, text_config={"hidden_size": 1024, "layers": 5},
          reduced=["text_config"],
          published={"text_config": {"hidden_size": 2048, "layers": 27}}),
     "text_config.hidden_size is a width"),
], ids=["vocab_no_chips", "vocab_not_share", "vocab_under_eighth",
        "experts_under_8", "experts_under_eighth", "hidden", "intermediate",
        "expert_width", "latent_rank", "rope_head_dim", "v_head_dim",
        "heads", "experts_per_token", "nested_width"])
def test_cut_past_the_floors_or_of_a_width_is_refused(cfg, fault):
    assert any(fault in f for f in reduction_faults(cfg)), \
        reduction_faults(cfg)
