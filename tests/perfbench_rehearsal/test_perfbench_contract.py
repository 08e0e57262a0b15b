"""BENCHMARK.json against the files it names and the contract's limits."""

import os
import re

import pytest

import rehearsal_util
from perfbench import contract, harness

BENCH = rehearsal_util.real_benchmark()
ROOT = rehearsal_util.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_exactly_the_contracts_keys():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= BENCH["run_seconds"] <= 51
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}


def test_names_units_and_lines_hold_only_what_is_allowed():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names += [e["name"] for e in BENCH[group]]
        assert len({e["name"] for e in BENCH[group]}) == len(BENCH[group])
    names += [w["traffic"] for w in BENCH["workloads"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    lines = [e["why"] for e in BENCH["workloads"] + BENCH["configs"]]
    lines += [m["layer"] for m in BENCH["per_layer"]] + BENCH["command"]
    assert all(1 <= len(s) <= 200 and "\n" not in s and "\t" not in s for s in lines)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_files_under_paths_are_named_from_the_allowed_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
        for base, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                assert ok.match(os.path.relpath(os.path.join(base, f), ROOT)), f


@pytest.mark.parametrize("cell", CELLS)
def test_every_workload_resolves_to_files_of_its_own(cell):
    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    config = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    assert os.path.exists(os.path.join(ROOT, config["file"]))
    cfg = harness.load_json(harness.find(ROOT, BENCH, "configs", w["config"] + ".json"))
    assert sorted(cfg["reduced"]) == sorted(config["reduced"])
    assert cfg["source"] == config["source"]
    harness.find(ROOT, BENCH, "families", cfg["family"] + ".py")
    harness.find(ROOT, BENCH, "traffic", w["traffic"] + ".json")
    harness.find(ROOT, BENCH, "cells", cell + ".json")
    # Only what a deployment must state reaches the server.
    assert set(cfg["server"]) == {"num_blocks", "block_size", "max_batch", "mesh"}
    e2e = [m["name"] for m in harness.cell_metrics(BENCH, "end_to_end", cell)]
    layer = harness.cell_metrics(BENCH, "per_layer", cell)
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    for name in e2e:
        harness.find(ROOT, BENCH, "end_to_end", name + ".py")
    for m in layer:
        harness.find(ROOT, BENCH, "layer_metrics", m["name"] + ".py")
        assert m["moves"] in e2e, (m["name"], m["moves"], cell)


def test_every_config_is_used_and_no_width_is_reduced():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        cfg = harness.load_json(os.path.join(ROOT, c["file"]))
        assert contract.check_config(c, cfg) == [], c["name"]


# A configuration of another architecture, as a later PR's data files
# would state it: expert layers, a head size that is not the quotient
# of hidden size and heads, window and full layers in a period of four.
# One chip's share of eight: 16 of 128 experts, an eighth of the
# vocabulary, one period of the depth.
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
OTHER_ENTRY = {
    "name": "other", "source": "none", "file": "none", "why": "another architecture",
    "reduced": ["num_hidden_layers", "layer_types", "num_experts", "vocab_size"],
}
OTHER = {
    "family": "other",
    "hidden_size": 4096, "intermediate_size": 4096, "num_attention_heads": 128,
    "num_key_value_heads": 8, "head_dim": 128, "num_experts": 16,
    "num_experts_per_tok": 8, "num_shared_experts": 4, "sliding_window": 4096,
    "vocab_size": 32768, "num_hidden_layers": 4, "layer_types": PERIOD,
    "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
    "published": {
        "hidden_size": 4096, "intermediate_size": 4096, "num_attention_heads": 128,
        "num_key_value_heads": 8, "head_dim": 128, "num_experts": 128,
        "num_experts_per_tok": 8, "num_shared_experts": 4, "sliding_window": 4096,
        "vocab_size": 262144, "num_hidden_layers": 32, "layer_types": PERIOD * 8,
        "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
    },
    "server": {"num_blocks": 2560, "block_size": 16, "max_batch": 32, "mesh": None},
}


def changed(file=None, published=None, drop=(), reduced=None):
    """OTHER with keys of the file and of `published` replaced, keys of
    the file dropped, and the entry's `reduced` replaced."""
    cfg = {**OTHER, **(file or {})}
    cfg["published"] = {**OTHER["published"], **(published or {})}
    cfg = {k: v for k, v in cfg.items() if k not in drop}
    entry = dict(OTHER_ENTRY, reduced=OTHER_ENTRY["reduced"] if reduced is None else reduced)
    return entry, cfg


def test_check_config_passes_a_second_architecture_without_an_edit():
    assert contract.check_config(*changed()) == []
    assert contract.layer_period(OTHER["published"]) == 4
    assert contract.layer_period({"full_attention_interval": 4, "expert_layer_period": 2}) == 4
    assert contract.layer_period({"num_hidden_layers": 32}) == 1


@pytest.mark.parametrize("case, kwargs, says", [
    ("a cut width", {"file": {"intermediate_size": 2048}}, "intermediate_size is 2048"),
    ("a cut width listed in reduced",
     {"file": {"head_dim": 64}, "reduced": OTHER_ENTRY["reduced"] + ["head_dim"]},
     "head_dim is a width"),
    ("a width missing from published", {"file": {"expert_width": 4096}}, "expert_width is a width"),
    ("a window missing from published", {"file": {"window_size": 512}}, "window_size is a width"),
    ("a stale reduced", {"file": {"vocab_size": 262144}}, "vocab_size is in `reduced` and equals"),
    ("a reduced key the source does not have",
     {"reduced": OTHER_ENTRY["reduced"] + ["tie_word_embeddings"]}, "no published value"),
    ("a changed key that is not in reduced", {"file": {"num_key_value_heads": 4}}, "num_key_value_heads is 4"),
    ("a published key the file leaves out", {"drop": ("num_shared_experts",)}, "num_shared_experts is published"),
    ("4 experts", {"file": {"num_experts": 4}}, "fewer than 8"),
    ("a sixteenth of the vocabulary", {"file": {"vocab_size": 16384}}, "under an eighth"),
    ("6 layers of a period of 4",
     {"file": {"num_hidden_layers": 6, "layer_types": PERIOD + PERIOD[:2]}}, "no whole number of periods"),
    ("a width changed inside a reduced group",
     {"file": {"rope_parameters": {"rope_theta": 50000, "rope_type": "default", "rotary_dim": 64}},
      "published": {"rope_parameters": {"rope_theta": 50000, "rope_type": "default", "rotary_dim": 128}},
      "reduced": OTHER_ENTRY["reduced"] + ["rope_parameters"]},
     "rope_parameters.rotary_dim"),
    ("no published values", {"drop": ("published",)}, "no `published`"),
])
def test_check_config_fails(case, kwargs, says):
    faults = contract.check_config(*changed(**kwargs))
    assert len(faults) == 1 and says in faults[0], (case, faults)
