"""BENCHMARK.json against the files it names and the contract's limits."""

import os
import re

import pytest

import rehearsal_util
from perfbench import harness

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
    widths = re.compile(r"(hidden_size|intermediate|latent|state_size|head_dim|_dim$|_rank$|experts_per_tok)")
    for c in BENCH["configs"]:
        assert not [k for k in c["reduced"] if widths.search(k)]
        cfg = harness.load_json(os.path.join(ROOT, c["file"]))
        published = {
            "hidden_size": 4096, "intermediate_size": 14336,
            "num_attention_heads": 32, "num_key_value_heads": 8,
            "vocab_size": 32000, "sliding_window": 4096,
            "rope_theta": 10000.0, "rms_norm_eps": 1e-05,
        }
        assert {k: cfg[k] for k in published} == published
