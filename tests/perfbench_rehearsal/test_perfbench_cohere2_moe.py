"""Family `cohere2_moe` through the real harness on the CPU: a toy of
the configuration's shape under a backlog, `correct` by the family's
reference, and the two readers over the expert layer's counters."""

import json
import os

import jax
import pytest

import rehearsal_util
from perfbench import contract, harness, peaks, run

BENCH = rehearsal_util.real_benchmark()
CELL = "cmdaplus-ep8-l4.chat-backlog"
READERS = ("moe_tokens_per_held_expert", "moe_experts_touched_share")
# The configuration's shape at toy sizes: a head that is not the
# quotient, window 8 under a 128-row table, [s, s, s, f], 16 experts
# top-2 with 4 held, 2 shared.
TOY = {
    "source": "none: toy sizes for the CPU rehearsal",
    "family": "cohere2_moe", "attention_bias": False,
    "expert_selection_fn": "sigmoid", "first_k_dense_replace": 0,
    "head_dim": 16, "hidden_size": 64, "intermediate_size": 32,
    "layer_norm_eps": 1e-5,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "logit_scale": 1, "max_position_embeddings": 128,
    "num_attention_heads": 8, "num_experts": 4, "experts_held": [0, 4],
    "num_experts_per_tok": 2, "num_hidden_layers": 4,
    "num_key_value_heads": 2, "num_shared_experts": 2,
    "position_embedding_type": "rope_gptj", "rope_theta": 50000,
    "rotary_pct": 1, "shared_expert_combination_strategy": "average",
    "sliding_window": 8, "use_parallel_block": True, "use_qk_norm": False,
    "vocab_size": 256, "published": {"num_experts": 16},
}
BACKLOG = {
    "arrival": {"kind": "backlog"},
    "prompt_tokens": {"dist": "choice", "values": [8, 12, 16]},
    "output_tokens": {"dist": "choice", "values": [4, 8]},
    "standing": {"population": "max_batch"},
    "shared_prefix_tokens": 0,
}


def reader(name):
    path = harness.find(rehearsal_util.REPO, BENCH, "layer_metrics", name + ".py")
    return harness.load_module(path)


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    """One traced toy run of a backlog cell of the family: its result,
    its details and the `Run` its readers were handed."""
    root = rehearsal_util.tiny_root(str(tmp_path_factory.mktemp("root")), model=TOY)
    for parts, obj in (
        (("traffic", "toy.json"), BACKLOG),
        (("cells", "tiny.toy.json"), {"backlog_per_s": 400.0}),
    ):
        with open(os.path.join(root, "perfbench", *parts), "w", encoding="utf-8") as f:
            json.dump(obj, f)
    seen, lines = [], []
    read_metrics = harness.read_metrics

    def spy(root_, bench, group, folder, run_):
        seen.append(run_)
        return read_metrics(root_, bench, group, folder, run_)

    with pytest.MonkeyPatch.context() as patch:
        patch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))
        patch.setattr(harness, "read_metrics", spy)
        rc = run.main(
            ["--workload", "tiny.toy", "--seed", str(2**31 + 28), "--seconds", "2",
             "--trace", "1"],
            root=root, devices=jax.devices(), out=lines.append,
        )
    assert rc == 0 and len(seen) == 1
    return json.loads(lines[-1]), json.loads(lines[-2][len("details: "):]), seen[0]


def test_a_backlog_cell_of_the_family_is_correct_end_to_end(toy):
    result, details, run_ = toy
    assert result["correct"] is True and result["failed"] == 0
    # The check's prompt runs past the window: half of the toy table.
    assert details["correct"]["prompt_tokens"] == 64 > TOY["sliding_window"]
    assert 0 <= result["compared"]["behind_best_max"]["value"] <= harness.MODEL_TOL
    # Every slot live, the queue never empty, nothing built in the window.
    assert details["pending_at_close"] > 0 and details["standing"] == 4
    assert set(details["live_at_fifths"]) == {4}
    assert details["programs_built"]["window"]["lowered"] == 0
    assert run_.family.decode_step_counts(run_.model, 10**6, (3, 40))[0] > 10**6


def test_the_two_readers_read_the_programs_counters(toy):
    result, _, run_ = toy
    got = result["metrics"]
    # 4 slots x 2 assignments over 16 published experts: half a token a
    # held expert a step under uniform routing; a toy router is not
    # uniform, so only the bounds are held (at most 2 a slot, 4 experts).
    assert 0 < got["moe_tokens_per_held_expert"]["value"] <= 2 * 4 / 4
    assert 0 < got["moe_experts_touched_share"]["value"] <= 100
    assert got["moe_tokens_per_held_expert"]["unit"] == "tokens"
    steps = 'defer_moe_layer_steps_total{phase="decode",server="paged"}'
    # Four expert layers a decode step; the tick that straddles the
    # window's close is in the counter and not among the window's ticks.
    layer_steps = run_.registry_close[steps] - run_.registry_open[steps]
    assert 0 <= layer_steps - 4 * len(run_.window_ticks()) <= 4
    for name in READERS:
        assert got[name]["value"] == reader(name).read(run_)


def test_the_readers_return_nothing_where_the_program_has_no_such_counter(toy):
    """A dense configuration's cell, or the parent's program: the
    registry holds no expert counters, or they stood still."""
    _, _, run_ = toy
    dense = harness.Run(
        workload={}, model=dict(rehearsal_util.TINY_MODEL), family=None,
        server_args={}, traffic={}, cell={}, chips=1, peaks={}, weight_bytes=0,
        pool_bytes=0, seconds=1.0, t_start=0.0,
    )
    dense.registry_open = {"defer_decode_ticks_total{server=\"paged\"}": 3}
    dense.registry_close = {"defer_decode_ticks_total{server=\"paged\"}": 9}
    stood_still = harness.Run(**{
        **{f.name: getattr(run_, f.name) for f in run_.__dataclass_fields__.values()},
        "registry_open": run_.registry_close,
    })
    for name in READERS:
        assert reader(name).read(dense) is None
        assert reader(name).read(stood_still) is None


def test_by_hand():
    run_ = harness.Run(
        workload={}, model={"num_experts": 16}, family=None, server_args={},
        traffic={}, cell={}, chips=1, peaks={}, weight_bytes=0, pool_bytes=0,
        seconds=1.0, t_start=0.0,
    )
    name = 'defer_moe_{}_total{{phase="decode",server="paged"}}'
    run_.registry_open = {
        name.format("assignments_held"): 100, name.format("experts_touched"): 50,
        name.format("layer_steps"): 10,
    }
    run_.registry_close = {
        name.format("assignments_held"): 100 + 40 * 32,
        name.format("experts_touched"): 50 + 40 * 14, name.format("layer_steps"): 50,
    }
    assert reader("moe_tokens_per_held_expert").read(run_) == 2.0
    assert reader("moe_experts_touched_share").read(run_) == 87.5


def test_the_new_entries_keep_the_contract_with_no_edit_to_it():
    entry = next(c for c in BENCH["configs"] if c["name"].startswith("command-a-plus"))
    cfg = harness.load_json(os.path.join(rehearsal_util.REPO, entry["file"]))
    assert contract.check_config(entry, cfg) == []
    assert cfg["published"]["num_experts"] == 128 and cfg["num_experts"] == 16
    assert cfg["experts_held"] == [0, 16] and cfg["check_prompt_tokens"] == 4096
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (entry["name"], "chat-backlog", 1)
    assert harness.load_json(
        harness.find(rehearsal_util.REPO, BENCH, "cells", CELL + ".json")
    )["backlog_per_s"] > 0
    # The two readers are listed for every cell whose configuration
    # has experts, and for no other.
    files = {c["name"]: c["file"] for c in BENCH["configs"]}
    expert_cells = [
        w["name"] for w in BENCH["workloads"]
        if "num_experts" in harness.load_json(
            os.path.join(rehearsal_util.REPO, files[w["config"]])
        )
    ]
    assert CELL in expert_cells and len(expert_cells) >= 2
    for m in BENCH["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == expert_cells and m["layer"] == "experts"
            assert m["moves"] == "tpot_p50_s"
    reports = {m["name"] for m in harness.cell_metrics(BENCH, "end_to_end", CELL)}
    assert reports == {"tpot_p50_s", "tokens_per_s", "setup_s"}
