"""Family `qwen3_next` through the real harness on the CPU: a toy of
the configuration's shape under a backlog, `correct` by the family's
reference, and the three readers this configuration brings."""

import json
import os

import jax
import pytest

import rehearsal_util
from perfbench import contract, harness, peaks, run, xplane

BENCH = rehearsal_util.real_benchmark()
CELL = "qwen3next-ep4-l8.longanswer-backlog"
CONFIG = "qwen3-next-80b-a3b-instruct-ep4-l8"
READERS = ("linear_state_share", "gdn_step_roofline", "gdn_time_share")
# The configuration's shape at toy sizes: [linear, linear, linear,
# full], 2 key and 4 value heads of 8, 4 Q / 2 KV heads of 16 with 4
# rotary lanes, 16 experts top-3 with 4 held, a gated shared one.
TOY = {
    "source": "none: toy sizes for the CPU rehearsal",
    "family": "qwen3_next", "decoder_sparse_step": 1,
    "full_attention_interval": 4, "head_dim": 16, "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 160, "linear_conv_kernel_dim": 4,
    "linear_key_head_dim": 8, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_value_head_dim": 8,
    "max_position_embeddings": 128, "mlp_only_layers": [],
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts": 4, "experts_held": [0, 4],
    "num_experts_per_tok": 3, "num_hidden_layers": 4,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-6, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 32, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 256,
    "check_prompt_tokens": 21, "published": {"num_experts": 16},
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
    root = rehearsal_util.tiny_root(
        str(tmp_path_factory.mktemp("root")), model=TOY, traffic=BACKLOG,
        cell={"backlog_per_s": 400.0},
    )
    seen, lines = [], []
    read_metrics = harness.read_metrics

    def spy(root_, bench, group, folder, run_):
        seen.append(run_)
        return read_metrics(root_, bench, group, folder, run_)

    with pytest.MonkeyPatch.context() as patch:
        patch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))
        patch.setattr(harness, "read_metrics", spy)
        rc = run.main(
            ["--workload", "tiny.toy", "--seed", str(2**31 + 34), "--seconds", "2",
             "--trace", "1"],
            root=root, devices=jax.devices(), out=lines.append,
        )
    assert rc == 0 and len(seen) == 1
    return json.loads(lines[-1]), json.loads(lines[-2][len("details: "):]), seen[0]


def test_a_backlog_cell_of_the_family_is_correct_end_to_end(toy):
    result, details, run_ = toy
    assert result["correct"] is True and result["failed"] == 0
    # The check's prompt pads (21 rows in a bucket of 32).
    assert details["correct"]["prompt_tokens"] == 21
    assert 0 <= result["compared"]["behind_best_max"]["value"] <= harness.MODEL_TOL
    # Every slot live, the queue never empty, nothing built in the window.
    assert details["pending_at_close"] > 0 and details["standing"] == 4
    assert set(details["live_at_fifths"]) == {4}
    assert details["programs_built"]["window"]["lowered"] == 0
    # The pool's bytes are the K/V of the one full layer.
    assert run_.pool_bytes == 2 * 1 * 40 * 2 * 16 * 16 * 2


def test_the_share_of_the_recurrent_state_reads_the_programs_gauges(toy):
    result, _, run_ = toy
    got = result["metrics"]["linear_state_share"]
    assert got["unit"] == "%" and 0 < got["value"] < 100
    assert got["value"] == reader("linear_state_share").read(run_)
    # All four slots hold a state at both edges; by hand at the close.
    close = run_.registry_close
    state = close['defer_linear_state_pool_bytes{server="paged"}']
    assert state == 3 * 4 * (4 * 8 * 8 + 3 * 64) * 4  # S and 3 rows, float32
    live = close['defer_linear_state_slots_live{server="paged"}']
    assert 1 <= live <= 4  # a slot may be between two requests
    used = close['defer_pool_blocks_used{server="paged"}']
    kv = used * run_.pool_bytes / 40
    edge = reader("linear_state_share").share(close, run_)
    assert edge == pytest.approx(100 * state * live / 4 / (state * live / 4 + kv))
    # The expert layer's readers read in this cell too.
    assert 0 < result["metrics"]["moe_experts_touched_share"]["value"] <= 100


def test_the_readers_return_nothing_where_the_program_has_no_such_thing(toy):
    """The parent's program, or a configuration without recurrent
    layers: no such gauge, no operation of that name."""
    _, _, run_ = toy
    fields = {f.name: getattr(run_, f.name) for f in run_.__dataclass_fields__.values()}
    no_gauge = harness.Run(**{
        **fields,
        "registry_open": {'defer_pool_blocks_used{server="paged"}': 3},
        "registry_close": {'defer_pool_blocks_used{server="paged"}': 9},
    })
    assert reader("linear_state_share").read(no_gauge) is None
    empty_pool = harness.Run(**{
        **fields,
        "registry_open": {
            **run_.registry_open,
            'defer_linear_state_pool_bytes{server="paged"}': 0,
        },
    })
    assert reader("linear_state_share").read(empty_pool) is None
    # A CPU trace holds no operation named for the kernel.
    assert reader("gdn_step_roofline").read(run_) is None
    assert reader("gdn_time_share").read(run_) is None
    untraced = harness.Run(**{**fields, "trace": None})
    assert reader("gdn_step_roofline").read(untraced) is None
    assert reader("gdn_time_share").read(untraced) is None


def test_the_kernels_readers_by_hand(toy):
    """A synthetic slice: three calls of the kernel among other
    operations."""
    _, _, run_ = toy
    with open(os.path.join(rehearsal_util.REPO, "perfbench", "configs", CONFIG + ".json"),
              encoding="utf-8") as f:
        model = json.load(f)
    trace = xplane.Reduced(
        window_s=1.0, busy_s=0.1, busy_by_device={}, spans=[("tick", 0.0, 1.0)],
        gaps=[], union=[],
        ops=[("gdn_step.3", 0.0, 0.002), ("fusion.7", 0.1, 0.010),
             ("gdn_step.3", 0.2, 0.001), ("flash_decode", 0.3, 0.005),
             ("gdn_step.4", 0.4, 0.0008)],
    )
    fields = {f.name: getattr(run_, f.name) for f in run_.__dataclass_fields__.values()}
    run2 = harness.Run(**{
        **fields, "trace": trace, "model": model,
        "server_args": model["server"], "peaks": peaks.PEAKS["TPU v5 lite"],
    })
    # One layer's states of 128 slots, read and written: 536.9 MB at
    # 819 GB/s is 0.6555 ms, over the median call's 1 ms.
    nbytes = 2 * 128 * 32 * 128 * 128 * 4
    assert reader("gdn_step_roofline").read(run2) == pytest.approx(
        100 * (nbytes / 819e9) / 0.001
    )
    assert reader("gdn_time_share").read(run2) == pytest.approx(
        100 * 0.0038 / 0.0188
    )


def test_the_new_entries_keep_the_contract_with_no_edit_to_it():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    cfg = harness.load_json(os.path.join(rehearsal_util.REPO, entry["file"]))
    assert contract.check_config(entry, cfg) == []
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json"
    )
    assert cfg["published"]["num_experts"] == 512 and cfg["num_experts"] == 128
    assert cfg["experts_held"] == [0, 128] and cfg["check_prompt_tokens"] == 1500
    assert cfg["vocab_size"] * 4 == cfg["published"]["vocab_size"]
    assert cfg["num_hidden_layers"] == 2 * contract.layer_period(cfg["published"])
    for key in ("published", "reduced", "assumed", "deployment", "server"):
        assert cfg[key]
    assert "EP4 x 8 layers" in cfg["deployment"] and "2.5 tokens" in cfg["deployment"]
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "longanswer-backlog", 1)
    assert harness.load_json(
        harness.find(rehearsal_util.REPO, BENCH, "cells", CELL + ".json")
    )["backlog_per_s"] > 0
    mix = harness.load_json(
        harness.find(rehearsal_util.REPO, BENCH, "traffic", "longanswer-backlog.json")
    )
    assert mix["prompt_tokens"]["high"] + mix["output_tokens"]["high"] <= cfg["max_position_embeddings"]
    for m in BENCH["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_p50_s"
    # The two `moe_*` readers list this cell too since PR 36 (PR 28's
    # rehearsal holds them to every cell whose configuration has
    # experts).
    for name in ("tokens_per_s", "tokens_per_s_slice_p50", "queue_left_share",
                 "moe_tokens_per_held_expert", "moe_experts_touched_share"):
        m = next(m for g in ("end_to_end", "per_layer") for m in BENCH[g] if m["name"] == name)
        assert m["workloads"][-1] == CELL
    reports = {m["name"] for m in harness.cell_metrics(BENCH, "end_to_end", CELL)}
    assert reports == {"tpot_p50_s", "tokens_per_s", "setup_s"}
