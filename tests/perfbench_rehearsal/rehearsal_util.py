"""A tiny benchmark tree for the CPU rehearsals: the real harness,
readers and family under a BENCHMARK.json of toy sizes."""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY_MODEL = {
    "source": "none: toy sizes for the CPU rehearsal",
    "family": "mistral",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 256,
    "max_position_embeddings": 128, "sliding_window": 128,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-05,
}
TINY_TRAFFIC = {
    "arrival": {"kind": "poisson", "share_of_knee": 0.8},
    "prompt_tokens": {"dist": "choice", "values": [8, 12, 16]},
    "output_tokens": {"dist": "choice", "values": [4, 8]},
    "standing": {"population": "rate_x_lifetime"},
    "shared_prefix_tokens": 0,
}


def real_benchmark() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


TINY_BACKLOG = dict(
    TINY_TRAFFIC, arrival={"kind": "backlog"}, standing={"population": "max_batch"}
)
TINY_CELL = {"knee_per_s": 8.0, "lifetime_s": 0.4}


def tiny_root(
    tmp: str, mesh=None, chips: int = 1, model: dict = TINY_MODEL, families=None,
    traffic: dict = TINY_TRAFFIC, cell: dict = TINY_CELL,
) -> str:
    """BENCHMARK.json's metrics over one toy cell of `model` under
    `traffic`, sized by `cell`, in `tmp`. The code directories are
    links to the real ones; the family files of the directory
    `families`, where one is given, are linked beside the real
    families."""
    bench = real_benchmark()
    real = os.path.join(REPO, "perfbench")
    os.makedirs(os.path.join(tmp, "perfbench"))
    for folder in ("end_to_end", "layer_metrics"):
        os.symlink(os.path.join(real, folder), os.path.join(tmp, "perfbench", folder))
    for folder in ("configs", "traffic", "cells", "families"):
        os.makedirs(os.path.join(tmp, "perfbench", folder))
    for source in filter(None, (os.path.join(real, "families"), families)):
        for name in os.listdir(source):
            if name.endswith(".py"):
                os.symlink(
                    os.path.join(source, name),
                    os.path.join(tmp, "perfbench", "families", name),
                )
    model = dict(model)
    model["server"] = {"num_blocks": 40, "block_size": 16, "max_batch": 4, "mesh": mesh}

    def dump(obj, *parts):
        with open(os.path.join(tmp, "perfbench", *parts), "w", encoding="utf-8") as f:
            json.dump(obj, f)

    dump(model, "configs", "tiny.json")
    dump(traffic, "traffic", "toy.json")
    dump(cell, "cells", "tiny.toy.json")
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            m.pop("workloads", None)
    bench["configs"] = [{"name": "tiny", "source": "none", "file": "perfbench/configs/tiny.json", "reduced": [], "why": "toy"}]
    bench["workloads"] = [{"name": "tiny.toy", "config": "tiny", "traffic": "toy", "chips": chips, "why": "toy"}]
    bench["paths"] = ["perfbench"]
    with open(os.path.join(tmp, "BENCHMARK.json"), "w", encoding="utf-8") as f:
        json.dump(bench, f)
    return tmp
