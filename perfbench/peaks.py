"""The one table of device peaks, and the bytes and operations a
decode step needs, computed from shapes. Kept with the benchmark so
that no PR that claims a gain can change what "100%" means.
"""

from __future__ import annotations

# Keyed by jax's `device_kind`. A device that is not here is an error,
# never a default.
PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 16 GB of HBM at 819 GB/s, per chip.
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "bytes_per_s": 819e9,
        "memory_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise RuntimeError(
            f"no peaks for device kind {device_kind!r}: add it to "
            "perfbench/peaks.py with its source"
        ) from None


def head_dim(model: dict) -> int:
    """A head's size: the file's `head_dim` where it states one, the
    quotient of hidden size and heads where the source has no such key."""
    return model.get("head_dim") or model["hidden_size"] // model["num_attention_heads"]


def kv_bytes_per_row(model: dict, itemsize: int = 2) -> int:
    """Bytes of K and V one cached token holds over all layers."""
    return (
        2 * model["num_hidden_layers"] * model["num_key_value_heads"]
        * head_dim(model) * itemsize
    )


def decode_step_bytes(weight_bytes: int, live_kv_rows: float, model: dict) -> float:
    """The least a decode step must read: every weight once (the tied
    head included: the embedding is read whole for the logits) and the
    K and V rows of every live token. What it writes (one row a slot)
    and the activations are thousands of times less and left out."""
    return weight_bytes + live_kv_rows * kv_bytes_per_row(model)


def decode_step_flops(live_slots: float, live_kv_rows: float, model: dict) -> float:
    """Operations of a decode step: two per weight and token in the
    matrices (attention projections, SwiGLU, tied head), and four per
    cached row, Q head and head dimension in attention."""
    d = model["hidden_size"]
    f = model["intermediate_size"]
    hq = model["num_attention_heads"]
    dh = head_dim(model)
    dkv = model["num_key_value_heads"] * dh
    per_layer = 2 * d * hq * dh + 2 * d * dkv + 3 * d * f
    matrices = model["num_hidden_layers"] * per_layer + model["vocab_size"] * d
    attention = 4 * model["num_hidden_layers"] * hq * dh * live_kv_rows
    return 2 * matrices * live_slots + attention


def decode_step_counts(model: dict, weight_bytes: int, depths) -> tuple[float, float]:
    """(bytes, operations): the least a decode step must read and
    compute with one live slot at each of `depths` cached rows. This
    is the dense GQA count, linear in the rows; a family whose step is
    not that (experts, a window under a longer table) brings its own
    `decode_step_counts` of the same signature in `families/<family>.py`."""
    rows = sum(depths)
    return (
        decode_step_bytes(weight_bytes, rows, model),
        decode_step_flops(len(depths), rows, model),
    )


def least_s(nbytes: float, flops: float, peaks: dict, chips: int) -> tuple[float, str]:
    """The least time one chip of `chips` could take for these bytes
    and operations, split evenly over the chips, and which peak bounds
    it."""
    by_bytes = nbytes / (chips * peaks["bytes_per_s"])
    by_flops = flops / (chips * peaks["flops_per_s"])
    if by_bytes >= by_flops:
        return by_bytes, "bytes"
    return by_flops, "flops"


def decode_step_least_s(
    weight_bytes: int, live_slots: float, live_kv_rows: float,
    model: dict, peaks: dict, chips: int,
) -> tuple[float, str]:
    """`least_s` of the dense GQA count at these live slots and rows."""
    return least_s(
        decode_step_bytes(weight_bytes, live_kv_rows, model),
        decode_step_flops(live_slots, live_kv_rows, model),
        peaks, chips,
    )
