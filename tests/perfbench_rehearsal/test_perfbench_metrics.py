"""The metric arithmetic on hand-made stamps."""

import math

import numpy as np
import pytest

import rehearsal_util  # noqa: F401  (puts the repo on sys.path)
from perfbench import metrics, peaks


def ticks(n, period=0.15, tokens=32, start=0.0):
    return [(start + period * (k + 1), tokens) for k in range(n)]


@pytest.mark.parametrize("shift", [0.0, 0.01, 0.07, 0.149])
def test_tokens_per_s_is_unmoved_by_where_the_window_edges_fall_in_a_tick(shift):
    events = ticks(400)
    # A 45.07 s window whose edges fall `shift` into a tick.
    t_open, t_close = 3.0 + shift, 48.07 + shift
    inside = metrics.whole_dispatches(events, t_open, t_close)
    assert metrics.tokens_per_s(inside) == pytest.approx(32 / 0.15, rel=1e-9)


def test_a_count_in_a_fixed_window_does_move_with_its_edges():
    # PR 22's reading, kept here to show what the whole-dispatch rate cures.
    events = ticks(400)
    counted = {
        sum(n for t, n in events if 3.0 + s < t <= 48.07 + s) for s in (0.0, 0.07, 0.149)
    }
    assert len(counted) > 1 and max(counted) - min(counted) == 32


def test_one_slow_slice_moves_the_rate_and_not_the_slice_median():
    events = ticks(100) + [(15.0 + 2.0, 32)] + ticks(200, start=17.0)
    steady = 32 / 0.15
    assert metrics.tokens_per_s(events) < steady * 0.97
    rates = metrics.slice_rates(events)
    assert len(rates) >= 8
    assert metrics.median(rates) == pytest.approx(steady, rel=1e-9)


def test_rate_counts_admissions_first_tokens_as_whole_dispatches():
    events = metrics.whole_dispatches(
        [(1.0, 32), (1.2, 1), (1.35, 32), (9.0, 0), (99.0, 32)], 0.5, 10.0
    )
    assert events == [(1.0, 32), (1.2, 1), (1.35, 32)]
    assert metrics.tokens_per_s(events) == pytest.approx(33 / 0.35)


def test_percentile_is_numpys():
    xs = list(np.random.default_rng(0).normal(size=101))
    for q in (0, 5, 50, 95, 100):
        assert metrics.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    with pytest.raises(ValueError):
        metrics.percentile([], 50)


def test_tpot_itl_and_ttft_on_hand_made_stamps():
    stamps = {
        1: [0.1 * k for k in range(10)],  # 9 gaps of 0.1
        2: [5.0, 5.3],  # too short for tpot, one gap for itl
        3: [1.0 + 0.2 * k for k in range(8)],
    }
    assert sorted(metrics.tpot_per_request(stamps)) == pytest.approx([0.1, 0.2])
    gaps = metrics.inter_token_gaps(stamps)
    assert len(gaps) == 9 + 1 + 7
    assert max(gaps) == pytest.approx(0.3)
    waits = metrics.ttft_per_request({1: 0.0, 2: 4.5, 9: 7.0}, {1: 0.1, 2: 5.0})
    assert waits[:2] == pytest.approx([0.1, 0.5]) and math.isinf(waits[2])


def test_peaks_one_table_unknown_device_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(RuntimeError, match="no peaks"):
        peaks.peaks_for("cpu")


def test_decode_step_bytes_and_least_time_from_shapes():
    model = {
        "hidden_size": 4096, "intermediate_size": 14336,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "num_hidden_layers": 16, "vocab_size": 32000,
    }
    # K and V, 16 layers, 8 heads of 128, bf16: 64 KiB a token.
    assert peaks.kv_bytes_per_row(model) == 65536
    weights = 7_241_736_704
    rows = 32 * 600
    assert peaks.decode_step_bytes(weights, rows, model) == weights + rows * 65536
    least, bound = peaks.decode_step_least_s(
        weights, 32, rows, model, peaks.PEAKS["TPU v5 lite"], 1
    )
    assert bound == "bytes"
    assert least == pytest.approx((weights + rows * 65536) / 819e9)
    # Four chips split weights and cache evenly.
    four, _ = peaks.decode_step_least_s(
        weights, 32, rows, model, peaks.PEAKS["TPU v5 lite"], 4
    )
    assert four == pytest.approx(least / 4)


@pytest.mark.parametrize("stated, head", [(None, 32), (128, 128)])
def test_kv_bytes_per_row_reads_head_dim_where_the_file_has_it(stated, head):
    # 128 Q heads on a hidden size of 4096: the quotient is 32, and a
    # file that states heads of 128 is counted at 128.
    model = {
        "hidden_size": 4096, "num_attention_heads": 128,
        "num_key_value_heads": 8, "num_hidden_layers": 4,
    }
    if stated is not None:
        model["head_dim"] = stated
    assert peaks.head_dim(model) == head
    assert peaks.kv_bytes_per_row(model) == 2 * 4 * 8 * head * 2
    # Q and the output projection are hidden x (heads x head size).
    model.update(intermediate_size=4096, vocab_size=32768)
    per_layer = 2 * 4096 * 128 * head + 2 * 4096 * 8 * head + 3 * 4096 * 4096
    assert peaks.decode_step_flops(1, 0, model) == 2 * (4 * per_layer + 32768 * 4096)


def test_the_default_count_is_the_dense_one_and_least_s_divides_either():
    model = {
        "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 256,
    }
    depths = (5, 9, 30)
    nbytes, flops = peaks.decode_step_counts(model, 1000, depths)
    assert nbytes == peaks.decode_step_bytes(1000, 44, model)
    assert flops == peaks.decode_step_flops(3, 44, model)
    table = {"bytes_per_s": 100.0, "flops_per_s": 1000.0}
    assert peaks.least_s(nbytes, flops, table, 1) == peaks.decode_step_least_s(
        1000, 3, 44, model, table, 1
    )
    assert peaks.least_s(500.0, 100.0, table, 1) == (5.0, "bytes")
    assert peaks.least_s(500.0, 100.0, table, 2) == (2.5, "bytes")
    assert peaks.least_s(50.0, 1000.0, table, 1) == (1.0, "flops")
