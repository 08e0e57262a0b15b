"""The command end to end at toy widths on the CPU, and the plain
reference against the program's own full forward."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rehearsal_util
from perfbench import peaks, run
from perfbench.families import mistral


@pytest.fixture
def cpu_peaks(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))


def run_tiny(tmp_path, trace, mesh=None, chips=1, **tree):
    root = rehearsal_util.tiny_root(str(tmp_path), mesh=mesh, chips=chips, **tree)
    lines = []
    rc = run.main(
        ["--workload", "tiny.toy", "--seed", str(2**31 + 11), "--seconds", "2",
         "--trace", str(trace)],
        root=root, devices=jax.devices(), out=lines.append,
    )
    assert rc == 0 and lines[-2].startswith("details: ")
    return json.loads(lines[-1]), json.loads(lines[-2][len("details: "):])


def test_last_line_has_exactly_the_contracts_keys(tmp_path, cpu_peaks):
    result, details = run_tiny(tmp_path, trace=0)
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device", "sizing", "compared"]
    # A configuration that states no `check_prompt_tokens`: the default
    # 256, capped at half of the toy table.
    assert details["correct"]["prompt_tokens"] == 64
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == details["requests_due"] > 5
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    bench = rehearsal_util.real_benchmark()
    assert set(result["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    # Warm-up covered every shape: nothing was built inside the window.
    assert details["programs_built"]["window"]["lowered"] == 0
    assert details["standing"] >= 1 and details["late_s_max"] < 0.5
    # Where a stall would show: every stretch of the window is a tick,
    # a seating admission or the time between two calls.
    for key in ("tick_s_max", "admit_s_max", "between_calls_s_max"):
        assert 0 < details[key][0] < 2 and 0 <= details[key][1] < 2
    # Every tick of the window under the rung the program ran it on.
    assert sum(details["ticks_by_span_rows"].values()) >= details["samples"]["ticks"] - 1


def test_traced_run_reports_per_layer_metrics_on_a_mesh(tmp_path, cpu_peaks):
    result, details = run_tiny(tmp_path, trace=1, mesh={"model": 2}, chips=2)
    assert result["correct"] is True
    got = result["metrics"]
    # The CPU trace has no device plane: what reads it returns nothing
    # and is left out; the clocks and counters are there.
    for name in ("tick_p50_s", "dispatches_per_token", "admit_stall_share",
                 "pool_peak_share", "live_slots_mean", "ttft_p50_s.chat"):
        assert got[name]["value"] > 0, name
    assert got["compiles_in_window"]["value"] == 0
    assert "device_idle_share" not in got and "breakdown" not in result
    assert details["trace_lines"] == {}
    # An open loop queues nothing at time 0: no share of a queue to read.
    assert details["queued_at_open"] == 0 and "queue_left_share" not in got


@pytest.mark.parametrize("queue, backlog_per_s", [("outlasts", 4000.0), ("is outlasted", 1.0)])
def test_queue_left_share_says_what_is_left_of_a_backlog(tmp_path, cpu_peaks, queue, backlog_per_s):
    result, details = run_tiny(
        tmp_path, trace=1, traffic=rehearsal_util.TINY_BACKLOG,
        cell={"backlog_per_s": backlog_per_s},
    )
    assert result["correct"] is True and result["failed"] == 0
    queued = details["queued_at_open"]
    assert queued == round(backlog_per_s * 2) and details["standing"] == 4
    left = result["metrics"]["queue_left_share"]
    assert left["unit"] == "%"
    assert left["value"] == pytest.approx(100.0 * details["pending_at_close"] / queued)
    if queue == "outlasts":
        # The window closes on a queue and on full slots, a request
        # seated in its last moments.
        assert 0 < left["value"] < 100 and details["pending_at_close"] > 0
        assert details["live_at_fifths"] == [4] * 5
        assert details["last_seated_s"] > 1.0
    else:
        assert left["value"] == 0 and details["pending_at_close"] == 0
        assert result["attempted"] == queued


def test_refuses_anything_but_a_tpu_and_too_few_chips(tmp_path, cpu_peaks):
    root = rehearsal_util.tiny_root(str(tmp_path), chips=4)
    argv = ["--workload", "tiny.toy", "--seed", "1", "--seconds", "1", "--trace", "0"]
    lines = []
    with pytest.raises(SystemExit, match="TPU"):
        run.main(argv, root=root, out=lines.append)
    with pytest.raises(SystemExit, match="needs 4 chips"):
        run.main(argv, root=root, devices=jax.devices()[:2], out=lines.append)
    assert lines == []


def test_plain_reference_agrees_with_the_programs_full_forward():
    model = dict(rehearsal_util.TINY_MODEL)
    dec = mistral.build_decoder(model)
    params = mistral.make_params(dec, 7)
    assert all(a.dtype == jnp.bfloat16 for a in jax.tree.leaves(params))
    assert float(jnp.abs(params["stack"]["wq"].astype(jnp.float32)).max()) > 0
    assert (params["final_ln_scale"] == 1).all()
    ids = np.random.default_rng(0).integers(1, model["vocab_size"], 48)
    mine = np.asarray(mistral.reference_logits(model, params, ids))
    from defer_tpu.models.gpt import GptDecoder

    ref_dec = GptDecoder(dec.cfg, compute_dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        theirs = np.asarray(
            ref_dec.reference_logits(ref_dec.cast_params(params), jnp.asarray(ids)[None])
        )[0]
    # Both are float32 over the same bf16 weights: only the order of
    # summation differs.
    assert np.max(np.abs(mine - theirs)) <= 1e-4 * np.max(np.abs(theirs))
    # Same seed, same weights; another seed, others.
    again = mistral.make_params(dec, 7)
    other = mistral.make_params(dec, 8)
    assert (again["stack"]["w1"] == params["stack"]["w1"]).all()
    assert not (other["stack"]["w1"] == params["stack"]["w1"]).all()
