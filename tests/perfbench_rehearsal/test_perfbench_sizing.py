"""What a run says of its queue: the result's `sizing` key, and the
line on standard error where a traced slice held no device operation.
Toy widths on the CPU, through the real harness."""

import json
import re
import shutil
import types

import jax
import pytest

import rehearsal_util
from perfbench import harness, peaks, run, xplane

SIZING_KEYS = ["queued_at_open", "pending_at_close", "last_seated_s", "live_at_close", "idle_tail_s"]


def run_backlog(tmp, backlog_per_s, trace, seconds=2):
    root = rehearsal_util.tiny_root(
        str(tmp), traffic=rehearsal_util.TINY_BACKLOG, cell={"backlog_per_s": backlog_per_s}
    )
    lines = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))
        rc = run.main(
            ["--workload", "tiny.toy", "--seed", str(2**31 + 36), "--seconds", str(seconds),
             "--trace", str(trace)],
            root=root, devices=jax.devices(), out=lines.append,
        )
    assert rc == 0 and lines[-2].startswith("details: ")
    return json.loads(lines[-1]), json.loads(lines[-2][len("details: "):])


@pytest.fixture(scope="module")
def outlasts(tmp_path_factory):
    return run_backlog(tmp_path_factory.mktemp("outlasts"), 4000.0, trace=0)


@pytest.fixture(scope="module")
def served_dry(tmp_path_factory):
    return run_backlog(tmp_path_factory.mktemp("dry"), 1.0, trace=0, seconds=4)


def test_sizing_stands_before_compared_with_its_five_numbers(outlasts):
    result, details = outlasts
    assert list(result) == [
        "correct", "attempted", "failed", "metrics", "device", "sizing", "compared",
    ]
    sizing = result["sizing"]
    assert list(sizing) == SIZING_KEYS
    assert all(isinstance(v, (int, float)) for v in sizing.values())
    for key in ("queued_at_open", "pending_at_close", "last_seated_s"):
        assert sizing[key] == details[key]


def test_a_queue_that_outlasts_the_window_closes_on_full_slots(outlasts):
    result, details = outlasts
    sizing = result["sizing"]
    assert sizing["queued_at_open"] == 8000 and sizing["pending_at_close"] > 7000
    # Every slot holds a request as the window closes, one was seated
    # in its last moments, and the last token fell a tick before it
    # (a toy tick, on a CPU that six test workers share).
    assert sizing["live_at_close"] == 4 == details["live_at_fifths"][-1]
    assert sizing["last_seated_s"] > 1.0
    assert 0 <= sizing["idle_tail_s"] < 1.0


def test_a_queue_served_dry_reads_no_live_slot_and_an_idle_tail(served_dry):
    result, details = served_dry
    sizing = result["sizing"]
    assert result["correct"] is True and result["failed"] == 0
    assert sizing["queued_at_open"] == 4 and sizing["pending_at_close"] == 0
    assert sizing["live_at_close"] == 0
    # 4 queued + 4 standing toy requests are answered well inside the
    # 4 s: the server then stands idle to the window's close.
    assert 1.0 < sizing["idle_tail_s"] < 4.0
    assert sizing["last_seated_s"] < 4.0 - sizing["idle_tail_s"]
    # (A standing request with one token left ends as it is seated.)
    assert details["requests_finished_in_window"] in (7, 8)


def _host_only_profile():
    """A trace as an idle server leaves it: `admit` spans on a host
    plane, a device plane whose `XLA Ops` line holds no event."""
    spans = [types.SimpleNamespace(name="admit", start_ns=i * 10**6, duration_ns=10**5) for i in range(50)]
    host = types.SimpleNamespace(
        name="/host:CPU", lines=[types.SimpleNamespace(name="python", events=spans)]
    )
    device = types.SimpleNamespace(
        name="/device:TPU:0", lines=[types.SimpleNamespace(name=xplane.OPS_LINE, events=[])]
    )
    return types.SimpleNamespace(planes=[host, device])


def test_an_empty_traced_slice_says_so_on_standard_error(tmp_path, capfd, monkeypatch):
    profile = _host_only_profile()
    assert xplane.reduce_profile(profile) is None

    def read_trace(trace_dir):
        shutil.rmtree(trace_dir, ignore_errors=True)
        return xplane.describe(profile), xplane.reduce_profile(profile)

    monkeypatch.setattr(harness, "read_trace", read_trace)
    result, details = run_backlog(tmp_path, 1.0, trace=1, seconds=4)
    assert details["trace_lines"] == {"/device:TPU:0": {xplane.OPS_LINE: 0}}
    # The result line is whole but for what only a trace gives: the
    # driver reads the missing `busy_s` as a malformed result, and
    # `sizing` beside the line on standard error names the cause.
    assert "busy_s" not in result["device"] and "breakdown" not in result
    assert list(result)[-2:] == ["sizing", "compared"]
    sizing = result["sizing"]
    assert sizing["pending_at_close"] == 0 and sizing["live_at_close"] == 0
    err = capfd.readouterr().err.strip().splitlines()
    said = [line for line in err if line.startswith("traced slice empty: ")]
    assert len(said) == 1
    t, window, seated = re.fullmatch(
        r"traced slice empty: last token at (\S+) s of (\S+) s, queue dry at (\S+) s",
        said[0],
    ).groups()
    assert float(window) == details["seconds"]
    assert float(t) == pytest.approx(float(window) - sizing["idle_tail_s"])
    assert float(seated) == sizing["last_seated_s"] < float(t)
    # The numbers compared stay the last lines of standard error.
    assert err[-1].startswith("compared: behind_best_max ")
    assert err.index(said[0]) < len(err) - 1


def test_an_untraced_run_prints_no_such_line(tmp_path, capfd):
    result, _ = run_backlog(tmp_path, 1.0, trace=0)
    assert "sizing" in result
    assert "traced slice empty" not in capfd.readouterr().err
