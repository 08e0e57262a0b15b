"""The reader of the tick dispatch's launch ahead, `tick_ahead_share`:
on a synthetic run, then out of a toy backlog through the real harness
on the CPU."""

import dataclasses

import pytest

import rehearsal_util
from perfbench import harness
from test_perfbench_run import cpu_peaks, run_tiny  # noqa: F401

BENCH = rehearsal_util.real_benchmark()
AHEAD = 'defer_tick_ahead_total{server="paged"}'
TICKS = 'defer_decode_ticks_total{server="paged"}'


@dataclasses.dataclass
class FakeRun:
    registry_open: dict
    registry_close: dict


def read(run):
    path = harness.find(rehearsal_util.REPO, BENCH, "layer_metrics", "tick_ahead_share.py")
    return harness.load_module(path).read(run)


def test_the_share_is_of_what_moved_across_the_window():
    run = FakeRun({AHEAD: 40, TICKS: 52}, {AHEAD: 235, TICKS: 252})
    assert read(run) == pytest.approx(100.0 * 195 / 200)
    # A counter the server registered as the window opened at zero.
    assert read(FakeRun({TICKS: 3}, {AHEAD: 9, TICKS: 13})) == pytest.approx(90.0)


def test_nothing_to_read_is_none():
    # A program without the counter, as the parent commit is.
    assert read(FakeRun({TICKS: 5}, {TICKS: 90})) is None
    # No step in the window.
    assert read(FakeRun({AHEAD: 7, TICKS: 8}, {AHEAD: 7, TICKS: 8})) is None


def test_the_entry_is_appended_and_every_cell_reports_it():
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == "tick_ahead_share"]
    assert BENCH["per_layer"][-1] == entry == {
        "name": "tick_ahead_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "tick dispatch",
        "moves": "tpot_p50_s",
    }
    for cell in BENCH["workloads"]:
        assert entry in harness.cell_metrics(BENCH, "per_layer", cell["name"])


def test_a_backlog_that_keeps_its_slots_full_reads_nearly_all_ahead(tmp_path, cpu_peaks):
    result, details = run_tiny(
        tmp_path, trace=1, traffic=rehearsal_util.TINY_BACKLOG,
        cell={"backlog_per_s": 4000.0},
    )
    assert result["correct"] is True and result["failed"] == 0
    assert details["live_at_fifths"] == [4] * 5
    share = result["metrics"]["tick_ahead_share"]
    assert share["unit"] == "%" and 90 <= share["value"] <= 100
