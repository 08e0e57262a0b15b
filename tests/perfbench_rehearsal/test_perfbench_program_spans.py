"""The readers of the program's spans, on a synthetic trace and span
log; then the seven metrics out of a toy run on the CPU."""

import dataclasses
import os

import pytest

import rehearsal_util
from perfbench import harness, program_spans, xplane
from test_perfbench_run import cpu_peaks, run_tiny  # noqa: F401
from test_perfbench_xplane import Line, Plane, ms, synthetic

BENCH = rehearsal_util.real_benchmark()
NEW = [
    "tick_launch_s_p50", "tick_sync_s_p50", "tick_drain_s_p50",
    "idle_launch_share", "idle_drain_share", "idle_admit_share",
    "admit_device_busy_share",
]
# The profiler's clock minus the host's, in the synthetic run.
SHIFT = 1000.0


@dataclasses.dataclass
class Rec:
    id: int
    parent: int | None
    name: str
    t0: float
    t1: float
    rid: int | None = None
    counts: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Snap:
    records: list
    complete: bool = True


@dataclasses.dataclass
class FakeRun:
    trace: object
    ticks: list
    t_open: float
    t_close: float


def host_ms(a, b):
    """Profiler milliseconds -> host-clock seconds."""
    return a * 1e-3 - SHIFT, b * 1e-3 - SHIFT


def span_log():
    """The program's spans under synthetic()'s host spans (tick
    [0,100], admit [100,150], tick [150,250] ms on the profiler's
    clock), on the host's clock. Device busy: [10,70], [110,140],
    [160,230]."""
    recs, n = [], iter(range(1, 99))

    def tick(a, plan, dispatch, sample, sync, drain, b):
        tid = next(n)
        edges = [plan, dispatch, sample, sync, drain, b]
        names = ["plan", "dispatch", "sample", "sync", "drain"]
        for name, lo, hi in zip(names, edges, edges[1:]):
            recs.append(Rec(next(n), tid, f"paged.tick.{name}", *host_ms(lo, hi)))
        recs.append(Rec(tid, None, "paged.tick", *host_ms(a, b)))

    # idle under tick 1: [0,10] (plan 4, dispatch 4, sample 1) and
    # [70,100] (sync's tail 2, drain 28).
    tick(0, 1, 5, 9, 12, 72, 100)
    aid = next(n)
    sid = next(n)
    recs.append(Rec(sid, aid, "paged.admit.seat", *host_ms(102, 148), rid=3))
    recs.append(Rec(aid, None, "paged.admit", *host_ms(101, 149)))
    # idle under tick 2: [150,160] (plan 3, dispatch 5, sample 1) and
    # [230,250] (sync's tail 5, drain 15).
    tick(150, 151, 154, 159, 161, 235, 250)
    return recs


@pytest.fixture
def run(monkeypatch):
    recs = span_log()

    def snapshot(t_lo, t_hi):
        kept = [
            r for r in recs
            if (t_lo is None or r.t1 > t_lo) and (t_hi is None or r.t1 <= t_hi)
        ]
        return Snap(sorted(kept, key=lambda r: r.t1))

    monkeypatch.setattr(program_spans, "_snapshot", snapshot)
    red = xplane.reduce_profile(synthetic())
    # The harness stamps t0 and enters its annotation back to back:
    # here 20 and 30 microseconds apart.
    ticks = [(0.0 - SHIFT - 20e-6, 0.100 - SHIFT, 2, 2, 9),
             (0.150 - SHIFT - 30e-6, 0.250 - SHIFT, 2, 2, 11)]
    return FakeRun(red, ticks, t_open=-SHIFT - 1.0, t_close=-SHIFT + 1.0)


def read(name, run):
    path = harness.find(rehearsal_util.REPO, BENCH, "layer_metrics", name + ".py")
    return harness.load_module(path).read(run)


def test_offset_recovers_the_shift_and_refuses_a_disagreement(run):
    assert program_spans.offset(run) == pytest.approx(SHIFT + 25e-6, abs=1e-9)
    # Ticks from before the trace began are matched from the end.
    run.ticks.insert(0, (-SHIFT - 5.0, -SHIFT - 4.9, 2, 2, 7))
    assert program_spans.offset(run) == pytest.approx(SHIFT + 25e-6, abs=1e-9)
    t0, *rest = run.ticks[-1]
    run.ticks[-1] = (t0 - 5e-3, *rest)
    assert program_spans.offset(run) is None
    for name in NEW[3:]:
        assert read(name, run) is None


def test_the_idle_shares_and_the_remainder_add_up(run):
    launch = read("idle_launch_share", run)
    drain = read("idle_drain_share", run)
    admit = read("idle_admit_share", run)
    # Of a 250 ms slice. The offset is 25 microseconds out, which
    # moves every edge by as much.
    assert launch == pytest.approx(100 * (4 + 4 + 3 + 5) / 250, abs=0.05)
    assert drain == pytest.approx(100 * (28 + 15) / 250, abs=0.05)
    assert admit == pytest.approx(100 * (9 + 9) / 250, abs=0.05)
    rest = program_spans.idle_share(
        run, ("paged.tick.sample", "paged.tick.sync")
    )
    assert rest == pytest.approx(100 * (1 + 2 + 1 + 5) / 250, abs=0.05)
    # What no program span covers: the harness's own loop, between
    # its span's edge and the program's (1 ms at four edges here).
    total = read("device_idle_share", run)
    assert total == pytest.approx(100 * 90 / 250)
    assert launch + drain + admit <= total
    assert total - (launch + drain + admit + rest) == pytest.approx(
        100 * 4 / 250, abs=0.05
    )
    # The seat spans [102,148]: busy [110,140] of 46 ms.
    assert read("admit_device_busy_share", run) == pytest.approx(
        100 * 30 / 46, abs=0.1
    )


def test_the_phase_medians_read_the_window_on_the_hosts_clock(run):
    assert read("tick_launch_s_p50", run) == pytest.approx((8 + 8) / 2 * 1e-3)
    assert read("tick_sync_s_p50", run) == pytest.approx((60 + 74) / 2 * 1e-3)
    assert read("tick_drain_s_p50", run) == pytest.approx((28 + 15) / 2 * 1e-3)
    # A window that opens inside tick 1, after its dispatch returned:
    # that tick lacks a phase and is left out of the launch time.
    run.t_open = 0.010 - SHIFT
    assert read("tick_launch_s_p50", run) == pytest.approx(8e-3)
    assert read("tick_drain_s_p50", run) == pytest.approx((28 + 15) / 2 * 1e-3)


def test_nothing_to_read_is_none_and_never_raises(run, monkeypatch):
    traced_run = dataclasses.replace(run)
    run.trace = None
    for name in NEW[:3]:
        assert read(name, run) > 0
    for name in NEW[3:]:
        assert read(name, run) is None
    # A log that no longer reaches back to the window's opening.
    whole = program_spans._snapshot
    monkeypatch.setattr(
        program_spans, "_snapshot",
        lambda lo, hi: Snap(whole(lo, hi).records, complete=False),
    )
    assert [read(name, traced_run) for name in NEW] == [None] * 7
    # A program without a span log, as the parent commit is.
    monkeypatch.setattr(program_spans, "_snapshot", lambda lo, hi: None)
    assert [read(name, traced_run) for name in NEW] == [None] * 7


def test_program_annotations_leave_the_harnesss_spans_as_they_were():
    plain = synthetic()
    noisy = synthetic()
    host = noisy.planes[0].lines[0].events
    host += [
        ms("paged.tick", 0.5, 99), ms("paged.tick.plan", 1, 4),
        ms("paged.tick.drain", 72, 27), ms("paged.admit", 101, 48),
        ms("paged.admit.seat", 102, 46), ms("paged.finish", 80, 5),
    ]
    noisy.planes.append(Plane("/host:other", [Line("t", [ms("paged.tick", 3, 5)])]))
    a, b = xplane.reduce_profile(plain), xplane.reduce_profile(noisy)
    assert a.spans == b.spans and a.gaps == b.gaps and a.window_s == b.window_s
    assert xplane.idle_by_span(a) == xplane.idle_by_span(b)
    assert xplane.busy_per_span(a, "tick") == xplane.busy_per_span(b, "tick")


def test_the_entries_are_appended_and_every_reader_is_a_file():
    added = [m for m in BENCH["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in added] == NEW
    for m in added:
        assert m["moves"] == "tpot_p50_s" and "workloads" not in m
        assert m["source"] in ("host_clock", "device_trace")
        assert os.path.exists(os.path.join(
            rehearsal_util.REPO, "perfbench", "layer_metrics", m["name"] + ".py"
        ))


def test_a_toy_run_reads_the_programs_spans(tmp_path, cpu_peaks):  # noqa: F811
    result, details = run_tiny(tmp_path, trace=1)
    got = result["metrics"]
    for name in NEW[:3]:
        assert got[name]["value"] > 0 and got[name]["unit"] == "s"
    # No device plane on the CPU: nothing to lay the spans over.
    assert not set(NEW[3:]) & set(got)
    phases = sum(got[n]["value"] for n in NEW[:3])
    assert phases < 3 * got["tick_p50_s"]["value"]
