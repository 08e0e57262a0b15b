"""The program's spans and its decode step off the trace's own clock
(`perfbench/program_trace.py`): on a synthetic plane, then a toy run
through the real harness on the CPU."""

import dataclasses
import json

import pytest

import rehearsal_util
from perfbench import program_spans, program_trace, xplane
from test_perfbench_run import cpu_peaks  # noqa: F401

STEP = "paged_decode_step"


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float
    stats: tuple = ()


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


@dataclasses.dataclass
class Profile:
    planes: list


def ms(name, a, b, **stats):
    return Ev(name, a * 1e6, (b - a) * 1e6, tuple(stats.items()))


def program_spans_ms(ids=True):
    """The serving thread's spans (ms). With `ids` each carries its
    record's id, parent and rid, as the program's annotations do;
    without, the name alone (a program from before)."""
    def sp(name, a, b, id, parent=None, rid=None):
        meta = {"id": id, "parent": parent, "rid": rid} if ids else {}
        return ms(name, a, b, **{k: v for k, v in meta.items() if v is not None})

    return [
        ms("tick", 0, 100),
        sp("paged.tick", 1, 99, 1),
        sp("paged.tick.plan", 2, 5, 2, 1), sp("paged.tick.dispatch", 5, 8, 3, 1),
        sp("paged.tick.sync", 8, 70, 4, 1), sp("paged.tick.drain", 70, 95, 5, 1),
        sp("paged.finish", 80, 85, 6, 5, rid=7), sp("paged.tick.sample", 95, 98, 7, 1),
        ms("admit", 100, 150),
        sp("paged.admit", 101, 149, 8),
        sp("paged.admit.seat", 102, 148, 9, 8, rid=3),
        sp("paged.admit.seat.prefill", 103, 140, 10, 9),
        sp("paged.admit.seat.first_token", 140, 147, 11, 9),
        ms("tick", 150, 250),
        sp("paged.tick", 151, 249, 12),
        sp("paged.tick.plan", 152, 156, 13, 12), sp("paged.tick.dispatch", 156, 160, 14, 12),
        sp("paged.tick.sync", 160, 232, 15, 12), sp("paged.tick.drain", 232, 245, 16, 12),
        sp("paged.tick.sample", 245, 248, 17, 12),
    ]


def device_ops():
    """Two executions of the step (58 and 68 ms busy), the admission's
    prefill and a sample between them; as on a v5e, the ops carry no
    module and the plane's module line says which program ran when."""
    step = f"jit_{STEP}"
    ops = [
        ms("fusion.1", 10, 40), ms("fusion.2", 40, 68), ms("argmax.3", 96, 97),
        ms("fusion.7", 110, 138), ms("fusion.1", 162, 200), ms("fusion.2", 200, 230),
    ]
    modules = [
        ms(f"{step}(42)", 10, 68, run_id=1), ms("jit__argmax(9)", 96, 97, run_id=2),
        ms("jit_step(7)", 110, 138, run_id=3), ms(f"{step}(42)", 162, 230, run_id=4),
        # a program whose name begins with the step's is another program
        ms(f"{step}_x(5)", 232, 240, run_id=5),
    ]
    return [Line("XLA Ops", ops), Line("XLA Modules", modules)]


def profile(ids=True, other_thread=True, spans=True):
    host = [line for line in [
        Line("python3", program_spans_ms(ids) if spans else [
            e for e in program_spans_ms(ids) if not e.name.startswith("paged.")
        ]),
        # Another thread's spans, the whole slice long: never charged.
        Line("worker", [ms("paged.tick", 0, 250, id=99), ms("paged.tick.sync", 0, 250)]),
    ] if other_thread or line.name == "python3"]
    return Profile([Plane("/host:CPU", host), Plane("/device:TPU:0", device_ops())])


def read(prof, name=STEP):
    red = xplane.reduce_profile(prof)
    return program_trace.reduce(prof, red, name), red


def test_each_idle_gap_goes_to_the_innermost_span_open():
    pt, red = read(profile())
    # Idle: [0,10] [68,96] [97,110] [138,162] [230,250] = 95 of 250 ms.
    assert sum(b - a for a, b in red.gaps) == pytest.approx(0.095)
    got = {k: round(v * 1e3, 9) for k, v in program_trace.idle_by_program_span(pt, red.gaps).items()}
    assert got == {
        "paged.tick.drain": 33, "paged.tick.sync": 8, "paged.admit.seat.prefill": 9,
        "paged.tick.plan": 7, "paged.tick.dispatch": 7, "paged.admit.seat.first_token": 7,
        "none": 6, "paged.tick.sample": 5, "paged.finish": 5, "paged.tick/self": 4,
        "paged.admit.seat/self": 2, "paged.admit/self": 2,
    }


def test_the_four_shares_and_the_ticks_own_time_add_up_to_device_idle():
    pt, red = read(profile())
    shares = program_trace.idle_shares(pt, red)
    want = {"launch": 19, "readback": 46, "seat": 20, "outside": 6, "tick": 4, "other": 0}
    assert shares == pytest.approx({k: 100 * v / 250 for k, v in want.items()})
    device_idle = 100.0 * (1.0 - red.busy_s / red.window_s)
    assert sum(shares.values()) == pytest.approx(device_idle)
    got = program_trace.readings(pt, red)
    assert list(got) == list(program_trace.NAMES)
    four = sum(v for k, v in got.items() if k.endswith("_share"))
    assert device_idle - four == pytest.approx(shares["tick"])


def test_annotations_without_ids_read_by_name_and_other_threads_are_not_charged():
    with_ids = read(profile())
    by_name = read(profile(ids=False))
    alone = read(profile(other_thread=False))
    assert by_name[0].spans[0].id is None and with_ids[0].spans[0].id == 1
    assert [s[:3] for s in by_name[0].spans] == [s[:3] for s in with_ids[0].spans]
    (seat,) = [s for s in with_ids[0].spans if s.name == "paged.admit.seat"]
    assert (seat.parent, seat.rid) == (8, 3)
    for pt, red in (by_name, alone):
        assert program_trace.readings(pt, red) == program_trace.readings(*with_ids)


def test_the_step_is_one_execution_of_its_own_program(monkeypatch):
    pt, red = read(profile())
    # Not the admission's prefill (28 ms), the sample between steps, nor
    # a program whose name only begins with the step's.
    assert [round(s * 1e3, 9) for _, _, s in pt.steps] == [58, 68]
    assert program_trace.step_device_s_p50(pt) == pytest.approx(0.063)
    # The program's own name is the one the test names.
    assert program_trace.step_name() == STEP
    # A program that does not name its step: nothing to read.
    monkeypatch.setattr(program_trace, "step_name", lambda: None)
    none = program_trace.reduce(profile(), red)
    assert none.steps == [] and program_trace.step_device_s_p50(none) is None


def test_a_trace_without_the_programs_spans_reads_none_and_never_raises():
    pt, red = read(profile(spans=False, other_thread=False))
    assert pt.spans == [] and program_trace.readings(pt, red) == dict.fromkeys(
        program_trace.NAMES
    )
    assert program_trace.idle_by_program_span(pt, red.gaps) == {
        "none": pytest.approx(0.095)
    }


@dataclasses.dataclass
class Rec:
    id: int
    parent: int | None
    name: str
    t0: float
    t1: float


@dataclasses.dataclass
class Snap:
    records: list
    complete: bool = True


@dataclasses.dataclass
class FakeRun:
    t_open: float
    t_close: float


def test_the_longest_call_brings_every_span_under_it(monkeypatch):
    recs = [
        Rec(1, None, "paged.tick", 0.0, 0.01), Rec(2, 1, "paged.tick.sync", 0.001, 0.009),
        Rec(3, None, "paged.tick", 0.02, 1.5), Rec(4, 3, "paged.tick.plan", 0.021, 0.03),
        Rec(5, 3, "paged.tick.sync", 0.03, 1.4), Rec(6, 5, "paged.finish", 1.41, 1.42),
        Rec(7, None, "paged.admit", 1.6, 1.7),
    ]

    def snapshot(lo, hi):
        return Snap([r for r in recs if r.t1 > lo and (hi is None or r.t1 <= hi)])

    monkeypatch.setattr(program_spans, "_snapshot", snapshot)
    run = FakeRun(-1.0, 2.0)
    secs, begun, under = program_trace.longest_call(run, "paged.tick")
    assert (secs, begun) == pytest.approx((1.48, 1.02))
    assert [n for n, _ in under] == ["paged.tick.plan", "paged.tick.sync", "paged.finish"]
    assert [s for _, s in under] == pytest.approx([0.009, 1.37, 0.01])
    assert program_trace.longest_call(run, "paged.admit")[2] == []
    assert program_trace.longest_call(run, "paged.settle") is None


def test_a_toy_run_prints_the_longest_calls_phases(tmp_path, cpu_peaks):  # noqa: F811
    import jax

    root = rehearsal_util.tiny_root(str(tmp_path))
    lines = []
    rc = program_trace.main(
        ["--workload", "tiny.toy", "--seed", str(2**31 + 13), "--seconds", "2",
         "--trace", "1"],
        root=root, devices=jax.devices(), out=lines.append,
    )
    assert rc == 0 and lines[-3].startswith("details: ")
    assert lines[-2].startswith("program_trace: ")
    got = json.loads(lines[-2][len("program_trace: "):])
    result = json.loads(lines[-1])
    assert result["correct"] is True and "metrics" in result
    secs, begun, under = got["longest_tick"]
    assert 0 < secs and 0 <= begun <= 2.5
    assert {"paged.tick.sync", "paged.tick.drain"} <= {n for n, _ in under}
    assert sum(s for n, s in under if n.startswith("paged.tick.")) <= secs
    assert got["tick_p50_s"] > 0
    # No device plane on the CPU: nothing to lay the spans over.
    assert not set(program_trace.NAMES) & set(got)
