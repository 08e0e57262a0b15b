"""The trace reduction on a small synthetic plane."""

import dataclasses

import pytest

import rehearsal_util  # noqa: F401
from perfbench import xplane


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float


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


def ms(name, start, dur):
    return Ev(name, start * 1e6, dur * 1e6)


def synthetic(chips=1):
    # Host: tick [0,100], admit [100,150], tick [150,250] (ms).
    host = Plane("/host:CPU", [Line("python3", [
        ms("tick", 0, 100), ms("admit", 100, 50), ms("tick", 150, 100),
        ms("something else", 10, 5),
    ])])
    # Device: in tick 1 a while [10,70] holding copy [10,40] and a
    # kernel [40,60]; in admit a fusion [110,140]; in tick 2 an
    # all-reduce [160,170] and a kernel [170,230]; one op after the
    # last span, which the window leaves out.
    ops = [
        ms("while.1", 10, 60), ms("copy.2", 10, 30),
        ms("closed_call.3 custom-call tpu_custom_call", 40, 20),
        ms("fusion.4", 110, 30), ms("all-reduce.5", 160, 10),
        ms("closed_call.3 custom-call tpu_custom_call", 170, 60),
        ms("copy.2", 300, 50),
    ]
    devices = [
        Plane(f"/device:TPU:{i}", [Line("XLA Ops", ops), Line("Steps", [ms("s", 0, 999)])])
        for i in range(chips)
    ]
    return Profile([host] + devices)


def test_busy_idle_and_window():
    red = xplane.reduce_profile(synthetic())
    assert red.window_s == pytest.approx(0.250)
    # union: [10,70] + [110,140] + [160,230] = 160 ms
    assert red.busy_s == pytest.approx(0.160)
    assert sum(b - a for a, b in red.gaps) == pytest.approx(0.090)
    idle = dict(xplane.idle_by_span(red))
    # tick: [0,10]+[70,100] and [150,160]+[230,250] = 70; admit: 10+10
    assert idle["tick"] == pytest.approx(0.070)
    assert idle["admit"] == pytest.approx(0.020)
    assert idle["other"] == pytest.approx(0.0, abs=1e-12)


def test_self_time_top_ops_and_shares():
    red = xplane.reduce_profile(synthetic())
    top = dict(xplane.top_device_ops(red))
    assert top["tick/closed_call.3 custom-call tpu_custom_call"] == pytest.approx(0.080)
    assert top["tick/copy.2"] == pytest.approx(0.030)
    assert top["tick/while.1"] == pytest.approx(0.010)  # 60 less its body's 50
    assert top["admit/fusion.4"] == pytest.approx(0.030)
    assert xplane.time_share(red, xplane.KERNEL_MARKS) == pytest.approx(50.0)
    assert xplane.time_share(red, ("all-reduce",)) == pytest.approx(6.25)
    assert xplane.busy_per_span(red, "tick") == pytest.approx([0.060, 0.070])
    assert xplane.busy_per_span(red, "admit") == pytest.approx([0.030])


def test_mean_over_chips_and_nothing_to_read():
    red = xplane.reduce_profile(synthetic(chips=4))
    assert len(red.busy_by_device) == 4 and red.busy_s == pytest.approx(0.160)
    assert xplane.describe(synthetic())["/device:TPU:0"] == {"XLA Ops": 7, "Steps": 1}
    no_device = Profile(synthetic().planes[:1])
    assert xplane.reduce_profile(no_device) is None
    no_spans = Profile(synthetic().planes[1:])
    assert xplane.reduce_profile(no_spans) is None
