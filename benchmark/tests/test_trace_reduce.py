"""The reduction from a profiler trace to numbers, on a hand-made trace
whose answers are known, and on a small recorded one."""

import os
from dataclasses import dataclass, field

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
SPANS = {"train_step_dispatch", "train_step_wait", "harness_client"}


@dataclass
class Event:
    name: str
    start_ns: float
    duration_ns: float


@dataclass
class Line:
    name: str
    events: list = field(default_factory=list)


@dataclass
class Plane:
    name: str
    lines: list = field(default_factory=list)


@dataclass
class Profile:
    planes: list


def ev(name, start_s, end_s):
    return Event(name, start_s * 1e9, (end_s - start_s) * 1e9)


def hand_made(chips=1):
    """Window 0..10 s.  The chip runs a ``while`` 1..5 that encloses
    ``fusion.1`` 1..3 and ``flash_fwd`` 3..4, then ``fusion.1`` 7..9:
    busy 6 s, idle gaps 0..1, 5..7 and 9..10."""
    ops = [ev("while", 1, 5), ev("fusion.1", 1, 3), ev("flash_fwd", 3, 4),
           ev("fusion.1", 7, 9)]
    host = Line("python", [
        ev("train_step_dispatch", 0, 1.5), ev("train_step_wait", 1.5, 5.2),
        ev("harness_client", 5.2, 6.9), ev("train_step_wait", 6.9, 10),
        ev("not_ours", 0, 10),
    ])
    planes = [Plane(tr.HOST_PLANE, [host])]
    for i in range(chips):
        planes.append(Plane(f"/device:TPU:{i}", [
            Line("XLA Modules", [ev("jit_step", 1, 9)]),
            Line(tr.OP_LINE, list(ops)),
        ]))
    return Profile(planes)


def test_busy_time_is_the_union_of_operation_intervals():
    r = tr.reduce_trace(hand_made(), SPANS)
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(10.0)
    assert r["busy_s"] == pytest.approx(6.0)  # not 4 + 2 + 1 + 2 = 9


def test_an_enclosing_operation_does_not_count_its_body_twice():
    r = tr.reduce_trace(hand_made(), SPANS)
    assert r["op_self_s"]["while"] == pytest.approx(1.0)   # 4 s less 2 + 1 inside
    assert r["op_self_s"]["fusion.1"] == pytest.approx(4.0)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(4.0)]
    assert tr.op_seconds(r, ["flash_"]) == pytest.approx(1.0)
    assert sum(r["op_self_s"].values()) == pytest.approx(r["busy_s"])


def test_idle_gaps_are_named_after_what_the_host_was_doing():
    r = tr.reduce_trace(hand_made(), SPANS)
    gaps = dict((n, s) for n, s in r["idle_gaps"])
    # 0..1 under dispatch; 5..7 mostly under harness_client (1.7 of 2 s);
    # 9..10 under the wait
    assert gaps == {"train_step_dispatch": pytest.approx(1.0),
                    "harness_client": pytest.approx(2.0),
                    "train_step_wait": pytest.approx(1.0)}
    assert r["longest_gaps"][0][:2] == ["harness_client", pytest.approx(2.0)]


def test_several_chips_are_averaged():
    prof = hand_made(chips=2)
    prof.planes[2].lines[1].events.append(ev("all-reduce.3", 9, 10))
    r = tr.reduce_trace(prof, SPANS)
    assert r["chips"] == 2 and r["busy_s_per_chip"] == [pytest.approx(6.0), pytest.approx(7.0)]
    assert r["busy_s"] == pytest.approx(6.5)
    assert r["op_self_s"]["all-reduce.3"] == pytest.approx(0.5)  # mean over chips


def test_a_trace_with_no_device_plane_reduces_to_nothing():
    assert tr.reduce_trace(Profile([Plane(tr.HOST_PLANE, [])]), SPANS) is None


def test_recorded_cpu_trace_holds_the_harness_spans():
    """A few steps of the tiny configuration recorded on the CPU: the host
    plane carries the harness's spans; there is no device plane."""
    pd = tr.load(os.path.join(HERE, "data", "cpu_train_slice.xplane.pb"))
    spans = tr.host_spans(pd, SPANS)
    names = [n for _, _, n in spans]
    assert names.count("train_step_dispatch") == names.count("train_step_wait") == 4
    assert all(e >= s for s, e, _ in spans) and spans == sorted(spans)
    assert tr.device_ops(pd) == {} and tr.reduce_trace(pd, SPANS) is None


def test_interval_helpers():
    assert tr.merge([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert tr.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
