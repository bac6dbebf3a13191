"""``benchmark/tools/trace_scopes.py`` on a hand-made trace of two chips
with known answers: nested scopes, a named kernel, a synchronous
all-reduce and an asynchronous collective half hidden under compute."""

import os
from dataclasses import dataclass, field

import pytest

from benchmark.tools import trace_scopes as ts

HLO = """HloModule jit_step, is_scheduled=true

%body (p: f32[8]) -> f32[8] {
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(step)/jvp()/shard_map/while/body/closed_call/attn/dot_general" stack_frame_id=3}
  ROOT %jvp_flash_fwd_.1 = f32[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp()/shard_map/while/body/closed_call/attn/jvp(flash_fwd)/pallas_call" stack_frame_id=4}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %while.1 = f32[8]{0} while(%a), body=%body, metadata={op_name="jit(step)/jvp()/shard_map/while" stack_frame_id=1}
  %fusion.2 = f32[8]{0} fusion(%while.1), kind=kLoop, metadata={op_name="jit(step)/transpose(jvp())/shard_map/mlp/head_loss/mul" stack_frame_id=5}
  %fusion.9 = f32[8]{0} fusion(%fusion.2), kind=kLoop, metadata={op_name="jit(step)/optimizer/add" stack_frame_id=6}
  ROOT %all-reduce.3 = f32[4]{0} all-reduce(%fusion.9), replica_groups={}, metadata={op_name="jit(step)/transpose(jvp())/shard_map/grad_allreduce/psum_invariant" stack_frame_id=7}
}
"""
TEXT = {
    "while.1": "%while.1 = f32[8]{0} while(%a), body=%body",
    "fusion.1": "%fusion.1 = f32[8]{0} fusion(%p), kind=kLoop",
    "flash": '%jvp_flash_fwd_.1 = f32[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call"',
    "fusion.2": "%fusion.2 = f32[8]{0} fusion(%while.1), kind=kLoop",
    "all-reduce.3": "%all-reduce.3 = f32[4]{0} all-reduce(%fusion.9), replica_groups={}",
    "permute": "%collective-permute-start.1 = (f32[8]{0}, f32[8]{0}) collective-permute-start(%fusion.2)",
}


@dataclass
class Event:
    name: str
    start_ns: float
    duration_ns: float
    stats: list = field(default_factory=list)


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


def ev(name, start_s, end_s, **stats):
    return Event(name, start_s * 1e9, (end_s - start_s) * 1e9, list(stats.items()))


def hand_made():
    """Window 0..10 s (the ``serve.step`` span).

    Chip 0: ``while`` 1..5 around ``fusion.1`` 1..3 (attn, forward) and
    the kernel ``flash_fwd`` 3..4 (inside attn); ``fusion.2`` 6..8 (mlp
    around head_loss, transposed); ``all-reduce.3`` 8..9 on the operations
    line; an asynchronous ``collective-permute`` 5..7 whose first second
    falls in an idle gap and whose second runs under ``fusion.2``.
    Busy 7 s; gaps 0..1, 5..6, 9..10.

    Chip 1: ``fusion.1`` 1..3 alone, its ``op_name`` carried as an event
    stat instead of by the HLO map."""
    host = Line("python", [
        ev("serve.step", 0, 10), ev("serve.decode_tick", 0.5, 6, active=2),
        ev("serve.emit", 6, 9.5), ev("not_ours", 0, 10),
    ])
    chip0 = Plane("/device:TPU:0", [
        Line("XLA Modules", [ev("jit_step(7)", 0.9, 9.1)]),
        Line(ts.OP_LINE, [
            ev(TEXT["while.1"], 1, 5), ev(TEXT["fusion.1"], 1, 3),
            ev(TEXT["flash"], 3, 4), ev(TEXT["fusion.2"], 6, 8),
            ev(TEXT["all-reduce.3"], 8, 9),
        ]),
        Line(ts.ASYNC_LINE, [ev(TEXT["permute"], 5, 7)]),
    ])
    chip1 = Plane("/device:TPU:1", [
        Line(ts.OP_LINE, [ev(
            TEXT["fusion.1"], 1, 3,
            tf_op="jit(step)/jvp()/shard_map/while/body/closed_call/attn/dot_general",
        )]),
    ])
    return Profile([Plane(ts.HOST_PLANE, [host]), chip0, chip1])


@pytest.fixture
def reduced(tmp_path):
    (tmp_path / "module_0007.jit_step.tpu_after_optimizations.txt").write_text(HLO)
    (tmp_path / "module_0007.jit_step.before_optimizations.txt").write_text("junk")
    return ts.reduce_scopes(hand_made(), ts.hlo_op_names(str(tmp_path)))


def test_names():
    assert ts.bare("transpose(jvp(attn))") == "attn" and ts.bare("mlp") == "mlp"
    assert ts.scope_of("jit(f)/jvp()/while/body/attn/jvp(flash_fwd)/pallas_call") == "fwd.attn"
    assert ts.scope_of("jit(f)/transpose(jvp())/mlp/head_loss/mul") == "bwd.mlp"
    assert ts.scope_of("jit(f)/jvp(head_loss)/mul") == "fwd.head_loss"  # older wrapping
    assert ts.scope_of("jit(tick)/while/body/page_gather/gather") == "page_gather"
    assert ts.scope_of("jit(f)/while/body/add") == ts.scope_of(None) == "unscoped"
    assert ts.kind_of(TEXT["permute"]) == "collective-permute"
    assert ts.kind_of(TEXT["all-reduce.3"]) == "all-reduce"
    assert ts.kind_of("%psum_invariant.116 = f32[8]{0} fusion(%x)") == "psum"
    assert ts.kind_of(TEXT["fusion.1"]) is None
    assert ts.kernel_of(None, TEXT["flash"]) == "flash_fwd"
    call = '%x.1 = f32[8]{0} custom-call(%y), custom_call_target="tpu_custom_call"'
    assert ts.kernel_of("a/transpose(jvp(flash_bwd_dkv))/pallas_call", call) == "flash_bwd_dkv"
    # a copy the compiler put behind the kernel inherits its op_name, not its name
    assert ts.kernel_of("a/jvp(flash_fwd)/pallas_call", "%copy.3 = f32[8]{0} copy(%x.1)") is None
    assert ts.kernel_of(None, call) == "unnamed_kernel"
    assert ts.kernel_of(None, TEXT["fusion.1"]) is None


def test_hlo_map_reads_optimized_texts_only(tmp_path):
    (tmp_path / "module_0007.jit_step.tpu_after_optimizations.txt").write_text(HLO)
    names = ts.hlo_op_names(str(tmp_path))
    assert set(names) == {"jit_step"}
    assert names["jit_step"]["fusion.2"].endswith("/mlp/head_loss/mul")
    assert names["jit_step"]["jvp_flash_fwd_.1"].endswith("jvp(flash_fwd)/pallas_call")


def test_scope_self_times_sum_to_busy(reduced):
    c0, c1 = reduced["chips"]["0"], reduced["chips"]["1"]
    assert reduced["window_s"] == pytest.approx(10.0)
    assert c0["busy_s"] == pytest.approx(7.0) and c1["busy_s"] == pytest.approx(2.0)
    # the while's own second is nobody's; the kernel's is attention's
    assert c0["scope_self_s"] == {
        "fwd.attn": pytest.approx(3.0), "bwd.mlp": pytest.approx(2.0),
        "unscoped": pytest.approx(1.0), "bwd.grad_allreduce": pytest.approx(1.0),
    }
    assert sum(c0["scope_self_s"].values()) == pytest.approx(c0["busy_s"])
    assert c0["unscoped_top"] == [("while.1 f32[8]", pytest.approx(1.0))]
    assert c0["kernel_self_s"] == {"flash_fwd": pytest.approx(1.0)}
    # chip 1's name came from the event's own stat
    assert c1["scope_self_s"] == {"fwd.attn": pytest.approx(2.0)}
    assert c0["events_without_op_name"] == 0 == c1["events_without_op_name"]


def test_time_outside_every_known_scope_is_reported_by_its_path(reduced):
    """A scope the program gains later is not in ``SCOPES``: its time is
    ``unscoped``, and ``unscoped_paths`` says under which names it ran."""
    assert reduced["chips"]["0"]["unscoped_paths"] == [
        ("jit(step)/jvp()/shard_map", pytest.approx(1.0))  # the while itself
    ]
    chip = Plane("/device:TPU:0", [Line(ts.OP_LINE, [
        ev(TEXT["fusion.1"], 1, 3, tf_op="jit(step)/jvp()/shard_map/rope_cache/mul"),
        ev(TEXT["fusion.2"], 3, 4, tf_op="jit(step)/jvp()/shard_map/rope_cache/add"),
        ev(TEXT["while.1"], 4, 4.5),
    ])])
    c = ts.reduce_scopes(Profile([chip]))["chips"]["0"]
    assert c["scope_self_s"] == {"unscoped": pytest.approx(3.5)}
    assert c["unscoped_paths"] == [
        ("jit(step)/jvp()/shard_map/rope_cache", pytest.approx(3.0)),
        ("(no op_name)", pytest.approx(0.5)),
    ]
    assert ts.path_of("jit(f)/while/body/add") == "jit(f)/while/body"


def test_collectives_by_kind_and_their_exposed_part(reduced):
    c0 = reduced["chips"]["0"]
    assert c0["collective_self_s"] == {"all-reduce": pytest.approx(1.0)}
    assert c0["collective_async_s"] == {"collective-permute": pytest.approx(2.0)}
    # the all-reduce runs alone on the operations line: all of it exposed;
    # the permute's 5..6 meets no operation, its 6..7 hides under fusion.2
    assert c0["collective_exposed_s"] == {
        "all-reduce": pytest.approx(1.0), "collective-permute": pytest.approx(1.0),
    }
    assert c0["collective_exposed_total_s"] == pytest.approx(2.0)
    assert c0["compute_s"] == pytest.approx(6.0)  # busy less the all-reduce
    assert reduced["chips"]["1"]["collective_exposed_total_s"] == 0.0


def test_gaps_are_named_by_the_deepest_span_that_covers_them(reduced):
    # mean over the two chips.  Chip 0: 0..1 under serve.step (the tick
    # covers half of it), 5..6 under step AND tick -> the deeper,
    # 9..10 under serve.step (emit covers half).  Chip 1: 0..1 serve.step;
    # 3..10 serve.step (covers all 7 s; the tick 3, emit 3.5).
    assert reduced["idle_gaps_s"] == {
        "serve.step": pytest.approx((1 + 1 + 1 + 7) / 2),
        "serve.decode_tick": pytest.approx(1 / 2),
    }
    assert reduced["host_spans"]["serve.step"] == {"n": 1, "total_s": pytest.approx(10.0)}
    assert "not_ours" not in reduced["host_spans"]


def test_interval_helpers():
    assert ts.minus([(0, 10)], [(1, 2), (4, 5)]) == [(0, 1), (2, 4), (5, 10)]
    assert ts.minus([(0, 2), (3, 6)], [(1, 4)]) == [(0, 1), (4, 6)]
    assert ts.minus([(0, 1)], []) == [(0, 1)] and ts.minus([(1, 2)], [(0, 3)]) == []
    segs = ts.innermost([(1, 5, 0), (1, 3, 1), (3, 4, 2), (6, 8, 3)])
    assert segs == [(1, 3, 1), (3, 4, 2), (4, 5, 0), (6, 8, 3)]


def test_a_trace_with_no_device_plane_keeps_the_host_spans():
    here = os.path.dirname(os.path.abspath(__file__))
    pd = ts.load(os.path.join(here, "data", "cpu_train_slice.xplane.pb"))
    out = ts.reduce_scopes(pd)
    assert out["chips"] == {} and out["host_spans"]["train_step_wait"]["n"] == 4
