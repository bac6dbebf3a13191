"""The hazard linter: per-rule synthetic positives + clean baselines.

Two contracts pinned here:

1. **Every rule fires** — each H-rule gets one deliberately-hazardous
   synthetic HLO module (and each S-rule one pitfall Python snippet)
   proving the rule detects what it claims, plus a near-miss showing it
   stays quiet when the hazard is absent.
2. **Every strategy is clean** — every registered strategy (all
   twenty-three, the rule-table and speculative-serving variants
   included) compiles with ZERO
   unwaived findings on this jax, the same
   way PR 2 pinned their collective signatures.  A refactor that
   introduces a sync-collective pileup, a donation miss, an axis leak,
   or a participant-stream mismatch fails here (and the ``graft-lint``
   CI job) before it ever reaches a TPU.

The strategy compiles ride the shared session cache in
``tests/conftest.py`` — one compile per strategy per test session,
shared with test_xla_analytics's signature pins and test_sched's
overlap-bound pins.
"""

import json

import pytest

from ddl25spring_tpu.analysis import engine, source_lint
from ddl25spring_tpu.analysis.rules import (
    DEFAULT_THRESHOLDS,
    Finding,
    severity_rank,
    worst_severity,
)
from ddl25spring_tpu.analysis.waivers import apply_waivers, load_waivers
from ddl25spring_tpu.obs.compile_report import DEFAULT_STRATEGIES
from ddl25spring_tpu.utils.mesh import make_mesh
from conftest import cached_strategy_report as _report  # lower-once cache


def _rules_fired(findings):
    return {f.rule for f in findings}


def _lint(hlo, **kw):
    kw.setdefault("obs_enabled", False)
    kw.setdefault("waivers", [])
    return engine.lint_hlo_text(hlo, **kw)


# --------------------------------------------------------- rule positives

_ADD = """\
%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(f32[] %a, f32[] %b)
}
"""

H001_SYNC = f"""\
HloModule h001
{_ADD}
ENTRY %main (x: f32[1048576]) -> f32[1048576] {{
  %x = f32[1048576]{{0}} parameter(0)
  ROOT %ar = f32[1048576]{{0}} all-reduce(f32[1048576]{{0}} %x), replica_groups={{{{0,1,2,3}}}}, to_apply=%add
}}
"""


def test_h001_sync_collective_fires_and_async_is_exempt():
    fs = _lint(H001_SYNC)
    assert "H001" in _rules_fired(fs)
    f = next(f for f in fs if f.rule == "H001")
    assert f.severity == "warn"
    # ring all-reduce over 4 devices: 2*(n-1)/n x the 4 MiB payload
    assert f.bytes == int(2 * 4 * 1048576 * 3 / 4)
    # the async spelling of the same op is the fix, not a finding
    fs2 = _lint(H001_SYNC.replace("all-reduce(", "all-reduce-start("))
    assert "H001" not in _rules_fired(fs2)
    # below the byte threshold: scalar loss pmeans must never fire
    small = H001_SYNC.replace("1048576]", "8]")
    assert "H001" not in _rules_fired(_lint(small))


# a PROPERLY paired async all-reduce: -start issues, independent
# compute runs (the overlap), -done collects — the exact shape the
# overlapped strategies must lower to on hardware with async-collective
# support, and the fix H001's hint prescribes
H001_ASYNC_PAIRED = f"""\
HloModule h001async
{_ADD}
ENTRY %main (x: f32[1048576], y: f32[1048576]) -> f32[1048576] {{
  %x = f32[1048576]{{0}} parameter(0)
  %y = f32[1048576]{{0}} parameter(1)
  %ars = f32[1048576]{{0}} all-reduce-start(f32[1048576]{{0}} %x), replica_groups={{{{0,1,2,3}}}}, to_apply=%add
  %w = f32[1048576]{{0}} multiply(f32[1048576]{{0}} %y, f32[1048576]{{0}} %y)
  %ard = f32[1048576]{{0}} all-reduce-done(f32[1048576]{{0}} %ars)
  ROOT %out = f32[1048576]{{0}} add(f32[1048576]{{0}} %ard, f32[1048576]{{0}} %w)
}}
"""


def test_h001_paired_async_collective_passes():
    """The negative the overlap work pins: a 4 MiB all-reduce lowered
    as a start/done pair with intervening compute is the OVERLAPPED
    form — H001 must stay quiet, and the parser must count the pair as
    ONE async op site (the -done op never double-counts)."""
    fs = _lint(H001_ASYNC_PAIRED)
    assert "H001" not in _rules_fired(fs)
    from ddl25spring_tpu.obs.xla_analytics import parse_hlo_collectives

    ops = parse_hlo_collectives(H001_ASYNC_PAIRED)
    ars = [o for o in ops if o["kind"] == "all-reduce"]
    assert len(ars) == 1
    assert ars[0]["async"] is True
    assert ars[0]["result_bytes"] == 4 * 1048576


def test_h001_judges_wire_bytes_not_result_shape():
    """A reduce-scatter's RESULT is payload/n, but (n-1) result-sized
    shards cross the wire — the rule must catch it despite the small
    result shape."""
    rs = f"""\
HloModule h001rs
{_ADD}
ENTRY %main (x: f32[524288]) -> f32[131072] {{
  %x = f32[524288]{{0}} parameter(0)
  ROOT %rs = f32[131072]{{0}} reduce-scatter(f32[524288]{{0}} %x), replica_groups={{{{0,1,2,3}}}}, dimensions={{0}}, to_apply=%add
}}
"""
    fs = _lint(rs)
    f = next(f for f in fs if f.rule == "H001")
    # result = 512 KiB (under the 1 MiB threshold), wire = (n-1) x result
    # = 1.5 MiB (over it): only the wire measure catches this one
    assert 131072 * 4 < DEFAULT_THRESHOLDS["h001_sync_bytes"] <= f.bytes


H002_INVERSE = f"""\
HloModule h002
{_ADD}
ENTRY %main (x: f32[8,64]) -> f32[8,64] {{
  %x = f32[8,64]{{1,0}} parameter(0)
  %ag = f32[32,64]{{1,0}} all-gather(f32[8,64]{{1,0}} %x), replica_groups={{{{0,1,2,3}}}}, dimensions={{0}}
  ROOT %rs = f32[8,64]{{1,0}} reduce-scatter(f32[32,64]{{1,0}} %ag), replica_groups={{{{0,1,2,3}}}}, dimensions={{0}}, to_apply=%add
}}
"""

H002_GATHER_SLICE = """\
HloModule h002b
ENTRY %main (x: f32[8,64], i: s32[]) -> f32[2,64] {
  %x = f32[8,64]{1,0} parameter(0)
  %i = s32[] parameter(1)
  %zero = s32[] constant(0)
  %ag = f32[32,64]{1,0} all-gather(f32[8,64]{1,0} %x), replica_groups={{0,1,2,3}}, dimensions={0}
  %r = f32[32,64]{1,0} reshape(f32[32,64]{1,0} %ag)
  ROOT %ds = f32[2,64]{1,0} dynamic-slice(f32[32,64]{1,0} %r, s32[] %i, s32[] %zero), dynamic_slice_sizes={2,64}
}
"""


def test_h002_inverse_pair_and_gather_then_slice():
    assert "H002" in _rules_fired(_lint(H002_INVERSE))
    # the walk crosses pass-through ops (reshape) to find the gather
    fs = _lint(H002_GATHER_SLICE)
    assert any(
        f.rule == "H002" and "dynamic-sliced" in f.message for f in fs
    )
    # gather NOT feeding its inverse (or a slice) is quiet
    solo = H002_GATHER_SLICE.replace(
        "f32[32,64]{1,0} %r, s32[] %i", "f32[32,64]{1,0} %x2, s32[] %i"
    ).replace(
        "%r = f32[32,64]{1,0} reshape(f32[32,64]{1,0} %ag)",
        "%x2 = f32[32,64]{1,0} broadcast(f32[8,64]{1,0} %x), dimensions={0,1}",
    )
    assert "H002" not in _rules_fired(_lint(solo))


# optimized HLO routinely fuses the consumer: the dynamic-slice lives in
# a fused computation whose parameter 0 is the caller's all-gather
H002_FUSED_SLICE = """\
HloModule h002c
%fused_slice (p0: f32[32,64], p1: s32[]) -> f32[2,64] {
  %p0 = f32[32,64]{1,0} parameter(0)
  %p1 = s32[] parameter(1)
  %z = s32[] constant(0)
  ROOT %ds = f32[2,64]{1,0} dynamic-slice(f32[32,64]{1,0} %p0, s32[] %p1, s32[] %z), dynamic_slice_sizes={2,64}
}
ENTRY %main (x: f32[8,64], i: s32[]) -> f32[2,64] {
  %x = f32[8,64]{1,0} parameter(0)
  %i = s32[] parameter(1)
  %ag = f32[32,64]{1,0} all-gather(f32[8,64]{1,0} %x), replica_groups={{0,1,2,3}}, dimensions={0}
  ROOT %f = f32[2,64]{1,0} fusion(f32[32,64]{1,0} %ag, s32[] %i), kind=kLoop, calls=%fused_slice
}
"""


def test_h002_sees_through_fusion_computations():
    """Fusion bodies are reachable (the multiplier walk only follows
    control flow) and the producer walk climbs from a fused parameter
    back to the caller's operand — the fused form of gather-then-slice
    must not hide the hazard."""
    fs = _lint(H002_FUSED_SLICE)
    assert any(
        f.rule == "H002" and "dynamic-sliced" in f.message for f in fs
    )


H003_UNKNOWN_TRIP = """\
HloModule h003a
%body (p: (s32[], f32[4,8])) -> (s32[], f32[4,8]) {
  %p = (s32[], f32[4,8]{1,0}) parameter(0)
  %c = s32[] get-tuple-element((s32[], f32[4,8]{1,0}) %p), index=0
  %g = f32[4,8]{1,0} get-tuple-element((s32[], f32[4,8]{1,0}) %p), index=1
  %cp = f32[4,8]{1,0} collective-permute(f32[4,8]{1,0} %g), source_target_pairs={{0,1},{1,0}}
  ROOT %t = (s32[], f32[4,8]{1,0}) tuple(%c, %cp)
}
%cond (p: (s32[], f32[4,8])) -> pred[] {
  %p = (s32[], f32[4,8]{1,0}) parameter(0)
  ROOT %lt = pred[] constant(false)
}
ENTRY %main (x: f32[4,8]) -> f32[4,8] {
  %x = f32[4,8]{1,0} parameter(0)
  %c0 = s32[] constant(0)
  %t = (s32[], f32[4,8]{1,0}) tuple(%c0, %x)
  %w = (s32[], f32[4,8]{1,0}) while((s32[], f32[4,8]{1,0}) %t), condition=%cond, body=%body
  ROOT %out = f32[4,8]{1,0} get-tuple-element((s32[], f32[4,8]{1,0}) %w), index=1
}
"""


def test_h003_unknown_trip_count_fires_and_known_is_quiet():
    fs = _lint(H003_UNKNOWN_TRIP)
    assert any(
        f.rule == "H003" and "unknown trip" in f.message for f in fs
    )
    known = H003_UNKNOWN_TRIP.replace(
        "condition=%cond, body=%body",
        'condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"7"}}',
    )
    # trip known AND the permute's operand changes each iteration (the
    # carry slot holds the permute result): nothing to report
    assert "H003" not in _rules_fired(_lint(known))


# carry slot 1 is returned untouched (ROOT passes gte 1 through) yet the
# all-gather re-sends it every one of the 7 annotated iterations
H003_HOISTABLE = """\
HloModule h003b
%body (p: (s32[], f32[128])) -> (s32[], f32[128]) {
  %p = (s32[], f32[128]{0}) parameter(0)
  %c = s32[] get-tuple-element((s32[], f32[128]{0}) %p), index=0
  %inv = f32[128]{0} get-tuple-element((s32[], f32[128]{0}) %p), index=1
  %ag = f32[512]{0} all-gather(f32[128]{0} %inv), replica_groups={{0,1,2,3}}, dimensions={0}
  %one = s32[] constant(1)
  %c2 = s32[] add(s32[] %c, s32[] %one)
  ROOT %t = (s32[], f32[128]{0}) tuple(%c2, %inv)
}
%cond (p: (s32[], f32[128])) -> pred[] {
  %p = (s32[], f32[128]{0}) parameter(0)
  ROOT %lt = pred[] constant(false)
}
ENTRY %main (x: f32[128]) -> f32[128] {
  %x = f32[128]{0} parameter(0)
  %c0 = s32[] constant(0)
  %t = (s32[], f32[128]{0}) tuple(%c0, %x)
  %w = (s32[], f32[128]{0}) while((s32[], f32[128]{0}) %t), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"7"}}
  ROOT %out = f32[128]{0} get-tuple-element((s32[], f32[128]{0}) %w), index=1
}
"""


def test_h003_loop_invariant_collective_is_hoistable():
    fs = _lint(H003_HOISTABLE)
    assert any(
        f.rule == "H003" and "loop-invariant" in f.message for f in fs
    )


H004_UPCAST = f"""\
HloModule h004
{_ADD}
ENTRY %main (x: bf16[1024]) -> f32[1024] {{
  %x = bf16[1024]{{0}} parameter(0)
  %cv = f32[1024]{{0}} convert(bf16[1024]{{0}} %x)
  ROOT %ar = f32[1024]{{0}} all-reduce(f32[1024]{{0}} %cv), replica_groups={{{{0,1,2,3}}}}, to_apply=%add
}}
"""


def test_h004_upcast_before_collective():
    fs = _lint(H004_UPCAST)
    f = next(f for f in fs if f.rule == "H004")
    assert "bf16" in f.message and "2x" in f.message
    # down-casting before the wire is the FIX, never a finding
    down = H004_UPCAST.replace(
        "%cv = f32[1024]{0} convert(bf16[1024]{0} %x)",
        "%cv = f32[1024]{0} convert(f64[1024]{0} %y)",
    )
    assert "H004" not in _rules_fired(_lint(down))


H005_MISS = """\
HloModule h005, input_output_alias={ {1}: (1, {}, may-alias) }
ENTRY %main (p0: f32[262144], p1: f32[262144], b: f32[64]) -> (f32[262144], f32[262144]) {
  %p0 = f32[262144]{0} parameter(0), metadata={op_name="params[\'w\']"}
  %p1 = f32[262144]{0} parameter(1), metadata={op_name="opt_state[0]"}
  %b = f32[64]{0} parameter(2), metadata={op_name="batch"}
  ROOT %t = (f32[262144]{0}, f32[262144]{0}) tuple(%p0, %p1)
}
"""


def test_h005_donation_miss_only_for_donatable_params():
    report = {"donation": {"donatable_leaves": 2}, "lowered": "train_step"}
    fs = _lint(H005_MISS, report=report)
    missed = [f for f in fs if f.rule == "H005"]
    # param 0 (1 MiB, donatable, unaliased) fires; param 1 is aliased;
    # the batch input (#2) is beyond donatable_leaves and exempt
    assert len(missed) == 1
    assert missed[0].op == "params['w']"
    assert missed[0].severity == "error"
    assert missed[0].bytes == 4 * 262144
    # without donatable info (forward-only lowering) the rule claims
    # nothing
    assert "H005" not in _rules_fired(_lint(H005_MISS, report=None))


H006_CALLBACK = """\
HloModule h006
ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %c = s64[] constant(7)
  %cc = () custom-call(s64[] %c, f32[8]{0} %x), custom_call_target="xla_python_cpu_callback", custom_call_has_side_effect=true
  ROOT %y = f32[8]{0} add(f32[8]{0} %x, f32[8]{0} %x)
}
"""


def test_h006_host_roundtrip_gated_on_obs():
    fs = _lint(H006_CALLBACK, obs_enabled=False)
    assert any(f.rule == "H006" and f.severity == "error" for f in fs)
    # instrumentation ON means the host cost was requested
    assert "H006" not in _rules_fired(
        _lint(H006_CALLBACK, obs_enabled=True)
    )
    outfeed = H006_CALLBACK.replace(
        'custom-call(s64[] %c, f32[8]{0} %x), custom_call_target='
        '"xla_python_cpu_callback", custom_call_has_side_effect=true',
        "outfeed(f32[8]{0} %x, token[] %tok)",
    ).replace(
        "%c = s64[] constant(7)", "%tok = token[] after-all()"
    )
    assert "H006" in _rules_fired(_lint(outfeed, obs_enabled=False))


H007_DUP_TARGET = """\
HloModule h007
ENTRY %main (x: f32[4,8]) -> f32[4,8] {
  %x = f32[4,8]{1,0} parameter(0)
  ROOT %cp = f32[4,8]{1,0} collective-permute(f32[4,8]{1,0} %x), source_target_pairs={{0,1},{2,1},{1,3}}
}
"""


def test_h007_mismatched_permute_cycle():
    fs = _lint(H007_DUP_TARGET)
    f = next(f for f in fs if f.rule == "H007")
    assert "repeats a target" in f.message
    ok = H007_DUP_TARGET.replace("{0,1},{2,1},{1,3}", "{0,1},{1,2},{2,0}")
    assert "H007" not in _rules_fired(_lint(ok))
    # duplicate SOURCES are legal one-to-many multicast, never a finding
    multicast = H007_DUP_TARGET.replace(
        "{0,1},{2,1},{1,3}", "{0,1},{0,2},{1,3}"
    )
    assert "H007" not in _rules_fired(_lint(multicast))


H007_AXIS_LEAK = f"""\
HloModule h007b
{_ADD}
ENTRY %main (x: f32[4,8]) -> f32[4,8] {{
  %x = f32[4,8]{{1,0}} parameter(0)
  ROOT %ar = f32[4,8]{{1,0}} all-reduce(f32[4,8]{{1,0}} %x), replica_groups={{{{0,1,2,3}}}}, to_apply=%add
}}
"""


@pytest.fixture(scope="module")
def mesh22(devices8):
    return make_mesh(devices8[:4], outer=2, inner=2)


def test_h007_axis_leak_against_declared_signature(mesh22):
    # groups {0,1,2,3} span BOTH axes of the 2x2 mesh; the signature
    # only declares traffic on "inner"
    report = {"expected": {"all-reduce": {"axes": ["inner"]}}}
    fs = _lint(H007_AXIS_LEAK, mesh=mesh22, report=report)
    assert any(f.rule == "H007" and "axis leak" in f.message for f in fs)
    # declaring both axes clears it
    report2 = {"expected": {"all-reduce": {"axes": ["inner", "outer"]}}}
    fs2 = _lint(H007_AXIS_LEAK, mesh=mesh22, report=report2)
    assert "H007" not in _rules_fired(fs2)
    # no declaration at all -> the rule has no baseline to judge against
    assert "H007" not in _rules_fired(_lint(H007_AXIS_LEAK, mesh=mesh22))


# ------------------------------------------ sched rule pack (H008-H009)

# 4 MiB async pair closed immediately: the cosmetic-overlap shape the
# PR-9 motivation names — H001's has-a-pair test passes it trivially,
# H008 must not
H008_ZERO_SLACK_PAIR = f"""\
HloModule h008
{_ADD}
ENTRY %main (x: f32[1048576], a: f32[512,512], b: f32[512,512]) -> f32[1048576] {{
  %x = f32[1048576]{{0}} parameter(0)
  %a = f32[512,512]{{1,0}} parameter(1)
  %b = f32[512,512]{{1,0}} parameter(2)
  %ars = f32[1048576]{{0}} all-reduce-start(f32[1048576]{{0}} %x), replica_groups={{{{0,1,2,3}}}}, to_apply=%add
  %ard = f32[1048576]{{0}} all-reduce-done(f32[1048576]{{0}} %ars)
  %d = f32[512,512]{{1,0}} dot(f32[512,512]{{1,0}} %a, f32[512,512]{{1,0}} %b), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}
  ROOT %out = f32[1048576]{{0}} add(f32[1048576]{{0}} %ard, f32[1048576]{{0}} %ard)
}}
"""


def test_h008_zero_slack_async_pair_fires():
    fs = _lint(H008_ZERO_SLACK_PAIR)
    f = next(f for f in fs if f.rule == "H008")
    assert f.severity == "warn"
    assert "cosmetic" in f.message
    # H001 is satisfied by the pair — exactly the blind spot H008 covers
    assert "H001" not in _rules_fired(fs)


def test_h008_near_miss_pair_with_real_window_is_quiet():
    # the same pair with the 2*512^3-FLOP dot INSIDE the window (above
    # 1% of the transfer's wire time on the reference chip): overlapped
    # for real, H008 stays quiet
    moved = H008_ZERO_SLACK_PAIR.replace(
        "  %ard = f32[1048576]{0} all-reduce-done(f32[1048576]{0} %ars)\n"
        "  %d = f32[512,512]{1,0} dot(f32[512,512]{1,0} %a, "
        "f32[512,512]{1,0} %b), lhs_contracting_dims={1}, "
        "rhs_contracting_dims={0}\n",
        "  %d = f32[512,512]{1,0} dot(f32[512,512]{1,0} %a, "
        "f32[512,512]{1,0} %b), lhs_contracting_dims={1}, "
        "rhs_contracting_dims={0}\n"
        "  %ard = f32[1048576]{0} all-reduce-done(f32[1048576]{0} %ars)\n",
    )
    assert "H008" not in _rules_fired(_lint(moved))
    # below the byte threshold nothing fires either way
    small = H008_ZERO_SLACK_PAIR.replace("1048576]", "1024]")
    assert "H008" not in _rules_fired(_lint(small))


def test_h008_judges_overlap_declared_sync_collectives_too():
    """An overlap-DECLARED strategy (describe meta overlap=True) whose
    big sync collective has no dataflow-independent work is the same
    cosmetic claim without the async spelling — H008 fires; give the
    window real independent compute and it clears."""
    sync_big = f"""\
HloModule h008b
{_ADD}
ENTRY %main (x: f32[1048576], a: f32[512,512], b: f32[512,512]) -> f32[1048576] {{
  %x = f32[1048576]{{0}} parameter(0)
  %a = f32[512,512]{{1,0}} parameter(1)
  %b = f32[512,512]{{1,0}} parameter(2)
  %ar = f32[1048576]{{0}} all-reduce(f32[1048576]{{0}} %x), replica_groups={{{{0,1,2,3}}}}, to_apply=%add
  ROOT %out = f32[1048576]{{0}} negate(f32[1048576]{{0}} %ar)
}}
"""
    report = {"meta": {"overlap": True}}
    fs = _lint(sync_big, report=report)
    assert any(f.rule == "H008" and "no dataflow-independent" in f.message
               for f in fs)
    # the dot is independent of the all-reduce: a real dataflow window
    with_dot = sync_big.replace(
        "ROOT %out = f32[1048576]{0} negate(f32[1048576]{0} %ar)",
        "%d = f32[512,512]{1,0} dot(f32[512,512]{1,0} %a, "
        "f32[512,512]{1,0} %b), lhs_contracting_dims={1}, "
        "rhs_contracting_dims={0}\n"
        "  ROOT %out = f32[1048576]{0} negate(f32[1048576]{0} %ar)",
    )
    assert "H008" not in _rules_fired(_lint(with_dot, report=report))
    # without the overlap declaration the sync op is H001's department
    assert "H008" not in _rules_fired(_lint(sync_big))


# two sites share channel 7 but group the mesh differently: every
# participant waits on a peer set that never assembles — the
# mismatched-participant deadlock H007 (shape-local: duplicate permute
# targets, axis leaks) cannot catch
H009_CHANNEL_MISMATCH = f"""\
HloModule h009, num_partitions=4
{_ADD}
ENTRY %main (x: f32[1024], y: f32[1024]) -> f32[1024] {{
  %x = f32[1024]{{0}} parameter(0)
  %y = f32[1024]{{0}} parameter(1)
  %ar0 = f32[1024]{{0}} all-reduce(f32[1024]{{0}} %x), channel_id=3, replica_groups={{{{0,1,2,3}}}}, use_global_device_ids=true, to_apply=%add
  %ar1 = f32[1024]{{0}} all-reduce(f32[1024]{{0}} %ar0), channel_id=7, replica_groups={{{{0,1}},{{2,3}}}}, use_global_device_ids=true, to_apply=%add
  %ar2 = f32[1024]{{0}} all-reduce(f32[1024]{{0}} %y), channel_id=7, replica_groups={{{{0,2}},{{1,3}}}}, use_global_device_ids=true, to_apply=%add
  ROOT %s = f32[1024]{{0}} add(f32[1024]{{0}} %ar1, f32[1024]{{0}} %ar2)
}}
"""


def test_h009_mismatched_participants_deadlock_h007_cannot_catch():
    fs = _lint(H009_CHANNEL_MISMATCH)
    f = next(f for f in fs if f.rule == "H009")
    assert f.severity == "error"
    assert "channel-group-mismatch" in f.message
    # H007's shape-local checks see nothing wrong with either site
    assert "H007" not in _rules_fired(fs)
    # near miss: same channel, same groups — two instances of one
    # rendezvous shape, perfectly legal
    ok = H009_CHANNEL_MISMATCH.replace("{{0,2},{1,3}}", "{{0,1},{2,3}}")
    assert "H009" not in _rules_fired(_lint(ok))


def test_h009_divergent_conditional_sequences():
    hlo = f"""\
HloModule h009b
{_ADD}
%true_b (t: f32[256]) -> f32[256] {{
  %t = f32[256]{{0}} parameter(0)
  ROOT %ar = f32[256]{{0}} all-reduce(f32[256]{{0}} %t), replica_groups={{{{0,1,2,3}}}}, to_apply=%add
}}
%false_b (f: f32[256]) -> f32[256] {{
  %f = f32[256]{{0}} parameter(0)
  ROOT %n = f32[256]{{0}} negate(f32[256]{{0}} %f)
}}
ENTRY %main (p: pred[], x: f32[256]) -> f32[256] {{
  %p = pred[] parameter(0)
  %x = f32[256]{{0}} parameter(1)
  ROOT %c = f32[256]{{0}} conditional(pred[] %p, f32[256]{{0}} %x, f32[256]{{0}} %x), true_computation=%true_b, false_computation=%false_b
}}
"""
    fs = _lint(hlo)
    assert any(f.rule == "H009" and "divergent-branches" in f.message
               for f in fs)
    same = hlo.replace(
        "ROOT %n = f32[256]{0} negate(f32[256]{0} %f)",
        "ROOT %n = f32[256]{0} all-reduce(f32[256]{0} %f), "
        "replica_groups={{0,1,2,3}}, to_apply=%add",
    )
    assert "H009" not in _rules_fired(_lint(same))


# ------------------------------------------------------- source rule pack

S101_SRC = """\
import os

def donation_default():
    return os.environ.get("DDL25_DONATE", "1") not in ("", "0")

TRACE_FLAG = os.environ.get("AT_IMPORT_IS_FINE")
"""


def test_s101_env_read_in_traced_module_function():
    fs = source_lint.lint_source(
        S101_SRC, "ddl25spring_tpu/parallel/bucketing.py"
    )
    assert [f.rule for f in fs] == ["S101"]  # module-level read exempt
    assert fs[0].op == "donation_default"
    # outside the traced-code scope (data loaders) env reads are fine
    assert source_lint.lint_source(
        S101_SRC, "ddl25spring_tpu/data/cifar10.py"
    ) == []


S102_SRC = """\
import jax
from functools import partial

def make_step_bad(fn):
    return jax.jit(fn)

def make_step_good(fn):
    return jax.jit(fn, donate_argnums=(0, 1))

@partial(jax.jit, donate_argnums=(0,))
def decorated_good(x):
    return x

@jax.jit
def decorated_bad(x):
    return x
"""


def test_s102_jit_without_donation_decision():
    fs = source_lint.lint_source(
        S102_SRC, "ddl25spring_tpu/parallel/newthing.py"
    )
    assert sorted(f.op for f in fs if f.rule == "S102") == [
        "decorated_bad", "make_step_bad",
    ]
    # out of the donation scope (models/) the rule does not apply
    assert source_lint.lint_source(
        S102_SRC, "ddl25spring_tpu/models/llama.py"
    ) == []


S103_SRC = """\
import numpy as np
import jax
import jax.numpy as jnp
from functools import partial

SIZES = np.arange(4)  # module level: static metadata, fine

def plain_helper(x):
    return np.prod(x.shape)  # undecorated helper: fine

@partial(jax.jit, donate_argnums=())
def step(x):
    def inner(y):
        return np.sum(y)  # traced context (nested): fires
    return jnp.sum(x) + np.mean(x)  # traced context: fires
"""


def test_s103_numpy_inside_traced_functions():
    fs = source_lint.lint_source(S103_SRC, "ddl25spring_tpu/anywhere.py")
    hits = [f for f in fs if f.rule == "S103"]
    assert len(hits) == 2
    assert {f.severity for f in hits} == {"error"}
    assert any("np.sum" in f.message for f in hits)
    assert any("np.mean" in f.message for f in hits)


# ----------------------------------------------------- waivers + summary


def test_waiver_file_roundtrip(tmp_path):
    p = tmp_path / "waivers.toml"
    p.write_text(
        '# test waivers\n'
        '[[waiver]]\n'
        'rule = "H001"\n'
        'strategy = "zero*"\n'
        'match = "sync"\n'
        'reason = "tiny mesh, overlap not worth it"\n'
    )
    ws = load_waivers(str(p))
    assert len(ws) == 1 and ws[0].rule == "H001"
    f_covered = Finding(rule="H001", severity="warn", strategy="zero3",
                        message="sync all-reduce ...")
    f_other = Finding(rule="H001", severity="warn", strategy="dp",
                      message="sync all-reduce ...")
    apply_waivers([f_covered, f_other], ws)
    assert f_covered.waived and f_covered.waived_reason
    assert not f_other.waived


def test_waiver_path_matches_absolute_hlo_sources(tmp_path):
    """H-rule findings carry ABSOLUTE paths (HLO source_file metadata);
    a repo-relative waiver path must still cover them."""
    p = tmp_path / "w.toml"
    p.write_text(
        '[[waiver]]\n'
        'rule = "H001"\n'
        'path = "ddl25spring_tpu/parallel/zero.py"\n'
        'reason = "tiny mesh"\n'
    )
    ws = load_waivers(str(p))
    f_abs = Finding(rule="H001", severity="warn", message="m",
                    source="/root/repo/ddl25spring_tpu/parallel/zero.py:55")
    f_rel = Finding(rule="H001", severity="warn", message="m",
                    source="ddl25spring_tpu/parallel/zero.py:55")
    f_other = Finding(rule="H001", severity="warn", message="m",
                      source="/root/repo/ddl25spring_tpu/parallel/dp.py:9")
    apply_waivers([f_abs, f_rel, f_other], ws)
    assert f_abs.waived and f_rel.waived and not f_other.waived


def test_waiver_without_reason_is_rejected(tmp_path):
    p = tmp_path / "w.toml"
    p.write_text('[[waiver]]\nrule = "H001"\n')
    with pytest.raises(ValueError, match="reason"):
        load_waivers(str(p))
    p.write_text('[[waiver]]\nrule = "H001"\nreason = "r"\ntypo = "x"\n')
    with pytest.raises(ValueError, match="unknown keys"):
        load_waivers(str(p))


def test_mini_parser_rejects_trailing_junk_but_takes_comments():
    """A malformed line must not silently drop its tail (which would
    WIDEN the waiver); a trailing comment is fine — matching what
    tomllib does on 3.11, so the two parsers never diverge."""
    from ddl25spring_tpu.analysis.waivers import _parse_mini

    ok = _parse_mini(
        '[[waiver]]\nrule = "H001"  # the overlap rule\nreason = "r"\n'
    )
    assert ok["waiver"][0] == {"rule": "H001", "reason": "r"}
    with pytest.raises(ValueError, match="after string value"):
        _parse_mini('[[waiver]]\nrule = "H001" strategy = "dp"\n')


def _tomllib():
    try:
        import tomllib

        return tomllib
    except ModuleNotFoundError:  # the 3.10 image: fallback only
        return None


def test_mini_parser_matches_tomllib_on_escaped_quotes():
    """The fallback parser is load-bearing on the 3.10 build image —
    every construct the schema allows must parse IDENTICALLY to
    tomllib (checked directly on 3.11 CI, pinned by value here)."""
    from ddl25spring_tpu.analysis.waivers import _parse_mini

    text = (
        '[[waiver]]\n'
        'rule = "H001"\n'
        'match = "say \\"sync\\" twice"\n'
        'reason = "quoted \\"reason\\" with a # inside"\n'
    )
    mini = _parse_mini(text)
    assert mini["waiver"][0]["match"] == 'say "sync" twice'
    assert mini["waiver"][0]["reason"] == 'quoted "reason" with a # inside'
    tl = _tomllib()
    if tl is not None:
        assert mini == tl.loads(text)


def test_mini_parser_matches_tomllib_on_crlf_line_endings():
    """A waivers.toml saved with CRLF endings (Windows checkout, or a
    heredoc through a CR-preserving pipe) must parse identically —
    the \\r must never leak into a rule id or reason string."""
    from ddl25spring_tpu.analysis.waivers import _parse_mini

    text = (
        '[[waiver]]\r\n'
        'rule = "H005"\r\n'
        'reason = "crlf file"\r\n'
        '\r\n'
        '[[waiver]]\r\n'
        'rule = "H001"\r\n'
        'reason = "second entry"\r\n'
    )
    mini = _parse_mini(text)
    assert [w["rule"] for w in mini["waiver"]] == ["H005", "H001"]
    assert mini["waiver"][0]["reason"] == "crlf file"
    tl = _tomllib()
    if tl is not None:
        assert mini == tl.loads(text)


def test_mini_parser_matches_tomllib_on_escaped_hash_and_backslash_tail():
    """PR-12 satellite: the one-char-lookbehind quote scanner mis-read
    a string ending in an ESCAPED BACKSLASH (``"...\\\\"``) — the
    closing quote looked escaped, so the scanner hunted past it and,
    with a ``#`` comment on the line, swallowed the comment while
    looking for a closing quote that never came (a loud failure on a
    VALID file).  And ``\\#`` — not a TOML escape — parsed silently
    where tomllib rejects it: a waiver that loads on the 3.10 build
    image and crashes 3.11 CI.  Both halves pinned against tomllib."""
    from ddl25spring_tpu.analysis.waivers import _parse_mini

    # a reason ending in a literal backslash, with a trailing comment
    text = (
        '[[waiver]]\n'
        'rule = "H001"\n'
        'reason = "win path C:\\\\temp\\\\" # checkout note\n'
    )
    mini = _parse_mini(text)
    assert mini["waiver"][0]["reason"] == "win path C:\\temp\\"
    tl = _tomllib()
    if tl is not None:
        assert mini == tl.loads(text)

    # an escaped '#' inside the reason string: INVALID TOML — both
    # parsers must refuse (silent acceptance here is the divergence)
    bad = '[[waiver]]\nrule = "H001"\nreason = "keep the \\# literal"\n'
    with pytest.raises(ValueError, match="invalid escape"):
        _parse_mini(bad)
    if tl is not None:
        with pytest.raises(Exception):
            tl.loads(bad)

    # a PLAIN '#' inside the string (no escape) stays legal, comment
    # detection untouched
    ok = _parse_mini(
        '[[waiver]]\nrule = "H001"\nreason = "a # inside" # real comment\n'
    )
    assert ok["waiver"][0]["reason"] == "a # inside"

    # \uXXXX / \UXXXXXXXX are VALID TOML — the mini parser must accept
    # them exactly as tomllib does (review fix: rejecting them crashed
    # the 3.10 image on a file 3.11 CI accepts)
    uni = (
        '[[waiver]]\nrule = "H001"\n'
        'reason = "caf\\u00e9 \\U0001F600"\n'
    )
    mini = _parse_mini(uni)
    assert mini["waiver"][0]["reason"] == "caf\u00e9 \U0001F600"
    if tl is not None:
        assert mini == tl.loads(uni)
    with pytest.raises(ValueError, match="truncated"):
        _parse_mini('[[waiver]]\nrule = "H001"\nreason = "x\\u00"\n')
    # int(_, 16) would silently take '00_4' — strict hex digits only,
    # and lone surrogates are not scalar values (tomllib rejects both)
    with pytest.raises(ValueError, match="non-hex"):
        _parse_mini('[[waiver]]\nrule = "H001"\nreason = "x\\u00_4y"\n')
    with pytest.raises(ValueError, match="scalar"):
        _parse_mini('[[waiver]]\nrule = "H001"\nreason = "x\\uD800y"\n')


def test_mini_parser_rejects_table_of_tables_loudly():
    """tomllib accepts plain/nested tables (``[waiver]``,
    ``[waiver.meta]``); the mini parser supports exactly the
    array-of-tables schema and must REJECT anything else loudly —
    silently ignoring a section tomllib would honor is how the two
    parsers diverge into a waiver that works on CI (3.11) and not on
    the build image (3.10)."""
    from ddl25spring_tpu.analysis.waivers import _parse_mini

    for text in (
        '[waiver]\nrule = "H001"\nreason = "r"\n',
        '[[waiver]]\nrule = "H001"\nreason = "r"\n[waiver.meta]\nx = "y"\n',
    ):
        tl = _tomllib()
        if tl is not None:
            tl.loads(text)  # tomllib is fine with it — the divergence
        with pytest.raises(ValueError, match="only \\[\\[table\\]\\]"):
            _parse_mini(text)


def test_load_waivers_reads_crlf_and_escaped_quotes_from_disk(tmp_path):
    """End-to-end through load_waivers: binary-written CRLF bytes and
    escaped quotes survive the open()/parse path on any Python."""
    p = tmp_path / "w.toml"
    p.write_bytes(
        b'[[waiver]]\r\n'
        b'rule = "S102"\r\n'
        b'symbol = "make_\\"odd\\"_step"\r\n'
        b'reason = "windows checkout"\r\n'
    )
    (w,) = load_waivers(str(p))
    assert w.rule == "S102"
    assert w.symbol == 'make_"odd"_step'
    assert w.reason == "windows checkout"


def test_repo_waiver_file_loads_and_every_entry_has_reason():
    ws = load_waivers()
    assert ws, "analysis/waivers.toml should carry the in-repo waivers"
    assert all(w.reason for w in ws)


def test_severity_order_and_summary():
    assert severity_rank("error") > severity_rank("warn") > severity_rank(
        "info"
    ) > severity_rank(None)
    assert worst_severity(["info", "error", "warn"]) == "error"
    fs = [
        Finding(rule="H001", severity="warn", message="a"),
        Finding(rule="H005", severity="error", message="b", waived=True,
                waived_reason="ok"),
    ]
    s = engine.summarize(fs)
    assert s == {
        "findings": 2, "unwaived": 1, "waived": 1, "worst": "warn",
        "by_rule": {"H001": 1, "H005": 1},
    }


# ------------------------------------------------ per-strategy baselines


@pytest.mark.parametrize("name", DEFAULT_STRATEGIES)
def test_strategy_hlo_lints_clean(name):
    """The pinned clean baselines: every registered strategy's compiled
    train step carries ZERO unwaived hazard findings on this jax."""
    r = _report(name)
    assert "lint_error" not in r, r.get("lint_error")
    assert "findings" in r
    unwaived = [f for f in r["findings"] if not f["waived"]]
    assert unwaived == [], (
        f"{name} regressed: {[(f['rule'], f['message']) for f in unwaived]}"
    )


def test_strategy_reports_carry_donation_walk_fields():
    r = _report("dp")
    assert r["donation"]["donatable_leaves"] == 3
    # every donatable input is in the alias table (donate=True describe)
    assert set(range(3)) <= set(r["donation"]["aliased_params"])
    assert [p["number"] for p in r["entry_params"]] == sorted(
        p["number"] for p in r["entry_params"]
    )
    args = {p["arg"] for p in r["entry_params"] if p["arg"]}
    assert any(a.startswith("params[") for a in args)


def test_repo_source_lints_clean():
    """Dogfood pin: the repo's own Python has no unwaived findings (the
    PR-3 trace-time env read in bucketing.donation_default is fixed, the
    three justified jit sites are waived in analysis/waivers.toml)."""
    import os

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    findings = apply_waivers(
        source_lint.lint_repo(repo_root), load_waivers()
    )
    unwaived = [f for f in findings if not f.waived]
    assert unwaived == [], [
        (f.rule, f.source, f.op) for f in unwaived
    ]
    # the waivers are live, not dead entries
    assert any(f.waived for f in findings)


# --------------------------------------------------------- CLI + consumers


def test_graft_lint_cli_check_is_green(capsys):
    from tools import graft_lint

    assert graft_lint.main(["--check"]) == 0
    assert "graft-lint OK" in capsys.readouterr().err


def test_graft_lint_cli_json_format(capsys):
    from tools import graft_lint

    assert graft_lint.main(["--format", "json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["record"] == "graft_lint"
    assert {f["rule"] for f in doc["source"]} == {"S102"}
    assert all(f["waived"] for f in doc["source"])


def test_bench_lint_summary_condenses_compile_report():
    import bench

    cr = {"strategies": {
        "dp": {"findings": [
            {"rule": "H001", "severity": "warn", "waived": False},
            {"rule": "H005", "severity": "error", "waived": True},
        ]},
        "ep": {"findings": []},
        "dead": {"error": "no compile"},
    }}
    s = bench.lint_summary(cr)
    assert s["findings"] == 2 and s["unwaived"] == 1
    assert s["worst"] == "warn"
    assert s["per_strategy"]["dp"]["unwaived"] == 1
    assert s["per_strategy"]["ep"]["findings"] == 0
    # an unjudged strategy is an ERROR in the summary, never "clean"
    assert s["errors"] == 1
    assert s["per_strategy"]["dead"] == {"error": "no compile"}
    rec = bench.attach_parent_telemetry({}, None, cr)
    assert rec["telemetry"]["lint"]["unwaived"] == 1


def test_comms_report_findings_cell():
    from tools.comms_report import _findings_cell

    assert _findings_cell({}) == "hazards: not analyzed (lint=False)"
    assert _findings_cell({"findings": []}) == "hazards: none"
    cell = _findings_cell({"findings": [
        {"rule": "H001", "severity": "warn", "waived": False},
        {"rule": "H001", "severity": "warn", "waived": True},
    ]})
    assert "1 unwaived" in cell and "worst warn" in cell
    assert "1 waived" in cell and "H001" in cell
    assert "lint degraded" in _findings_cell({"lint_error": "boom"})


def test_lint_threshold_defaults_are_sane():
    assert DEFAULT_THRESHOLDS["h001_sync_bytes"] == 1024 * 1024
    assert DEFAULT_THRESHOLDS["h005_donation_bytes"] == 64 * 1024
    assert DEFAULT_THRESHOLDS["scalar_bytes"] == 64
