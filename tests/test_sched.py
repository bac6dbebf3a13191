"""graft-sched: the whole-program schedule verifier.

Three layers pinned here:

1. **Mechanics** — the instruction DAG, static FLOP accounting (dot
   contracting dims, fusion inlining, loop trip multiplication), and
   the three window models (async pair / committed schedule /
   dataflow) on synthetic HLO.
2. **Safety** — the per-participant stream expansion and each deadlock
   shape :func:`check_schedule_safety` proves absent (duplicate
   participant, channel-group mismatch, out-of-range device, divergent
   conditional branches, crossed async windows).
3. **Strategy pins** — every registered strategy carries a sched
   report, and each ``*-overlap`` strategy's ``static_overlap_bound``
   is STRICTLY greater than its sync twin's: the static proof of the
   PR-8 scheduling win that the noise-bound wall-clock A/B could not
   give.  These ride the shared lower-once compile cache
   (tests/conftest.py) — zero extra compiles.
"""

import pytest

from ddl25spring_tpu.analysis import sched
from ddl25spring_tpu.obs import xla_analytics as xa
from conftest import cached_strategy_report

# --------------------------------------------------------------- fixtures

_ADD = """\
%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(f32[] %a, f32[] %b)
}
"""

# a 4 MiB async all-reduce whose window holds one real matmul (2*512^3
# FLOPs — comfortably above 1% of the wire time on the reference chip)
PAIR_WITH_DOT = f"""\
HloModule pair_dot
{_ADD}
ENTRY %main (x: f32[1048576], a: f32[512,512], b: f32[512,512]) -> f32[1048576] {{
  %x = f32[1048576]{{0}} parameter(0)
  %a = f32[512,512]{{1,0}} parameter(1)
  %b = f32[512,512]{{1,0}} parameter(2)
  %ars = f32[1048576]{{0}} all-reduce-start(f32[1048576]{{0}} %x), replica_groups={{{{0,1,2,3}}}}, to_apply=%add
  %d = f32[512,512]{{1,0}} dot(f32[512,512]{{1,0}} %a, f32[512,512]{{1,0}} %b), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}
  %ard = f32[1048576]{{0}} all-reduce-done(f32[1048576]{{0}} %ars)
  %s = f32[] constant(0)
  ROOT %out = f32[1048576]{{0}} add(f32[1048576]{{0}} %ard, f32[1048576]{{0}} %ard)
}}
"""

# the cosmetic shape the motivation names: start immediately followed
# by done — the pair exists, the window is empty
PAIR_ZERO_SLACK = f"""\
HloModule pair_zero
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

# a sync collective: under the sync discipline its window is the
# committed schedule's [op, first use); under the overlap discipline it
# is the dataflow window (the dot is independent either way, but only
# the dataflow model may count it — it is scheduled after the use here)
SYNC_AR = f"""\
HloModule sync_ar
{_ADD}
ENTRY %main (x: f32[1048576], a: f32[512,512], b: f32[512,512]) -> f32[512,512] {{
  %x = f32[1048576]{{0}} parameter(0)
  %a = f32[512,512]{{1,0}} parameter(1)
  %b = f32[512,512]{{1,0}} parameter(2)
  %ar = f32[1048576]{{0}} all-reduce(f32[1048576]{{0}} %x), replica_groups={{{{0,1,2,3}}}}, to_apply=%add
  %u = f32[1048576]{{0}} negate(f32[1048576]{{0}} %ar)
  ROOT %d = f32[512,512]{{1,0}} dot(f32[512,512]{{1,0}} %a, f32[512,512]{{1,0}} %b), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}
}}
"""


# ------------------------------------------------------------- cost model


def test_dot_flops_use_contracting_dims():
    defs = xa.parse_op_defs(PAIR_WITH_DOT)
    d = defs["main"]["d"]
    assert sched.instruction_flops(defs, "main", d, {}) == 2 * 512**3


def test_fusion_flops_inline_the_called_computation():
    hlo = """\
HloModule fus
%fused (p0: f32[64,32], p1: f32[32,16]) -> f32[64,16] {
  %p0 = f32[64,32]{1,0} parameter(0)
  %p1 = f32[32,16]{1,0} parameter(1)
  ROOT %d = f32[64,16]{1,0} dot(f32[64,32]{1,0} %p0, f32[32,16]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
ENTRY %main (a: f32[64,32], b: f32[32,16]) -> f32[64,16] {
  %a = f32[64,32]{1,0} parameter(0)
  %b = f32[32,16]{1,0} parameter(1)
  ROOT %f = f32[64,16]{1,0} fusion(f32[64,32]{1,0} %a, f32[32,16]{1,0} %b), kind=kOutput, calls=%fused
}
"""
    defs = xa.parse_op_defs(hlo)
    f = defs["main"]["f"]
    assert sched.instruction_flops(defs, "main", f, {}) == 2 * 64 * 16 * 32


def test_while_flops_multiply_by_known_trip_count():
    hlo = """\
HloModule wh
%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]{1,0}) parameter(0)
  %c = s32[] get-tuple-element((s32[], f32[8,8]{1,0}) %p), index=0
  %g = f32[8,8]{1,0} get-tuple-element((s32[], f32[8,8]{1,0}) %p), index=1
  %d = f32[8,8]{1,0} dot(f32[8,8]{1,0} %g, f32[8,8]{1,0} %g), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %t = (s32[], f32[8,8]{1,0}) tuple(%c, %d)
}
%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]{1,0}) parameter(0)
  ROOT %lt = pred[] constant(false)
}
ENTRY %main (x: f32[8,8]) -> f32[8,8] {
  %x = f32[8,8]{1,0} parameter(0)
  %c0 = s32[] constant(0)
  %t = (s32[], f32[8,8]{1,0}) tuple(%c0, %x)
  %w = (s32[], f32[8,8]{1,0}) while((s32[], f32[8,8]{1,0}) %t), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
  ROOT %out = f32[8,8]{1,0} get-tuple-element((s32[], f32[8,8]{1,0}) %w), index=1
}
"""
    defs = xa.parse_op_defs(hlo)
    w = defs["main"]["w"]
    assert sched.instruction_flops(defs, "main", w, {}) == 5 * 2 * 8**3


def test_data_movement_costs_zero_flops():
    defs = xa.parse_op_defs(SYNC_AR)
    dag = sched.build_dag(defs, "main")
    for name in ("x", "a", "ar"):
        assert dag.flops[dag.index[name]] == 0.0


# ----------------------------------------------------------- window slack


def test_pair_window_counts_the_dot_between_start_and_done():
    defs = xa.parse_op_defs(PAIR_WITH_DOT)
    dag = sched.build_dag(defs, "main")
    rec = sched.window_slack(dag, "ars")
    assert rec["window"] == "pair"
    assert rec["slack_flops"] == 2 * 512**3
    assert rec["independent_instructions"] == 1


def test_zero_slack_pair_window_is_empty():
    defs = xa.parse_op_defs(PAIR_ZERO_SLACK)
    dag = sched.build_dag(defs, "main")
    rec = sched.window_slack(dag, "ars")
    assert rec["window"] == "pair"
    assert rec["slack_flops"] == 0.0


def test_pair_window_excludes_dependents_of_the_start():
    # the op between start and done CONSUMES the start: not slack
    hlo = PAIR_WITH_DOT.replace(
        "%d = f32[512,512]{1,0} dot(f32[512,512]{1,0} %a, "
        "f32[512,512]{1,0} %b), lhs_contracting_dims={1}, "
        "rhs_contracting_dims={0}",
        "%d = f32[1048576]{0} negate(f32[1048576]{0} %ars)",
    )
    defs = xa.parse_op_defs(hlo)
    dag = sched.build_dag(defs, "main")
    assert sched.window_slack(dag, "ars")["slack_flops"] == 0.0


def test_sync_vs_dataflow_window_disciplines():
    defs = xa.parse_op_defs(SYNC_AR)
    dag = sched.build_dag(defs, "main")
    # sync: the committed schedule puts the use right after the op
    assert sched.window_slack(dag, "ar", "sync")["slack_flops"] == 0.0
    # overlap: the dot is dataflow-independent, wherever it is scheduled
    rec = sched.window_slack(dag, "ar", "overlap")
    assert rec["window"] == "dataflow"
    assert rec["slack_flops"] == 2 * 512**3


def test_control_predecessors_count_as_dependencies():
    hlo = SYNC_AR.replace(
        "%u = f32[1048576]{0} negate(f32[1048576]{0} %ar)",
        "%u = f32[1048576]{0} negate(f32[1048576]{0} %x), "
        "control-predecessors={%ar}",
    )
    defs = xa.parse_op_defs(hlo)
    dag = sched.build_dag(defs, "main")
    i, j = dag.index["ar"], dag.index["u"]
    assert not dag.independent(i, j)


# --------------------------------------------------------- bound roll-up


def test_static_overlap_bound_ratio_and_scalar_exemption():
    r = sched.analyze_schedule(PAIR_WITH_DOT)
    assert r["async_pairs"] == 1
    (w,) = [s for s in r["slack"] if s["result_bytes"] > 64]
    assert w["t_wire_s"] > 0
    # bound = hideable/wire over the non-scalar windows only
    expect = min(w["t_wire_s"], w["t_slack_s"]) / w["t_wire_s"]
    assert r["static_overlap_bound"] == pytest.approx(expect)
    # a module with no non-scalar collectives has no bound at all
    scalar = PAIR_WITH_DOT.replace("1048576]", "4]")
    assert sched.analyze_schedule(scalar)["static_overlap_bound"] is None


def test_zero_slack_pair_bounds_at_zero():
    r = sched.analyze_schedule(PAIR_ZERO_SLACK)
    assert r["static_overlap_bound"] == 0.0


def test_discipline_of_reads_meta():
    assert sched.discipline_of(None) == "sync"
    assert sched.discipline_of({}) == "sync"
    assert sched.discipline_of({"overlap": True}) == "overlap"
    assert sched.discipline_of({"prefetch": True}) == "overlap"


# ------------------------------------------------------- stream safety


def _sites(hlo):
    ops = xa.parse_hlo_collectives(hlo)
    defs = xa.parse_op_defs(hlo)
    return defs, ops


def test_participant_streams_expand_groups():
    defs, ops = _sites(SYNC_AR)
    sites = [dict(o, groups=[[0, 1], [2, 3]]) for o in ops]
    streams = sched.participant_streams(sites)
    assert set(streams) == {0, 1, 2, 3}
    # every participant sees the same (site, kind, groups) sequence
    assert len({tuple(v) for v in streams.values()}) == 1


def test_safety_flags_duplicate_participant_in_group():
    hlo = SYNC_AR.replace(
        "replica_groups={{0,1,2,3}}", "replica_groups={{0,0,1,2}}"
    )
    defs, ops = _sites(hlo)
    hz = sched.check_schedule_safety(hlo, defs, _anchor(hlo, ops))
    assert any(h["check"] == "duplicate-participant" for h in hz)


def test_safety_flags_out_of_range_participant():
    hlo = SYNC_AR.replace(
        "HloModule sync_ar", "HloModule sync_ar, num_partitions=4"
    ).replace("replica_groups={{0,1,2,3}}", "replica_groups={{0,1,2,9}}")
    defs, ops = _sites(hlo)
    hz = sched.check_schedule_safety(hlo, defs, _anchor(hlo, ops))
    assert any(h["check"] == "participant-out-of-range" for h in hz)
    # in-range groups on the same module are quiet
    ok = SYNC_AR.replace(
        "HloModule sync_ar", "HloModule sync_ar, num_partitions=4"
    )
    defs, ops = _sites(ok)
    assert sched.check_schedule_safety(ok, defs, _anchor(ok, ops)) == []


def test_safety_range_uses_replica_times_partition_bound():
    """A pmap-lowered REPLICA-mode module (replica_count=8,
    num_partitions=1) groups over replica ids 0-7 — comparing them
    against num_partitions alone would false-fire on every valid
    replica-mode program.  The bound is replica_count x num_partitions
    (the flattened use_global_device_ids id space)."""
    rep = SYNC_AR.replace(
        "HloModule sync_ar",
        "HloModule sync_ar, replica_count=8, num_partitions=1",
    ).replace("replica_groups={{0,1,2,3}}",
              "replica_groups={{0,1,2,3,4,5,6,7}}")
    defs, ops = _sites(rep)
    assert sched.check_schedule_safety(rep, defs, _anchor(rep, ops)) == []
    # and id 8 is still out of the 8-device flattened space
    bad = rep.replace("{{0,1,2,3,4,5,6,7}}", "{{0,1,2,3,4,5,6,8}}")
    defs, ops = _sites(bad)
    hz = sched.check_schedule_safety(bad, defs, _anchor(bad, ops))
    assert any(h["check"] == "participant-out-of-range" for h in hz)


def _anchor(hlo, ops):
    """Re-anchor inventory records with their def line + groups (what
    analyze_schedule does internally)."""
    defs = xa.parse_op_defs(hlo)
    out = []
    for op in ops:
        d = defs.get(op.get("computation") or "", {}).get(op["name"])
        site = dict(op)
        site["line"] = d["line"] if d else ""
        site["groups"] = xa._parse_groups(site["line"]) if d else None
        out.append(site)
    return out


CHANNEL_MISMATCH = f"""\
HloModule chan, num_partitions=4
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


def test_safety_flags_channel_reuse_with_different_groups():
    """The mismatched-participant deadlock H007 cannot catch: two sites
    share a channel (the rendezvous identity) but group the mesh
    differently — each participant waits for a peer set that never
    forms."""
    defs, ops = _sites(CHANNEL_MISMATCH)
    hz = sched.check_schedule_safety(
        CHANNEL_MISMATCH, defs, _anchor(CHANNEL_MISMATCH, ops)
    )
    assert any(h["check"] == "channel-group-mismatch" for h in hz)
    # same groups on both sites: distinct instances of one rendezvous
    # shape — quiet
    ok = CHANNEL_MISMATCH.replace("{{0,2},{1,3}}", "{{0,1},{2,3}}")
    defs, ops = _sites(ok)
    assert sched.check_schedule_safety(ok, defs, _anchor(ok, ops)) == []


DIVERGENT_BRANCHES = f"""\
HloModule cond
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


def test_safety_flags_divergent_conditional_branches():
    defs, ops = _sites(DIVERGENT_BRANCHES)
    hz = sched.check_schedule_safety(
        DIVERGENT_BRANCHES, defs, _anchor(DIVERGENT_BRANCHES, ops)
    )
    assert any(h["check"] == "divergent-branches" for h in hz)
    # both branches issuing the SAME sequence is safe
    ok = DIVERGENT_BRANCHES.replace(
        "ROOT %n = f32[256]{0} negate(f32[256]{0} %f)",
        "ROOT %n = f32[256]{0} all-reduce(f32[256]{0} %f), "
        "replica_groups={{0,1,2,3}}, to_apply=%add",
    )
    defs, ops = _sites(ok)
    assert sched.check_schedule_safety(ok, defs, _anchor(ok, ops)) == []


CROSSED_ASYNC = f"""\
HloModule crossed
{_ADD}
ENTRY %main (x: f32[1024], y: f32[1024]) -> f32[1024] {{
  %x = f32[1024]{{0}} parameter(0)
  %y = f32[1024]{{0}} parameter(1)
  %s1 = f32[1024]{{0}} all-reduce-start(f32[1024]{{0}} %x), replica_groups={{{{0,1}}}}, to_apply=%add
  %s2 = f32[1024]{{0}} all-reduce-start(f32[1024]{{0}} %y), replica_groups={{{{1,2}}}}, to_apply=%add
  %d1 = f32[1024]{{0}} all-reduce-done(f32[1024]{{0}} %s1)
  %d2 = f32[1024]{{0}} all-reduce-done(f32[1024]{{0}} %s2)
  ROOT %s = f32[1024]{{0}} add(f32[1024]{{0}} %d1, f32[1024]{{0}} %d2)
}}
"""


def test_safety_flags_crossed_async_windows_on_unequal_groups():
    defs, ops = _sites(CROSSED_ASYNC)
    dags = {"main": sched.build_dag(defs, "main")}
    hz = sched.check_schedule_safety(
        CROSSED_ASYNC, defs, _anchor(CROSSED_ASYNC, ops), dags
    )
    assert any(h["check"] == "crossed-async-windows" for h in hz)
    # equal participant sets serialize fine; nested windows too
    ok = CROSSED_ASYNC.replace("replica_groups={{1,2}}",
                               "replica_groups={{0,1}}")
    defs, ops = _sites(ok)
    dags = {"main": sched.build_dag(defs, "main")}
    assert sched.check_schedule_safety(ok, defs, _anchor(ok, ops), dags) == []


# ------------------------------------------------------- strategy pins


def test_every_registered_strategy_carries_a_sched_report():
    from ddl25spring_tpu.obs.compile_report import DEFAULT_STRATEGIES

    assert set(DEFAULT_STRATEGIES) == set(xa.STRATEGIES)
    # 16 training + 2 serving (PR 10; PR 11's cached-prefill variant
    # left with PR 27: the one-pass prefill is one program) + the 2
    # partition-rule-table strategies (PR 12) + the speculative
    # draft/verify pair (PR 13) + the TP serving trio (PR 18: tp
    # decode/prefill + zero3 weight streaming)
    assert len(DEFAULT_STRATEGIES) == 23
    for name in DEFAULT_STRATEGIES:
        r = cached_strategy_report(name)
        s = r.get("sched")
        assert s and "error" not in s, (name, s)
        assert s["discipline"] == (
            "overlap" if ("overlap" in name or "prefetch" in name) else "sync"
        )
        # schedule safety: ZERO deadlock hazards on every strategy
        assert s["hazards"] == [], (name, s["hazards"])


@pytest.mark.parametrize("overlap,sync", [
    ("dp-overlap", "dp"),
    ("zero1-overlap", "zero1"),
    ("zero2-overlap", "zero2"),
    ("zero3-overlap", "zero3"),
])
def test_overlap_strategies_prove_strictly_positive_slack(overlap, sync):
    """THE pin the tentpole exists for: each backward-overlapped
    strategy's static overlap bound is strictly above its sync twin's —
    the provable scheduling win PR 8's noise-bound wall-clock A/B could
    not show.  The sync twin's committed schedule leaves (next to)
    nothing in its windows; the overlapped twin's dataflow provably
    holds independent backward compute."""
    r_ov = cached_strategy_report(overlap)["sched"]
    r_sy = cached_strategy_report(sync)["sched"]
    assert r_ov["static_overlap_bound"] is not None
    assert r_sy["static_overlap_bound"] is not None
    assert r_ov["static_overlap_bound"] > r_sy["static_overlap_bound"]
    assert r_ov["static_overlap_bound"] > 0.0
    # the windows carry real FLOPs, not rounding dust
    ov_slack = sum(w["slack_flops"] for w in r_ov["slack"])
    assert ov_slack > 0


def test_zero3_prefetch_double_buffer_shows_positive_slack():
    """The scanned double-buffer gathers layer i+1 while layer i
    computes — dataflow-visible slack inside the loop body."""
    s = cached_strategy_report("zero3-prefetch")["sched"]
    assert s["static_overlap_bound"] is not None
    assert s["static_overlap_bound"] > 0.0


def test_multi_bucket_describe_default():
    """The overlap-vs-sync pins need the windows to exist: a
    single-bucket plan has nothing to overlap (its one collective
    depends on the entire backward), so the describe() workloads must
    plan >= 2 buckets by default."""
    for name in ("dp", "dp-overlap", "zero1", "zero2", "zero3"):
        assert cached_strategy_report(name)["meta"]["n_buckets"] >= 2, name


def test_comms_report_sched_cell():
    from tools.comms_report import _sched_cell

    assert _sched_cell({}) == "sched: not analyzed"
    assert "degraded" in _sched_cell({"sched": {"error": "boom"}})
    r = cached_strategy_report("dp-overlap")
    cell = _sched_cell(r)
    assert "static overlap bound" in cell and "overlap issue" in cell
