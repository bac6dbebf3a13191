"""Compile-time XLA analytics: HLO parsing units + per-strategy
collective-signature pins.

The signature pins are the comms-regression contract: each parallel
strategy's ``describe()`` declares the analytic collective signature its
compiled train step must show (DP = grad-bytes of all-reduce and nothing
else; ZeRO-3 = per-leaf all-gathers + reduce-scatters with NO param-sized
all-reduce; GPipe = ``M + S - 1`` collective-permutes per direction; ...),
and these tests assert the optimized HLO matches — on CPU, no
accelerator.  A refactor that silently adds a stray all-gather or breaks
fusion fails here before it ever reaches a TPU.

Strategies whose grad path needs VMA-typed shard_map lower forward-only
on this jax (``describe()`` handles the gating); the pins below compute
their expectations from ``meta``/``lowered`` so they are green on both
vintages.
"""

import jax
import jax.numpy as jnp
import pytest

from ddl25spring_tpu.obs import xla_analytics as xa
from ddl25spring_tpu.utils.compat import (
    compiled_cost_analysis,
    compiled_memory_stats,
)
from ddl25spring_tpu.utils.mesh import make_mesh

# ------------------------------------------------------------ parser units

SYNTHETIC_HLO = """\
HloModule synthetic, is_scheduled=true

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(f32[] %a, f32[] %b)
}

%body (p: (s32[], f32[4,8])) -> (s32[], f32[4,8]) {
  %p = (s32[], f32[4,8]{1,0}) parameter(0)
  %g = f32[4,8]{1,0} get-tuple-element((s32[], f32[4,8]{1,0}) %p), index=1
  %cp = f32[4,8]{1,0} collective-permute(f32[4,8]{1,0} %g), channel_id=1, source_target_pairs={{0,2},{2,0},{1,3},{3,1}}, metadata={op_name="ppermute" source_file="fake.py" source_line=7}
  ROOT %t = (s32[], f32[4,8]{1,0}) tuple(%g, %cp)
}

%cond (p: (s32[], f32[4,8])) -> pred[] {
  %p = (s32[], f32[4,8]{1,0}) parameter(0)
  ROOT %lt = pred[] constant(false)
}

%dead (x: f32[2]) -> f32[2] {
  %x = f32[2]{0} parameter(0)
  ROOT %agd = f32[2]{0} all-gather(f32[2]{0} %x), replica_groups={{0,1,2,3}}
}

ENTRY %main (x: f32[4,8]) -> f32[4,8] {
  %x = f32[4,8]{1,0} parameter(0)
  %ar = (f32[4,8]{1,0}, f32[2]{0}) all-reduce(f32[4,8]{1,0} %x, f32[2]{0} %x), channel_id=2, replica_groups={{0,1},{2,3}}, use_global_device_ids=true, to_apply=%add
  %t = (s32[], f32[4,8]{1,0}) tuple(%x, %x)
  %w = (s32[], f32[4,8]{1,0}) while((s32[], f32[4,8]{1,0}) %t), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"7"}}
  ROOT %out = f32[4,8]{1,0} get-tuple-element((s32[], f32[4,8]{1,0}) %w), index=1
}
"""


@pytest.fixture(scope="module")
def mesh22(devices8):
    return make_mesh(devices8[:4], outer=2, inner=2)


def test_parser_counts_and_trip_multipliers(mesh22):
    ops = xa.parse_hlo_collectives(SYNTHETIC_HLO, mesh22)
    kinds = {o["kind"]: o for o in ops}
    # the dead computation's all-gather is unreachable from ENTRY
    assert set(kinds) == {"all-reduce", "collective-permute"}
    ar = kinds["all-reduce"]
    # tuple-shaped fused all-reduce: f32[4,8] + f32[2] = 128 + 8 bytes
    assert ar["result_bytes"] == 136
    assert ar["count"] == 1 and ar["trip_known"]
    cp = kinds["collective-permute"]
    # one site inside a while with known_trip_count 7
    assert cp["count"] == 7 and cp["trip_known"]
    assert cp["result_bytes"] == 128
    assert cp["source"] == "fake.py:7"


def test_parser_axes_from_groups_and_pairs(mesh22):
    ops = xa.parse_hlo_collectives(SYNTHETIC_HLO, mesh22)
    by = {o["kind"]: o for o in ops}
    # groups {{0,1},{2,3}} vary the INNER coordinate of the 2x2 mesh
    assert by["all-reduce"]["axes"] == ["inner"]
    assert by["all-reduce"]["group_size"] == 2
    # pairs {0<->2, 1<->3} vary the OUTER coordinate
    assert by["collective-permute"]["axes"] == ["outer"]


def test_parser_iota_replica_groups(mesh22):
    txt = SYNTHETIC_HLO.replace(
        "replica_groups={{0,1},{2,3}}", "replica_groups=[2,2]<=[4]"
    )
    ops = xa.parse_hlo_collectives(txt, mesh22)
    ar = next(o for o in ops if o["kind"] == "all-reduce")
    # iota [2,2]<=[4] is {{0,1},{2,3}} — same inner-axis grouping
    assert ar["axes"] == ["inner"]


def test_totals_and_wire_accounting():
    ops = xa.parse_hlo_collectives(SYNTHETIC_HLO)
    totals = xa.collective_totals(ops)
    assert totals["collective-permute"]["count"] == 7
    assert totals["collective-permute"]["result_bytes"] == 7 * 128
    # permute wire = one payload per execution
    assert totals["collective-permute"]["wire_bytes"] == 7 * 128
    # ring all-reduce over groups of 2: 2 * (n-1)/n = 1x payload
    assert totals["all-reduce"]["wire_bytes"] == 136


def test_check_signature_catches_drift():
    ops = [
        {"kind": "all-reduce", "result_bytes": 1000, "count": 2,
         "trip_known": True, "axes": ["data"], "group_size": 4,
         "wire_bytes": 1500, "source": "x.py:1"},
        {"kind": "all-gather", "result_bytes": 500, "count": 1,
         "trip_known": True, "axes": ["stage"], "group_size": 2,
         "wire_bytes": 250, "source": "x.py:2"},
    ]
    report = {"collectives": {"ops": ops, "totals": xa.collective_totals(ops)}}
    ok = xa.check_signature(report, {
        "all-reduce": {"count": 2, "min_bytes": 2000, "axes": ["data"]},
    })
    assert ok == []
    viols = xa.check_signature(report, {
        "forbidden": ["all-gather"],
        "all-reduce": {"count": 1, "max_bytes": 100, "axes": ["model"]},
    })
    # stray kind + count drift + byte drift + wrong axis all reported
    assert len(viols) == 4


def test_strategy_mesh_folds_extra_dims():
    mesh = xa.strategy_mesh("zero3", (2, 4))
    assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {"data": 8}
    mesh = xa.strategy_mesh("pipeline", (2,))
    assert mesh.axis_names == ("stage",)


def test_roofline_projection_bounds():
    # 1e12 flops on a 275e12-peak chip with negligible bytes: compute-bound
    p = xa.roofline_projection(1e12, 1e6, 0.0, chips=["TPU v4"])["TPU v4"]
    assert p["bound"] == "compute"
    assert p["projected_mfu"] == pytest.approx(1.0)
    # byte-dominated program: hbm-bound, low MFU
    p = xa.roofline_projection(1e9, 1e12, 0.0, chips=["TPU v4"])["TPU v4"]
    assert p["bound"] == "hbm" and p["projected_mfu"] < 0.01
    # collective-dominated: ici-bound
    p = xa.roofline_projection(1e9, 0.0, 1e12, chips=["TPU v4"])["TPU v4"]
    assert p["bound"] == "ici"


# ------------------------------------- compiled-program probes (compat)


class _FakeMemStatsOld:
    """CompiledMemoryStats of a backend that reports no peak."""

    argument_size_in_bytes = 1000
    output_size_in_bytes = 300
    temp_size_in_bytes = 700
    alias_size_in_bytes = 100
    generated_code_size_in_bytes = 50


class _FakeMemStatsNew(_FakeMemStatsOld):
    peak_memory_in_bytes = 4242


def test_memory_stats_fallback_assembles_peak():
    class C:
        def memory_analysis(self):
            return _FakeMemStatsOld()

    out = compiled_memory_stats(C())
    assert out["peak_hbm_bytes"] == 1000 + 300 + 700 + 50 - 100


def test_memory_stats_prefers_backend_peak():
    class C:
        def memory_analysis(self):
            return _FakeMemStatsNew()

    assert compiled_memory_stats(C())["peak_hbm_bytes"] == 4242


def test_memory_stats_absent_or_raising_is_none():
    class NoApi:
        pass

    class Raising:
        def memory_analysis(self):
            raise NotImplementedError("backend has no memory stats")

    class ReturnsNone:
        def memory_analysis(self):
            return None

    assert compiled_memory_stats(NoApi()) is None
    assert compiled_memory_stats(Raising()) is None
    assert compiled_memory_stats(ReturnsNone()) is None


def test_cost_analysis_dict_and_failures():
    class DictShaped:
        def cost_analysis(self):
            return {"flops": 9.0}

    class Raising:
        def cost_analysis(self):
            raise RuntimeError("no cost model")

    class Empty:
        def cost_analysis(self):
            return {}

    assert compiled_cost_analysis(DictShaped()) == {"flops": 9.0}
    assert compiled_cost_analysis(Raising()) is None
    assert compiled_cost_analysis(Empty()) is None


def test_compiled_flops_rides_the_shared_compat_path():
    from ddl25spring_tpu.utils.flops import compiled_flops

    @jax.jit
    def f(a):
        return (a @ a).sum()

    fl = compiled_flops(f, jnp.ones((32, 32)))
    assert fl is not None and fl >= 2 * 32**3


# ------------------------------------------------- strategy signature pins

# the compile-once cache moved to tests/conftest.py (PR 9): one
# compile_strategy() per strategy per SESSION, shared with
# test_hlo_lint's clean baselines and test_sched's overlap-bound pins
from conftest import cached_strategy_report as _report  # noqa: E402


def _count(r: dict, kind: str) -> int:
    return r["collectives"]["totals"].get(kind, {}).get("count", 0)


def _payload(r: dict, kind: str) -> int:
    return r["collectives"]["totals"].get(kind, {}).get("result_bytes", 0)


def test_dp_signature_exactly_one_fused_gradient_allreduce():
    r = _report("dp")
    assert r["signature_violations"] == []
    grad = r["meta"]["grad_bytes"]
    # all traffic is the gradient all-reduce (+ scalar loss reductions)
    assert grad <= _payload(r, "all-reduce") <= grad + 256
    # bucketed: the non-scalar launches == the plan's bucket count
    big = [
        o for o in r["collectives"]["ops"]
        if o["kind"] == "all-reduce" and o["result_bytes"] > 64
    ]
    assert sum(o["count"] for o in big) == r["meta"]["n_buckets"]
    for kind in ("all-gather", "reduce-scatter", "collective-permute",
                 "all-to-all"):
        assert _count(r, kind) == 0, f"plain DP grew a stray {kind}"
    assert all(
        o["axes"] == ["data"]
        for o in r["collectives"]["ops"] if o["result_bytes"] > 64
    )


def test_dp_overlap_signature_matches_dp_with_backward_issue():
    """The overlapped DP strategy is a scheduling restructure, not a
    traffic change: identical all-reduce payload, the same per-bucket
    launch ceiling, data-axis-only grouping, and the same forbidden
    kinds as sync dp — any drift here means the custom_vjp machinery
    changed what crosses the wire.  The meta declares the mode so every
    downstream consumer (comms tables) names it."""
    r = _report("dp-overlap")
    sync = _report("dp")
    assert r["signature_violations"] == []
    assert r["meta"]["overlap"] is True
    assert r["meta"]["bucket_bytes"] == sync["meta"]["bucket_bytes"]
    # same bytes on the wire as sync dp, same bucket-count launch shape
    assert _payload(r, "all-reduce") == _payload(sync, "all-reduce")
    big = [
        o for o in r["collectives"]["ops"]
        if o["kind"] == "all-reduce" and o["result_bytes"] > 64
    ]
    assert sum(o["count"] for o in big) == r["meta"]["n_buckets"]
    for kind in ("all-gather", "reduce-scatter", "collective-permute",
                 "all-to-all"):
        assert _count(r, kind) == 0, f"dp-overlap grew a stray {kind}"


def test_zero3_overlap_signature_matches_zero3():
    """zero3-overlap re-plans the row buckets in backward-readiness
    order — gather/scatter counts, payloads, and the no-param-all-reduce
    invariant pin identically to sync zero3."""
    r = _report("zero3-overlap")
    sync = _report("zero3")
    assert r["signature_violations"] == []
    assert r["meta"]["overlap"] is True
    for kind in ("all-gather", "reduce-scatter"):
        assert _count(r, kind) == _count(sync, kind)
        assert _payload(r, kind) == _payload(sync, kind)
    assert _payload(r, "all-reduce") <= 64


def test_zero3_signature_bucketed_gathers_and_scatters():
    r = _report("zero3")
    assert r["signature_violations"] == []
    n_buckets = r["meta"]["n_buckets"]
    padded = r["meta"]["padded_param_bytes"]
    n = r["mesh"]["data"]
    assert n_buckets < r["meta"]["n_param_leaves"]
    # forward gathers the full padded params, once per BUCKET (the
    # O(n_leaves) -> O(n_buckets) collapse; per-leaf counts are pinned
    # against this path in test_zero3_bucketing_collapses_llama_launches)
    assert _count(r, "all-gather") == n_buckets
    assert _payload(r, "all-gather") == padded
    # backward reduce-scatters the 1/n grad shards, once per bucket
    assert _count(r, "reduce-scatter") == n_buckets
    assert _payload(r, "reduce-scatter") == padded // n
    # NO param-sized all-reduce — that would be replicated DP again
    assert _payload(r, "all-reduce") <= 64


def test_zero_stage1_vs_stage2_collective_distinction():
    r1, r2 = _report("zero1"), _report("zero2")
    assert r1["signature_violations"] == []
    assert r2["signature_violations"] == []
    padded = r1["meta"]["padded_param_bytes"]
    # stage 1: full-grad all-reduce, NO reduce-scatter
    assert _payload(r1, "all-reduce") >= padded
    assert _count(r1, "reduce-scatter") == 0
    # stage 2: grads reduce-scatter straight to shards (one launch per
    # bucket), NO full all-reduce
    assert _count(r2, "reduce-scatter") == r2["meta"]["n_buckets"]
    assert _payload(r2, "all-reduce") <= 64
    # both all-gather the updated params back to replicas
    for r in (r1, r2):
        assert _payload(r, "all-gather") == padded
        assert _count(r, "all-gather") == r["meta"]["n_buckets"]


def test_zero3_bucketing_collapses_llama_launches():
    """The tentpole's machine-checkable core: on a param tree with a
    realistic leaf count (tiny LLaMA, 12 leaves), the bucketed ZeRO-3
    step launches O(n_buckets) collectives — strictly fewer than the
    per-leaf path's O(n_leaves) — while moving the same padded bytes."""
    bucketed = xa.compile_strategy("zero3", workload="llama")
    per_leaf = xa.compile_strategy(
        "zero3", workload="llama", bucketed=False
    )
    assert "error" not in bucketed and "error" not in per_leaf
    assert bucketed["signature_violations"] == []
    assert per_leaf["signature_violations"] == []
    n_leaves = per_leaf["meta"]["n_param_leaves"]
    n_buckets = bucketed["meta"]["n_buckets"]
    assert n_buckets < n_leaves
    for kind in ("all-gather", "reduce-scatter"):
        assert _count(per_leaf, kind) == n_leaves
        assert _count(bucketed, kind) == n_buckets
        assert _count(bucketed, kind) < _count(per_leaf, kind)
        # same padded payload rides fewer launches
        assert _payload(bucketed, kind) == _payload(per_leaf, kind)


def test_zero3_prefetch_gather_rides_the_layer_scan():
    """Leg-2 pin: the scanned-LLaMA prefetch step's parameter all-gather
    sits INSIDE the layer while-loop (trip count == n_layers, annotated
    by XLA) — one launch per layer-bucket per trip plus the initial
    double-buffer fill — instead of one up-front whole-tree gather."""
    r = _report("zero3-prefetch")
    assert r["signature_violations"] == []
    assert r["lowered"] == "train_step"
    L = r["meta"]["n_layers"]
    n_lb = r["meta"]["n_layer_buckets"]
    n_ob = r["meta"]["n_outer_buckets"]
    in_loop = [
        o for o in r["collectives"]["ops"]
        if o["kind"] == "all-gather" and o["count"] >= L - 1
    ]
    assert in_loop and all(o["trip_known"] for o in in_loop)
    # forward issues: L-1 in-scan (the peeled last layer prefetches
    # nothing) + 1 initial fill per layer-bucket, plus the outer
    # (embed/ln_f/unembed) gathers — exactly one gather per layer
    assert _count(r, "all-gather") == n_lb * L + n_ob
    # the backward reduce-scatters every layer's grads
    assert _count(r, "reduce-scatter") >= n_lb * (L - 1)
    assert _payload(r, "all-reduce") <= 64  # never collapses to DP


def test_strategy_reports_pin_memory_budgets_and_donation():
    """Satellite pins: every describe() that declares a peak-HBM budget
    or a donation floor is enforced through signature_violations (so an
    HBM regression fails tier-1 like a comms regression), and the
    donated builds alias a nonzero byte count on this backend."""
    for name in ("dp", "zero1", "zero2", "zero3", "zero3-prefetch", "ep"):
        r = _report(name)
        assert r["signature_violations"] == []
        assert "memory" in r["expected"], name
        assert "donation" in r["expected"], name
        assert r["memory"]["peak_hbm_bytes"] <= (
            r["expected"]["memory"]["max_peak_hbm_bytes"]
        )
        assert r["donation"]["hbm_saved_bytes"] >= (
            r["expected"]["donation"]["min_saved_bytes"]
        )
        assert r["donation"]["hbm_saved_bytes"] > 0


def test_pipeline_signature_ticks_times_permutes():
    r = _report("pipeline")
    assert r["signature_violations"] == []
    T = r["meta"]["ticks"]  # M + S - 1
    hops = _count(r, "collective-permute")
    # value_and_grad: the scan transpose replays the M+S-1 forward hops
    assert r["lowered"] == "value_and_grad"
    assert T * 2 <= hops <= T * 3
    assert all(
        o["axes"] == ["stage"]
        for o in r["collectives"]["ops"]
        if o["kind"] == "collective-permute"
    )
    # every boundary hop carries the [mb, L, d] activation
    assert _payload(r, "collective-permute") == hops * r["meta"]["boundary_bytes"]


def test_het_pipeline_signature():
    r = _report("het_pipeline")
    assert r["signature_violations"] == []
    T = r["meta"]["ticks"]
    hops = _count(r, "collective-permute")
    expect = T if r["lowered"] == "loss" else 2 * T
    assert hops == expect
    assert _payload(r, "collective-permute") == hops * r["meta"]["boundary_bytes"]
    assert _count(r, "all-gather") == 0


def test_tp_signature_allreduce_over_model_only():
    r = _report("tp")
    assert r["signature_violations"] == []
    # >= 2 row-parallel psums per block forward + backward mirrors
    assert _count(r, "all-reduce") >= 4 * r["meta"]["n_layers"]
    assert _count(r, "collective-permute") == 0
    # nothing may group outside the model axis (no data axis on this mesh)
    assert all(
        set(o["axes"]) <= {"model"}
        for o in r["collectives"]["ops"]
        if o["axes"] is not None and o["result_bytes"] > 64
    )


def test_sp_ring_signature_permutes_over_seq():
    r = _report("sp")
    assert r["signature_violations"] == []
    n = r["meta"]["seq_shards"]
    # at least one KV rotation per ring step per layer, plus boundary hops
    assert _count(r, "collective-permute") >= r["meta"]["n_layers"] * n
    assert _count(r, "all-to-all") == 0  # ring mode never all-to-alls
    assert all(
        o["axes"] == ["seq"]
        for o in r["collectives"]["ops"]
        if o["kind"] == "collective-permute"
    )


def test_ep_signature_alltoall_dispatch_combine():
    r = _report("ep")
    assert r["signature_violations"] == []
    # dispatch + combine forward; backward transposes may CSE
    assert 2 <= _count(r, "all-to-all") <= 4
    assert _count(r, "collective-permute") == 0
    assert _count(r, "reduce-scatter") == 0
    assert all(
        o["axes"] == ["expert"]
        for o in r["collectives"]["ops"] if o["kind"] == "all-to-all"
    )


def test_reports_carry_memory_and_flops():
    r = _report("dp")
    assert r["memory"]["peak_hbm_bytes"] > 0
    assert r["flops"] and r["flops"] > 0
    assert "TPU v4" in r["projection"]


def test_pipeline_grad_signature_doubles_the_ring():
    # the pipeline strategy lowers value_and_grad: the
    # transpose must replay the forward's M+S-1 hops in reverse
    r = _report("pipeline")
    assert r["lowered"] == "value_and_grad"
    assert _count(r, "collective-permute") >= 2 * r["meta"]["ticks"]


# ----------------------------------------------------- bench driver pieces


def test_attach_parent_telemetry_merges_into_bench_line():
    import bench

    rec = {"metric": "m", "value": 0.0, "error": "accelerator unreachable"}
    failures = [{"record": "bench_retry_failure", "attempt": 1,
                 "error": "timeout", "backoff_s": 60.0, "wall_s": 1.0,
                 "rc": None}]
    cr = {"record": "compile_report", "strategies": {}}
    out = bench.attach_parent_telemetry(rec, failures, cr)
    assert out["telemetry"]["retry_failures"] == failures
    assert out["telemetry"]["compile_report"] is cr
    # an existing telemetry dict is extended, not replaced
    rec2 = {"telemetry": {"enabled": True, "phases": {}}}
    out2 = bench.attach_parent_telemetry(rec2, failures, None)
    assert out2["telemetry"]["enabled"] is True
    assert out2["telemetry"]["retry_failures"] == failures


def test_compile_report_document_shape():
    from ddl25spring_tpu.obs.compile_report import build_compile_report

    doc = build_compile_report(["dp"])
    assert doc["record"] == "compile_report"
    assert "dp" in doc["strategies"]
    # reuse the cached strategy report for the deep checks
    assert doc["strategies"]["dp"]["collectives"]["totals"]
