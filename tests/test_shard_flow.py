"""graft-shard: the sharding-flow verifier + the partition-rule engine.

Four contracts pinned here:

1. **The rules fire** — synthetic positives and near-miss negatives for
   H011 (implicit reshard), H012 (rule-coverage defect), and H013
   (cross-program layout mismatch), like every rule before them.
2. **Strategy-as-data is exact** — the ``dp-rules`` / ``zero3-rules``
   registry strategies lower to optimized HLO **bitwise identical** to
   their bespoke builders, with their tables proven covered (every
   param leaf matched exactly once, every rule reachable).
3. **The layout contracts hold on the real programs** — ZeRO-family
   entry-parameter shardings match ``ft/reshard``'s ``[n, k]`` /
   ``[L, n, k]`` checkpoint contract, and the serve prefill/decode
   programs agree on the paged-KV pool split.
4. **The flow walk attributes collectives** — zero3's gathers trace
   back to the ``dim0/n``-sharded param shards that feed them.

Every registered-strategy fact rides the shared lower-once compile
cache (``tests/conftest.py``, now ``keep_hlo=True``) — this module
pays for ZERO extra strategy compiles.
"""

import json
import re

import jax
import jax.numpy as jnp
import pytest

from conftest import cached_strategy_report as _report  # lower-once cache
from ddl25spring_tpu.analysis import engine, shard_flow
from ddl25spring_tpu.obs import xla_analytics as xa
from ddl25spring_tpu.parallel import rules as prules
from ddl25spring_tpu.utils.mesh import make_mesh


def _lint(hlo, **kw):
    kw.setdefault("obs_enabled", False)
    kw.setdefault("waivers", [])
    return engine.lint_hlo_text(hlo, **kw)


def _rules_fired(findings):
    return {f.rule for f in findings}


# ------------------------------------------------- sharding-attr parsing


def test_parse_sharding_forms():
    ps = xa.parse_sharding
    assert ps(None) is None
    assert ps("replicated")["replicated"] is True
    assert ps("maximal device=0")["maximal"] is True

    d = ps("devices=[4,1]<=[4]")
    assert d["partitioned_dims"] == [0] and d["partitions"] == {0: 4}
    assert not d["replicated"]

    d = ps("devices=[1,4]<=[4]")
    assert d["partitioned_dims"] == [1] and d["partitions"] == {1: 4}

    # stacked [L, n, k]: the layer dim replicated, rows on dim 1
    d = ps("devices=[1,4,1]<=[4]")
    assert d["partitioned_dims"] == [1]

    # a trailing replicated tile dim is a subgroup, not a data split
    d = ps("devices=[2,1,2]<=[4] last_tile_dim_replicate")
    assert d["partitioned_dims"] == [0] and d["trailing_subgroups"] == 1
    d = ps("devices=[2,1,2]<=[4] last_tile_dims={replicated}")
    assert d["partitioned_dims"] == [0] and d["trailing_subgroups"] == 1


def test_sharding_attr_of_line_balances_braces():
    line = ('%p = f32[4]{0} parameter(0), sharding={devices=[2,2]<=[4] '
            'last_tile_dims={manual}}, metadata={op_name="x"}')
    attr = xa._sharding_attr_of_line(line)
    assert attr == "devices=[2,2]<=[4] last_tile_dims={manual}"
    assert xa._sharding_attr_of_line("%p = f32[4]{0} parameter(0)") is None


def test_sharding_summary_tokens():
    assert shard_flow.sharding_summary(None) == "-"
    assert shard_flow.sharding_summary({"replicated": True}) == "replicated"
    assert shard_flow.sharding_summary(
        {"partitioned_dims": [0], "partitions": {0: 4}}
    ) == "dim0/4"


def test_h013_proof_survives_a_json_roundtrip():
    """Stored reports are the re-run substrate (compile_report.json):
    JSON coerces the partitions dict's int keys to strings, and the
    walk must still judge them — no spurious 'matching no mesh axis'
    error, no KeyError in the summary."""
    report = {
        "strategy": "zero3", "meta": {"zero_stage": 3},
        "mesh": {"data": 4}, "donation": {"donatable_leaves": 1},
        "entry_params": [{
            "number": 0, "name": "p0", "bytes": 2048,
            "type": "f32[1,128]{1,0}", "arg": "param_shards['w1']",
            "sharding": {"replicated": False, "maximal": False,
                         "manual": False, "tile": [4, 1],
                         "trailing_subgroups": 0,
                         "partitioned_dims": [0], "partitions": {0: 4}},
        }],
    }
    rt = json.loads(json.dumps(report))
    assert rt["entry_params"][0]["sharding"]["partitions"] == {"0": 4}
    assert shard_flow.saved_layout_findings(rt) == []
    assert shard_flow.sharding_summary(
        rt["entry_params"][0]["sharding"]
    ) == "dim0/4"
    # a real violation still fires on the round-tripped shape
    rt["entry_params"][0]["sharding"]["partitions"] = {"0": 2}
    fs = shard_flow.saved_layout_findings(rt)
    assert [f.rule for f in fs] == ["H013"]


# -------------------------------------------------- partition-rule engine


def test_match_partition_rules_first_match_wins_and_raises_unmatched():
    tree = {"w1": jnp.zeros((2, 2)), "b1": jnp.zeros((2,))}
    atoms = prules.match_partition_rules(prules.TABLES["zero3"], tree)
    assert atoms == {"w1": "rows", "b1": "rows"}
    # first match wins: a catch-all AFTER a specific rule never fires
    atoms = prules.match_partition_rules(
        [("^w1$", "rows"), (".*", "replicated")], tree
    )
    assert atoms == {"w1": "rows", "b1": "replicated"}
    with pytest.raises(ValueError, match="no partition rule matches"):
        prules.match_partition_rules([("^w", "rows")], tree)


def test_partition_rule_validates_atom_and_regex():
    with pytest.raises(ValueError, match="unknown layout"):
        prules.PartitionRule("^w", "diagonal")
    import re as _re

    with pytest.raises(_re.error):
        prules.PartitionRule("[", "rows")
    # a typo'd discipline must fail at table construction, not fall
    # through discipline_of()'s legacy flags into wrong sched verdicts
    with pytest.raises(ValueError, match="discipline"):
        prules.RuleTable(
            name="t", axes=("data",),
            rules=(prules.PartitionRule(".*", "rows"),),
            discipline="overlpa",
        )


def test_rule_coverage_matrix():
    cov = prules.rule_coverage(
        [("^w", "rows"), ("^w1$", "rows"), ("^b", "rows")],
        ["w1", "w2", "b1"],
    )
    by_path = {r["path"]: r for r in cov["leaves"]}
    assert by_path["w1"]["matches"] == [0, 1]  # ambiguous
    assert by_path["w2"]["matches"] == [0]
    assert cov["rules"][1]["first_matches"] == 0  # shadowed by rule 0
    assert cov["rules"][1]["matches"] == 1
    assert cov["rules"][2]["first_matches"] == 1


def test_leaf_paths_join_nested_names():
    tree = {"blocks": {"wq": jnp.zeros(2)}, "w1": jnp.zeros(2)}
    assert set(prules.leaf_paths(tree)) == {"blocks/wq", "w1"}


def test_rule_table_meta_roundtrips_through_json():
    meta = prules.TABLES["zero3"].to_meta()
    again = json.loads(json.dumps(meta))
    assert again == meta
    assert shard_flow.coverage_defects(again, ["w1", "b1", "w2"]) == []


@pytest.fixture(scope="module")
def mesh4(devices8):
    return make_mesh(devices8[:4], data=4)


def test_rule_partitioner_rejects_mixed_and_layers_tables(mesh4):
    mixed = prules.RuleTable(
        name="mixed", axes=("data",),
        rules=(
            prules.PartitionRule("^w", "rows"),
            prules.PartitionRule("^b", "replicated"),
        ),
    )
    tree = {"w1": jnp.zeros((2, 2)), "b1": jnp.zeros((2,))}
    with pytest.raises(NotImplementedError, match="mixes layouts"):
        prules.RulePartitioner(mesh4, mixed).layout_of(tree)
    layered = prules.RuleTable(
        name="layered", axes=("data",),
        rules=(prules.PartitionRule(".*", "layers"),),
    )
    with pytest.raises(NotImplementedError, match="layers"):
        prules.RulePartitioner(mesh4, layered).layout_of(tree)
    wrong_axis = prules.RuleTable(
        name="w", axes=("model",),
        rules=(prules.PartitionRule(".*", "rows"),),
    )
    with pytest.raises(ValueError, match="mesh axes"):
        prules.RulePartitioner(mesh4, wrong_axis)


def test_rule_partitioner_shard_params_matches_zero_layout(mesh4):
    from ddl25spring_tpu.parallel.zero import zero_shard_params

    params = {"w1": jnp.arange(12.0).reshape(3, 4), "b1": jnp.ones((3,))}
    part = prules.RulePartitioner(mesh4, prules.TABLES["zero3"])
    a = part.shard_params(params)
    b = zero_shard_params(params, mesh4, "data")
    assert jax.tree.all(jax.tree.map(
        lambda x, y: bool((x == y).all()) and x.sharding == y.sharding,
        a, b,
    ))
    # the replicated table passes params through untouched
    part_dp = prules.RulePartitioner(mesh4, prules.TABLES["dp"])
    assert part_dp.shard_params(params) is params


def test_discipline_rides_the_table_as_data():
    from ddl25spring_tpu.analysis import sched

    assert sched.discipline_of({"discipline": "sync"}) == "sync"
    assert sched.discipline_of({"discipline": "overlap"}) == "overlap"
    # the legacy flags still decide when no table discipline is present
    assert sched.discipline_of({"overlap": True}) == "overlap"
    assert sched.discipline_of({}) == "sync"


# --------------------------------------------------------- H011 synthetic

_H011_UNDECLARED_GATHER = """\
HloModule h011
ENTRY %main (x: f32[128]) -> f32[512] {
  %x = f32[128]{0} parameter(0)
  ROOT %ag = f32[512]{0} all-gather(f32[128]{0} %x), replica_groups={{0,1,2,3}}, dimensions={0}
}
"""


def test_h011_undeclared_collective_fires_and_declared_is_quiet():
    report = {"expected": {"scalar_bytes": 64, "all-reduce": {"count": 1}}}
    fs = _lint(_H011_UNDECLARED_GATHER, report=report)
    f = next(f for f in fs if f.rule == "H011")
    assert f.severity == "error"
    assert "never declared" in f.message
    assert f.bytes == 512 * 4
    # declaring the kind (with any bounds) clears it
    report2 = {"expected": {"scalar_bytes": 64,
                            "all-gather": {"max_bytes": 4096}}}
    assert "H011" not in _rules_fired(
        _lint(_H011_UNDECLARED_GATHER, report=report2)
    )
    # FORBIDDING it also clears H011 — the violation is then the
    # signature gate's department, not an undeclared-traffic claim
    report3 = {"expected": {"scalar_bytes": 64,
                            "forbidden": ["all-gather"]}}
    assert "H011" not in _rules_fired(
        _lint(_H011_UNDECLARED_GATHER, report=report3)
    )
    # no declared signature at all: no claim to hold the HLO to
    assert "H011" not in _rules_fired(_lint(_H011_UNDECLARED_GATHER))


def test_h011_scalar_bookkeeping_is_exempt():
    small = _H011_UNDECLARED_GATHER.replace("512", "8").replace("128", "2")
    report = {"expected": {"scalar_bytes": 64, "all-reduce": {"count": 1}}}
    assert "H011" not in _rules_fired(_lint(small, report=report))


# --------------------------------------------------------- H012 synthetic

_NO_COLLECTIVES = """\
HloModule h012
ENTRY %main (x: f32[4]) -> f32[4] {
  ROOT %x = f32[4]{0} parameter(0)
}
"""


def _h012(table_rules, paths):
    report = {"meta": {
        "rule_table": {"name": "t", "rules": table_rules},
        "param_paths": paths,
    }}
    return _lint(_NO_COLLECTIVES, report=report)


def test_h012_unmatched_leaf_is_an_error():
    fs = _h012([["^w", "rows"]], ["w1", "b1"])
    f = next(f for f in fs if f.rule == "H012")
    assert f.severity == "error" and "unmatched" in f.message
    assert f.op == "b1"


def test_h012_shadowed_rule_can_never_fire():
    # rule #1 matches only w1, which rule #0 already takes: shadowed
    fs = _h012([["^w", "rows"], ["^w1$", "rows"]], ["w1", "w2"])
    kinds = {f.message.split("[")[1].split("]")[0]
             for f in fs if f.rule == "H012"}
    assert "shadowed" in kinds
    assert "ambiguous" in kinds  # w1 matched twice: order load-bearing
    assert all(
        f.severity == "warn" for f in fs if f.rule == "H012"
    )


def test_h012_bad_table_is_loud_not_a_crash():
    fs = _h012([["[", "rows"]], ["w1"])
    f = next(f for f in fs if f.rule == "H012")
    assert f.severity == "error" and "bad-table" in f.message
    fs = _h012([["^w", "diagonal"]], ["w1"])
    assert any("bad-table" in f.message for f in fs if f.rule == "H012")


def test_h012_clean_table_and_non_table_strategies_are_quiet():
    fs = _h012([["^w", "rows"], ["^b", "rows"]], ["w1", "b1", "w2"])
    assert "H012" not in _rules_fired(fs)
    assert "H012" not in _rules_fired(_lint(_NO_COLLECTIVES))


# --------------------------------------------------------- H013 synthetic

_H013_TRANSPOSED = """\
HloModule h013
ENTRY %main (p0: f32[128,4]) -> f32[128,4] {
  ROOT %p0 = f32[128,4]{1,0} parameter(0), sharding={devices=[1,4]<=[4]}, metadata={op_name="param_shards['w']"}
}
"""


def test_h013_transposed_save_layout_fires_through_the_engine():
    """The satellite case: a [k, n] save layout — rows on dim 1 instead
    of ft/reshard's dim-0 contract — caught from the compiled program's
    own entry-parameter sharding."""
    report = {"meta": {"zero_stage": 3}, "mesh": {"data": 4},
              "donation": {"donatable_leaves": 1}}
    fs = _lint(_H013_TRANSPOSED, report=report)
    f = next(f for f in fs if f.rule == "H013")
    assert f.severity == "error"
    assert "param_shards['w']" in (f.op or "")
    assert "dim" in f.message and "reshard" in f.message
    # the near-miss: the contract layout [n, k] (rows on dim 0) passes
    ok = _H013_TRANSPOSED.replace("devices=[1,4]", "devices=[4,1]")
    assert "H013" not in _rules_fired(_lint(ok, report=report))
    # replicated leaves (zero1/2 params) make no sharded-save claim
    rep = _H013_TRANSPOSED.replace(
        "sharding={devices=[1,4]<=[4]}", "sharding={replicated}"
    )
    assert "H013" not in _rules_fired(_lint(rep, report=report))
    # a non-ZeRO-family strategy makes no claim at all
    assert "H013" not in _rules_fired(
        _lint(_H013_TRANSPOSED, report={"meta": {}, "mesh": {"data": 4}})
    )


def test_h013_row_count_must_match_a_mesh_axis():
    # [n, k] on dim 0 but split 2 ways on a 4-way mesh: the row refit
    # cannot be exact
    hlo = _H013_TRANSPOSED.replace("devices=[1,4]", "devices=[2,1]")
    report = {"meta": {"zero_stage": 3}, "mesh": {"data": 4},
              "donation": {"donatable_leaves": 1}}
    fs = _lint(hlo, report=report)
    f = next(f for f in fs if f.rule == "H013")
    assert "matching no mesh axis" in f.message


def test_h013_serve_pair_mismatch_and_declared_dim():
    mk = lambda dims, parts: {  # noqa: E731 — tiny local factory
        "meta": {"program": "decode", "kv_sharded_dim": 3, "tp": 2},
        "entry_params": [{
            "number": 0, "name": "p0", "bytes": 4096,
            "type": "f32[17,2,4,2,8]",
            "arg": "pool['k']",
            "sharding": {"partitioned_dims": dims,
                         "partitions": parts},
        }],
    }
    good = mk([3], {3: 2})
    bad_dim = mk([0], {0: 2})
    # declared-dim half: pages split off the head dim flag immediately
    fs = shard_flow.serve_pair_findings({"serve-x": bad_dim})
    assert [f.rule for f in fs] == ["H013"]
    assert "head dim" in fs[0].message
    # a pool that silently fell back to REPLICATED under tp>1 is as
    # much a contract break as a wrong dim (exact match, not subset)
    fs = shard_flow.serve_pair_findings({"serve-x": mk([], {})})
    assert [f.rule for f in fs] == ["H013"]
    # at tp=1 a replicated pool is the legitimate compile
    solo = mk([], {})
    solo["meta"]["tp"] = 1
    assert shard_flow.serve_pair_findings({"serve-x": solo}) == []
    # pair half: two programs disagreeing on the same pool buffer
    fs = shard_flow.serve_pair_findings(
        {"serve-a": good, "serve-b": bad_dim}
    )
    pair = [f for f in fs if "cross-program layout mismatch" in f.message]
    assert pair
    # the finding carries a REAL strategy name (waiver globs must
    # match it), with both pair members named in the message
    assert pair[0].strategy == "serve-a"
    assert "serve-b" in pair[0].message
    # agreement is quiet
    assert shard_flow.serve_pair_findings(
        {"serve-a": good, "serve-b": mk([3], {3: 2})}
    ) == []


# ----------------------------------------- pinned real-strategy contracts


_SOURCE_TABLE_RE = re.compile(
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*",
    re.M,
)


def _program(hlo_text: str) -> str:
    """The optimized HLO minus what names the Python call stack it was
    compiled from: jax 0.9.0 prints source-location tables in the module
    header and a ``stack_frame_id`` on every op, and the session cache
    compiles each strategy under whichever test asked first."""
    return re.sub(
        r" ?stack_frame_id=\d+", "", _SOURCE_TABLE_RE.sub("", hlo_text)
    )


@pytest.mark.parametrize("bespoke,ruled", [
    ("dp", "dp-rules"), ("zero3", "zero3-rules"),
])
def test_rule_table_strategy_is_bitwise_identical_to_bespoke(
    bespoke, ruled
):
    """The tentpole acceptance pin: the strategy-as-data variants lower
    to byte-for-byte the SAME optimized HLO as the builders they will
    eventually replace — the rule engine changes where the strategy is
    written down, not what XLA compiles."""
    a, b = _report(bespoke), _report(ruled)
    assert _program(a["hlo_text"]) == _program(b["hlo_text"])
    assert "ENTRY" in _program(a["hlo_text"])  # the program survived
    assert a["signature_violations"] == [] == b["signature_violations"]


@pytest.mark.parametrize("name", ["dp-rules", "zero3-rules"])
def test_rule_table_coverage_proof_holds(name):
    """Every param leaf matched exactly once, every rule fires — the
    H012 proof, re-derived from the serialized meta exactly as the lint
    pass does (no import of the strategy module)."""
    meta = _report(name)["meta"]
    table, paths = meta["rule_table"], meta["param_paths"]
    assert shard_flow.coverage_defects(table, paths) == []
    cov = prules.rule_coverage(
        [tuple(r) for r in table["rules"]], paths
    )
    assert all(len(leaf["matches"]) == 1 for leaf in cov["leaves"])
    assert all(r["first_matches"] >= 1 for r in cov["rules"])
    assert meta["discipline"] == "sync"


def test_zero_family_entry_layouts_satisfy_the_reshard_contract():
    """The per-program H013 walk on the real compiled programs: every
    saved sharded leaf sits on the checkpoint contract's dim (rows on
    dim 0; the stacked LLaMA blocks on dim 1), with the row count equal
    to the shard axis."""
    for name in ("zero3", "zero3-rules"):
        r = _report(name)
        shards = [
            p for p in r["entry_params"]
            if p["number"] < r["donation"]["donatable_leaves"]
            and (p.get("sharding") or {}).get("partitioned_dims")
        ]
        assert shards, f"{name}: no sharded saved leaves?"
        for p in shards:
            assert p["sharding"]["partitioned_dims"] == [0], p
            assert p["sharding"]["partitions"][0] == 4, p
    r = _report("zero3-prefetch")
    stacked = [
        p for p in r["entry_params"]
        if shard_flow._type_rank(p["type"]) == 3
        and (p.get("sharding") or {}).get("partitioned_dims")
    ]
    assert stacked, "prefetch step lost its [L, n, k] stacked leaves?"
    for p in stacked:
        assert p["sharding"]["partitioned_dims"] == [1], p
    assert shard_flow.saved_layout_findings(r) == []


def test_serve_programs_agree_on_the_kv_pool_split():
    """The cross-program half on the real serve programs: prefill,
    decode AND the PR-18 trio (per-chip
    budget entries + the ZeRO-3 streaming decode) shard every pool
    buffer identically, k/v on the engine's declared head dim."""
    reports = {
        n: _report(n)
        for n in (
            "serve-decode", "serve-prefill",
            "serve-decode-tp", "serve-prefill-tp",
            "serve-decode-zero3stream",
        )
    }
    assert shard_flow.check_layout_contracts(reports, waivers=[]) == []
    for n, r in reports.items():
        pool = shard_flow._pool_params(r)
        assert set(pool) >= {"pool['k']", "pool['v']"}, (n, sorted(pool))
        for arg in ("pool['k']", "pool['v']"):
            sh = pool[arg]["sharding"]
            assert sh["partitioned_dims"] == [
                r["meta"]["kv_sharded_dim"]
            ], (n, arg, sh)


def test_flow_walk_attributes_zero3_gathers_to_sharded_params():
    """The per-tensor propagation walk on the real program: each
    forward all-gather's sources are exactly dim0/4-sharded
    param_shards leaves (the batch never feeds a gather)."""
    r = _report("zero3")
    flows = shard_flow.collective_flows(r["hlo_text"], report=r)
    gathers = [f for f in flows if f["kind"] == "all-gather"]
    assert gathers
    for g in gathers:
        assert g["sources"], g
        assert g["truncated"] is False, g  # complete walk on this program
        for s in g["sources"]:
            assert "param_shards" in s["arg"], g
            assert s["sharding"] == "dim0/4", g
    # the backward's scatters depend on the whole loss: batch included
    scatters = [f for f in flows if f["kind"] == "reduce-scatter"]
    assert scatters
    assert any(
        any("batch" in s["arg"] for s in f["sources"]) for f in scatters
    )


def test_flow_report_counts_rules_and_strips_nothing_it_needs():
    reports = {"zero3": _report("zero3"), "dp": _report("dp")}
    doc = shard_flow.flow_report(reports, waivers=[])
    assert set(doc) == {"strategies", "findings", "by_rule"}
    assert doc["findings"] == []
    entry = doc["strategies"]["zero3"]["entry_params"]
    assert any(p["sharding"] == "dim0/4" for p in entry)
    # dict is JSON-serializable (the CI artifact contract)
    json.dumps(doc)


def test_h011_dogfood_declarations_survive():
    """The two real finds from H011's first run stay declared: tp's
    partitioner-inserted loss-assembly resharding and sp's replicated-
    params grad sync are signature facts now — removing them would
    resurrect the undeclared traffic this rule exists to catch."""
    tp = _report("tp")["expected"]
    for kind in ("all-gather", "reduce-scatter", "all-to-all"):
        assert kind in tp, kind
    sp = _report("sp")["expected"]
    assert "all-reduce" in sp
    assert sp["all-reduce"]["min_bytes"] > 0


def test_graft_lint_shard_flow_renderer():
    from tools.graft_lint import _fmt_shard_flow

    lines = _fmt_shard_flow({
        "entry_params": [
            {"arg": "param_shards['w1']", "bytes": 512,
             "sharding": "dim0/4"},
            {"arg": "batch[0]", "bytes": 128, "sharding": "replicated"},
        ],
        "flows": [
            {"op": "ag.1", "kind": "all-gather",
             "sources": [{"arg": "param_shards['w1']",
                          "sharding": "dim0/4"}],
             "internal": False},
            {"op": "ar.2", "kind": "all-reduce", "sources": [],
             "internal": True},
            {"op": "ag.3", "kind": "all-gather",
             "sources": [{"arg": "params['a']", "sharding": "dim0/4"}],
             "internal": False, "truncated": True},
        ],
    })
    text = "\n".join(lines)
    assert "1 sharded" in text
    assert "param_shards['w1'][dim0/4]" in text
    assert "<loop-internal>" in text
    # a budget-truncated walk must say so, not present as complete
    assert "walk truncated" in text


@pytest.mark.slow
def test_graft_lint_cli_shard_flow_check_is_green(capsys):
    """End-to-end: the CI gate's exact invocation shape over the two
    rule-table strategies (slow: pays its own compiles)."""
    from tools import graft_lint

    rc = graft_lint.main([
        "--strategy", "dp-rules,zero3-rules", "--shard-flow", "--check",
    ])
    assert rc == 0
    err = capsys.readouterr().err
    assert "graft-lint OK" in err
