"""Perf observatory: measurement decomposition invariants, the
regression ledger + gate, and the bench telemetry.perf contract.

The measurement layer's contract (``ddl25spring_tpu/obs/perfscope.py``):

- the step-wall decomposition is internally consistent — exposed comms
  is never negative, overlap efficiency lives in [0, 1], and the
  micro-cost table covers the compile-time collective inventory
  EXACTLY (every op site appears, costed or explicitly not);
- measured MFU is *defined* on this CPU image (the calibrated
  ``cpu-host`` pseudo-spec), with a projection error against the PR-2
  roofline on the same spec;
- records append to a JSONL ledger keyed by (strategy, mesh, host),
  and ``tools/perf_report.py --check`` trips on a genuinely slowed
  step (host-callback sleep) while a clean re-run passes.

Budget note (ROADMAP 870 s): the one dp measurement is compiled ONCE at
module scope and shared by every invariant test; the full
``bench.py --smoke`` subprocess pin is ``slow``-marked (CI's tier-1 job
asserts the same telemetry.perf contract on its own smoke run).
"""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl25spring_tpu.obs import perfscope

# ----------------------------------------------------- shared measurement

_CACHE: dict = {}


def _dp_record() -> dict:
    """Measure-once cache: the dp strategy's perf record (compiles the
    4-way step, the 1-device counterfactual, and the micro benches one
    time for the whole module)."""
    if "dp" not in _CACHE:
        _CACHE["dp"] = perfscope.measure_strategy(
            "dp", reps=4, warmup=2, micro_reps=3
        )[0]
    return _CACHE["dp"]


# ------------------------------------------------ decomposition invariants


def test_decomposition_invariants():
    rec = _dp_record()
    assert rec["step_s_p50"] > 0
    assert rec["step_s_p95"] >= rec["step_s_p50"] >= rec["step_s_min"]
    # the 1-device counterfactual exists for dp and is *compute*: with
    # the per-device workload held fixed (describe() scales its batch
    # with the mesh) it cannot exceed the contended 4-fake-device step
    # by more than scheduling noise (factor-2 slack: fake CPU devices
    # share this host's cores)
    assert rec["compute_s_p50"] is not None
    assert rec["compute_s_p50"] <= rec["step_s_p50"] * 2
    # exposed comms is clamped non-negative by construction
    assert rec["exposed_comms_s"] >= 0
    # dp's grad all-reduce is real traffic on this mesh: the micro cost
    # model must have priced it
    assert rec["micro_total_s"] > 0
    # capped at 1.0, deliberately NOT floored at 0: negative efficiency
    # is the contended-fake-mesh signal (exposure beyond the comms
    # bill) that before/after comparisons and --min-overlap-eff need
    assert rec["overlap_eff"] is None or rec["overlap_eff"] <= 1.0


def test_micro_costs_cover_inventory_exactly():
    """Every op site in the PR-2 collective inventory appears in the
    micro table — costed, or carrying an explicit why-not note."""
    from ddl25spring_tpu.obs import xla_analytics as xa

    rec = _dp_record()
    mesh = xa.strategy_mesh("dp")
    d = xa.describe_strategy("dp", mesh)
    compiled = d["fn"].lower(*d["args"]).compile()
    ops = xa.parse_hlo_collectives(compiled.as_text(), mesh)
    assert [m["op"] for m in rec["micro"]] == [o["name"] for o in ops]
    assert [m["count"] for m in rec["micro"]] == [o["count"] for o in ops]
    for m in rec["micro"]:
        assert (m["t_s"] is not None) or m.get("note")
    # the non-scalar grad-bucket all-reduce is costed (group of 4 over
    # the data axis — real wire traffic)
    big = [m for m in rec["micro"] if m["result_bytes"] > 64]
    assert big and all(m["t_s"] is not None and m["t_s"] > 0 for m in big)


def test_measured_mfu_defined_on_cpu_host():
    rec = _dp_record()
    assert rec["chip"] == "cpu-host"
    assert rec["peak_source"] == "calibrated-host"
    assert rec["peak_flops_per_chip"] and rec["peak_flops_per_chip"] > 0
    assert rec["measured_mfu"] and rec["measured_mfu"] > 0
    assert rec["projected_mfu"] and rec["projection_err"] is not None


def test_record_schema_and_ledger_key_fields():
    rec = _dp_record()
    required = {
        "record", "schema", "ts", "strategy", "mesh", "n_chips", "host",
        "git_sha", "jax_version", "backend", "chip",
        "peak_flops_per_chip", "peak_source", "reps", "warmup",
        "step_s_p50", "step_s_p95", "step_s_min", "compute_s_p50",
        "exposed_comms_s", "micro_total_s", "overlap_eff", "flops",
        "bytes_accessed", "wire_bytes", "measured_mfu", "projected_mfu",
        "projected_bound", "projection_err", "micro", "findings",
    }
    assert required <= set(rec)
    assert rec["record"] == "perf"
    assert rec["mesh"] == {"data": 4} and rec["n_chips"] == 4
    # the record is JSON-serializable as-is (the ledger contract)
    json.dumps(rec)


def test_perf_cell_carries_the_bench_contract_keys():
    cell = perfscope.perf_cell(_dp_record())
    assert {
        "measured_mfu", "overlap_eff", "exposed_comms_ms",
        "projection_err",
    } <= set(cell)
    assert cell["exposed_comms_ms"] is not None
    assert cell["exposed_comms_ms"] >= 0
    assert cell["measured_mfu"] > 0


def test_calibrated_host_peak_cached():
    from ddl25spring_tpu.utils.flops import calibrated_host_peak_flops

    p1 = calibrated_host_peak_flops()
    assert p1 and p1 > 0
    t0 = time.perf_counter()
    assert calibrated_host_peak_flops() == p1  # cache hit, no re-run
    assert time.perf_counter() - t0 < 0.05


# -------------------------------------------- ledger + regression gate


def _toy_step():
    """A step heavy enough (512x512 matmul chain, ~tens of ms on a CI
    core) that scheduling jitter is small RELATIVE to the wall time —
    light steps flake the tolerance band on shared CI machines."""
    a = jnp.full((512, 512), 0.5, jnp.float32)

    @jax.jit
    def f(x):
        for _ in range(8):
            x = x @ a
        return x

    return f, (a,)


def _slowed_step(sleep_s: float = 0.3):
    """The same toy step with a deliberate host-callback sleep inside
    the dispatch — the 'someone added a host round-trip to the hot
    path' regression the gate exists to catch."""
    f, (a,) = _toy_step()

    def cb(y):
        time.sleep(sleep_s)
        return np.asarray(y)

    @jax.jit
    def slow(x):
        y = f(x)
        return jax.pure_callback(
            cb, jax.ShapeDtypeStruct(y.shape, y.dtype), y
        )

    return slow, (a,)


def test_ledger_roundtrip_and_torn_tail(tmp_path):
    led = str(tmp_path / "ledger.jsonl")
    rec = perfscope.measure_callable(
        *_toy_step(), strategy="toy", reps=3, warmup=1
    )
    perfscope.append_ledger(rec, led)
    with open(led, "a") as f:
        f.write('{"record": "perf", "torn')  # killed mid-write
    out = perfscope.read_ledger(led)
    assert len(out) == 1
    assert out[0]["strategy"] == "toy"


def test_slowed_step_trips_the_gate_and_clean_rerun_passes(tmp_path):
    """The acceptance loop: clean baseline -> injected slowdown fails
    ``perf_report --check`` -> clean re-run passes again."""
    import tools.perf_report as perf_report

    # unambiguous both ways even beside five busy xdist workers (a 2x
    # band with a 0.3 s sleep flaked there: a CLEAN re-measurement of a
    # ~20 ms step landed outside 2x): clean runs must stay inside 5x,
    # the injected 1 s sleep is a >= 30x step regression
    band = ["--check", "--tolerance", "4.0"]
    led = str(tmp_path / "ledger.jsonl")
    fast_fn, fast_args = _toy_step()
    for _ in range(2):
        perfscope.append_ledger(perfscope.measure_callable(
            fast_fn, fast_args, strategy="toy", reps=6, warmup=2
        ), led)
    assert perf_report.main(["--ledger", led, *band]) == 0

    perfscope.append_ledger(perfscope.measure_callable(
        *_slowed_step(1.0), strategy="toy", reps=4, warmup=1
    ), led)
    assert perf_report.main(["--ledger", led, *band]) == 1

    perfscope.append_ledger(perfscope.measure_callable(
        fast_fn, fast_args, strategy="toy", reps=6, warmup=2
    ), led)
    assert perf_report.main(["--ledger", led, *band]) == 0


def test_check_is_per_host_and_needs_a_baseline(tmp_path, capsys):
    import tools.perf_report as perf_report

    led = str(tmp_path / "ledger.jsonl")
    rec = perfscope.measure_callable(
        *_toy_step(), strategy="toy", reps=3, warmup=1
    )
    perfscope.append_ledger(rec, led)
    # single record: no baseline, check passes with a note
    assert perf_report.main(["--ledger", led, "--check"]) == 0
    assert "no baseline" in capsys.readouterr().err
    # a 100x slower record from a DIFFERENT host never gates this one
    other = dict(rec, host="elsewhere/64cpu/tpu",
                 step_s_p50=rec["step_s_p50"] * 100)
    perfscope.append_ledger(other, led)
    assert perf_report.main(["--ledger", led, "--check"]) == 0
    # missing ledger: rc 2 under --check (CI misconfiguration must not
    # read as a pass), rc 0 without
    assert perf_report.main(
        ["--ledger", str(tmp_path / "absent.jsonl"), "--check"]
    ) == 2
    assert perf_report.main(
        ["--ledger", str(tmp_path / "absent.jsonl")]
    ) == 0


def test_min_overlap_eff_floor_gates_and_skips_undefined(tmp_path, capsys):
    """The --min-overlap-eff satellite: an absolute floor on the latest
    record's measured overlap efficiency — gates even a single fresh
    record, skips keys whose efficiency is undefined, and stays out of
    the way when the flag is absent."""
    import tools.perf_report as perf_report

    led = str(tmp_path / "ledger.jsonl")
    base = perfscope.measure_callable(
        *_toy_step(), strategy="toy", reps=2, warmup=1
    )
    low = dict(base, strategy="ov-low", overlap_eff=0.2,
               exposed_comms_s=0.008, micro_total_s=0.01)
    high = dict(base, strategy="ov-high", overlap_eff=0.9,
                exposed_comms_s=0.001, micro_total_s=0.01)
    undefined = dict(base, strategy="ov-none", overlap_eff=None)
    for r in (low, high, undefined):
        perfscope.append_ledger(r, led)
    # floor above the low record's 0.2: exactly one key fails
    assert perf_report.main(
        ["--ledger", led, "--check", "--min-overlap-eff", "0.5"]
    ) == 1
    err = capsys.readouterr().err
    fails = [l for l in err.splitlines() if l.startswith("CHECK FAIL")]
    assert len(fails) == 1
    assert "ov-low" in fails[0] and "overlap_eff 0.200" in fails[0]
    # floor below every defined record: passes (undefined key skipped)
    assert perf_report.main(
        ["--ledger", led, "--check", "--min-overlap-eff", "0.1"]
    ) == 0
    # no flag: the floor never engages
    assert perf_report.main(["--ledger", led, "--check"]) == 0


def test_dp_record_carries_bucket_knob_fields():
    """Sweep comparability: every strategy record names the bucket
    threshold + plan it measured (the DDL25_BUCKET_BYTES knob's value
    at build time) so grid points and env-knob runs never mix
    silently.  Since PR 9 the describe() default is the multi-bucket
    DESCRIBE_BUCKET_BYTES (the sched verifier's overlap windows need
    >= 2 launches to exist), not the 4 MiB runtime default."""
    from ddl25spring_tpu.parallel import dp

    rec = _dp_record()
    assert rec["bucket_bytes"] == dp.DESCRIBE_BUCKET_BYTES
    assert rec["n_buckets"] == 3


def test_record_carries_static_overlap_bound():
    """PR-9 wiring: every measured record ships the schedule verifier's
    analytical overlap ceiling next to the measured overlap_eff — dp is
    a sync-issue strategy, so its committed schedule provably allows
    (essentially) nothing, and the bound says so deterministically."""
    rec = _dp_record()
    assert "static_overlap_bound" in rec
    assert rec["static_overlap_bound"] == 0.0
    assert "static_overlap_bound" in perfscope.perf_cell(rec)


def test_bucket_sweep_measures_grid_and_recommends(tmp_path):
    """tools/bucket_sweep.py: one re-tagged record per grid point (the
    perf gate never sees them), exactly one marked best, and the best
    is the measured-fastest."""
    from tools.bucket_sweep import render_table, sweep_strategy

    records = sweep_strategy(
        "dp", (1024, 4 * 1024 * 1024), reps=2, warmup=1, micro_reps=1
    )
    assert len(records) == 2
    assert all(r["record"] == "bucket_sweep" for r in records)
    assert [r["bucket_bytes"] for r in records] == [1024, 4 * 1024 * 1024]
    # the 1 KiB grid point splits the 2.6 KiB MLP tree; 4 MiB holds it
    assert records[0]["n_buckets"] > records[1]["n_buckets"] == 1
    best = [r for r in records if r.get("best")]
    assert len(best) == 1
    assert best[0]["step_s_p50"] == min(r["step_s_p50"] for r in records)
    table = render_table("dp", records)
    assert "best" in table and "bucket_bytes" in table
    # sweep records are invisible to the perf regression gate
    led = str(tmp_path / "ledger.jsonl")
    for r in records:
        perfscope.append_ledger(r, led)
    assert perfscope.read_ledger(led) == []


# ------------------------------------------------ H001 cross-referencing


def test_attach_measured_costs_prices_h001():
    from ddl25spring_tpu.analysis.engine import attach_measured_costs

    findings = [
        {"rule": "H001", "op": "all-reduce.7", "severity": "warn"},
        {"rule": "H001", "op": "all-reduce.9", "severity": "warn"},
        {"rule": "H005", "op": "params['w1']", "severity": "error"},
    ]
    record = {
        "exposed_comms_s": 0.004,
        "overlap_eff": 0.25,
        "micro": [
            {"op": "all-reduce.7", "t_s": 0.003, "t_total_s": 0.003},
            {"op": "other.1", "t_s": 0.001, "t_total_s": 0.001},
        ],
    }
    n = attach_measured_costs(findings, record)
    assert n == 2  # both H001s annotated; H005 untouched
    assert findings[0]["measured"]["t_s_per_exec"] == 0.003
    assert findings[0]["measured"]["exposed_comms_s"] == 0.004
    # op not in the micro table still gains the strategy-level context
    assert findings[1]["measured"]["exposed_comms_s"] == 0.004
    assert "t_s_per_exec" not in findings[1]["measured"]
    assert "measured" not in findings[2]
    # the bench parent hands over the ms-denominated telemetry cell
    cell_findings = [{"rule": "H001", "op": "x", "severity": "warn"}]
    attach_measured_costs(cell_findings, {"exposed_comms_ms": 12.0})
    assert cell_findings[0]["measured"]["exposed_comms_s"] == (
        pytest.approx(0.012)
    )


def test_strategy_record_findings_ride_with_measured_slot():
    rec = _dp_record()
    # dp is pinned lint-clean, so no H001 here — but the findings slot
    # exists and is trimmed to the ledger schema
    assert isinstance(rec["findings"], list)
    for f in rec["findings"]:
        assert set(f) <= {
            "rule", "severity", "op", "bytes", "source", "waived",
            "measured",
        }


# ----------------------------------------------------- report rendering


def test_perf_report_table_renders(tmp_path, capsys):
    import tools.perf_report as perf_report

    led = str(tmp_path / "ledger.jsonl")
    perfscope.append_ledger(_dp_record(), led)
    assert perf_report.main(["--ledger", led]) == 0
    out = capsys.readouterr().out
    assert "strategy dp" in out and "step p50" in out and "MFU" in out


def test_perf_report_format_json_is_machine_readable(tmp_path, capsys):
    """PR-9 satellite: --format json mirrors graft_lint --format json —
    one structured document carrying the grouped records AND every
    check verdict, so CI parses instead of grepping stderr tables."""
    import tools.perf_report as perf_report

    led = str(tmp_path / "ledger.jsonl")
    base = _dp_record()
    perfscope.append_ledger(base, led)
    slow = dict(base, step_s_p50=base["step_s_p50"] * 50, ts=base["ts"] + 1)
    perfscope.append_ledger(slow, led)

    assert perf_report.main(["--ledger", led, "--format", "json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["record"] == "perf_report"
    (g,) = doc["groups"]
    assert g["strategy"] == "dp" and len(g["records"]) == 2
    # the 50x regression verdict rides the document
    assert doc["check"]["ok"] is False and doc["check"]["fails"] == 1
    assert any("step_s_p50" in f for f in g["fails"])

    # --check still gates on the same shared verdicts
    assert perf_report.main(
        ["--ledger", led, "--format", "json", "--check"]
    ) == 1
    out = capsys.readouterr()
    assert json.loads(out.out)["check"]["fails"] == 1
    assert "CHECK FAIL" in out.err
    # legacy --json spelling stays an alias
    assert perf_report.main(["--ledger", led, "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["record"] == "perf_report"


def test_obs_report_renders_performance_section(tmp_path, capsys):
    from ddl25spring_tpu.obs.report import format_report, summarize_run

    run_dir = str(tmp_path / "run")
    os.makedirs(run_dir)
    with open(os.path.join(run_dir, "metrics.jsonl"), "w") as f:
        f.write(json.dumps({"record": "header", "n_chips": 1}) + "\n")
        f.write(json.dumps(
            {"record": "step", "step": 0, "wall_s": 0.1, "label": "x"}
        ) + "\n")
    perfscope.write_run_perf(_dp_record(), run_dir)
    text = format_report(summarize_run(run_dir))
    assert "performance (perf.json" in text
    assert "measured MFU" in text
    assert "overlap efficiency" in text
    assert "cpu-host" in text


# --------------------------------------------- bench --smoke contract pin


@pytest.mark.slow
def test_bench_smoke_emits_perf_cell(tmp_path):
    """The acceptance pin: a --smoke BENCH line carries a full
    telemetry.perf cell and appends a ledger record.  slow-marked (one
    extra ResNet CPU compile); the tier-1 CI job asserts the same
    contract on its own bench --smoke run."""
    led = str(tmp_path / "ledger.jsonl")
    obs_dir = str(tmp_path / "run")
    # the CI smoke environment: single CPU device (the suite's 8-device
    # XLA_FLAGS would build the slower DPxPP pipeline), production
    # donation defaults
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "DDL25_DONATE", "DDL25_CHAOS")
    }
    env.update(JAX_PLATFORMS="cpu", DDL25_BENCH_NTRAIN="256")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(root, "bench.py"), "--smoke",
         "--steps", "2", "--per-chip-batch", "16",
         "--obs-dir", obs_dir, "--perf-ledger", led],
        capture_output=True, text=True, timeout=900, env=env, cwd=root,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    line = [ln for ln in r.stdout.splitlines() if ln.strip()][-1]
    d = json.loads(line)
    perf = d["telemetry"]["perf"]
    for k in ("measured_mfu", "overlap_eff", "exposed_comms_ms",
              "projection_err"):
        assert k in perf, (k, perf)
    assert perf["measured_mfu"] > 0
    assert perf["exposed_comms_ms"] >= 0
    assert perf["chip"] == "cpu-host"
    # the record landed in the ledger and in the run dir
    recs = perfscope.read_ledger(led)
    assert recs and recs[-1]["strategy"] == "bench-dp"
    assert os.path.exists(os.path.join(obs_dir, perfscope.PERF_BASENAME))
