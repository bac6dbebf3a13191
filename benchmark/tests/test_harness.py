"""The harness end to end at tiny sizes on the CPU: both runners through
their Python entry, cells and metrics added as files only, and the command
refusing to run without a TPU."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import flops, run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def check_line(result, metrics):
    assert RESULT_KEYS <= set(result)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(metrics) <= set(result["metrics"]), result["metrics"]
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(result["device"])
    json.dumps(result)  # the line is JSON as it stands


@pytest.mark.parametrize("cell,chips", [("tiny-train", 1), ("tiny-dppp", 4)])
def test_train_runner_end_to_end(bench_dir, cell, chips):
    """A cell and a configuration that exist only as ADDED files run: the
    one-chip mesh, and the DP x PP mesh on four virtual devices."""
    r = run.run_cell(cell, 2**31 + 7, 0.5, False, bench_dir=bench_dir, allow_cpu=True)
    check_line(r, {"setup_s", "train_tokens_s_chip"})
    assert r["device"]["count"] == chips
    assert r["metrics"]["train_tokens_s_chip"]["unit"] == "tokens/s/chip"


def test_training_state_is_placed_by_stage_not_replicated():
    """Each chip holds its own stage's layers and their Adam moments."""
    import jax
    import optax
    from jax.sharding import PartitionSpec as P

    from benchmark import model
    from ddl25spring_tpu.utils.mesh import make_mesh

    config = run.load_json(os.path.join(HERE, "data", "configs", "tiny-test.json"))
    dp, stages, n_layers = model.train_placement(config, 4)
    assert (dp, stages, n_layers) == (2, 2, 2)
    cfg = model.llama_config(config, n_layers=n_layers)
    mesh = make_mesh(jax.devices()[:4], data=dp, stage=stages)
    train = run.load_module(os.path.join(ROOT, "benchmark"), "runners", "train")
    staged, opt_state = train.init_state(cfg, optax.adam(1e-3), mesh, stages, 2**31 + 5)
    for tree in (staged, opt_state[0].mu, opt_state[0].nu):
        wq = tree["blocks"]["wq"]
        assert wq.sharding.spec == P("stage") and wq.shape[0] == stages
        assert {s.data.shape[0] for s in wq.addressable_shards} == {1}
        assert tree["unembed"].sharding.spec == P()


def test_train_runner_traced_reports_per_layer_metrics(bench_dir):
    r = run.run_cell("tiny-train", 3, 0.5, True, bench_dir=bench_dir, allow_cpu=True)
    # no device plane and no peaks on the CPU: the trace and peak readers
    # find nothing to read and their metrics are left out, not zeroed
    check_line(r, {"compile_s.train", "compiles_in_window.train", "step_ms.train"})
    assert "device_idle_pct.train" not in r["metrics"]
    assert "mfu_pct.train" not in r["metrics"]
    assert "setup_s" not in r["metrics"]  # end-to-end metrics: --trace 0 only
    assert r["metrics"]["compiles_in_window.train"]["value"] == 0.0


@pytest.mark.parametrize("cell", ["tiny-serve", "tiny-serve-open"])
def test_serve_runner_end_to_end(bench_dir, cell):
    """Closed loop, and open-loop Poisson arrivals with shared prefixes:
    both are data for the one generator."""
    r = run.run_cell(cell, 5, 0.7, False, bench_dir=bench_dir, allow_cpu=True)
    check_line(r, {"setup_s", "serve_tokens_s_chip", "ttft_p95_ms", "tpot_p95_ms"})
    t = run.run_cell(cell, 5, 0.7, True, bench_dir=bench_dir, allow_cpu=True)
    check_line(t, {"prefill_ms.serve", "decode_tick_ms.serve",
                   "prefill_share_pct.serve", "compiles_in_window.serve"})
    assert 0.0 < t["metrics"]["prefill_share_pct.serve"]["value"] < 100.0


def test_a_metric_and_its_reader_are_picked_up_as_new_files(bench_dir):
    """What a later PR does: a per-layer metric is a JSON file and a small
    reader; nothing that exists is edited."""
    with open(os.path.join(bench_dir, "readers", "throwaway.py"), "w") as f:
        f.write("def read(record, args):\n    return len(record['step_s']) * args['k']\n")
    with open(os.path.join(bench_dir, "metrics", "steps_x2.train.json"), "w") as f:
        json.dump({"kind": "per_layer", "runner": "train", "unit": "count",
                   "better": "higher", "source": "program_counter",
                   "layer": "step builders", "moves": "train_tokens_s_chip",
                   "reader": "throwaway", "args": {"k": 2},
                   "workloads": ["tiny-train"]}, f)
    r = run.run_cell("tiny-train", 1, 0.3, True, bench_dir=bench_dir, allow_cpu=True)
    assert r["metrics"]["steps_x2.train"]["value"] == 2.0 * r["attempted"]
    # its "workloads" list keeps it out of every other cell
    specs = run.metric_specs(bench_dir, "train", "tiny-dppp", "per_layer")
    assert "steps_x2.train" not in [s["name"] for s in specs]


def test_command_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "olmo1b-train-2k", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120, cwd=ROOT,
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_unknown_device_kind_is_an_error():
    assert flops.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="not in peaks.json"):
        flops.load_peaks("TPU v9 imaginary")


def test_manifest_names_the_files_that_exist():
    """``BENCHMARK.json`` and the data files say the same thing."""
    bench = os.path.join(ROOT, "benchmark")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert manifest["paths"] == ["benchmark"]
    configs = {c["name"]: c for c in manifest["configs"]}
    for name, c in configs.items():
        on_disk = run.load_json(os.path.join(ROOT, c["file"]))
        assert on_disk["source"] == c["source"] and on_disk["reduced"] == c["reduced"]
    for w in manifest["workloads"]:
        cell, _ = run.load_cell(bench, w["name"])
        assert cell["config"] == w["config"] in configs
        assert cell["chips"] == w["chips"] and cell["why"] == w["why"]
        assert os.path.isfile(os.path.join(bench, "runners", cell["runner"] + ".py"))
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for kind in ("end_to_end", "per_layer"):
        for m in manifest[kind]:
            spec = run.load_json(os.path.join(bench, "metrics", m["name"] + ".json"))
            assert spec["kind"] == kind
            for key in ("unit", "better", "source"):
                assert spec[key] == m[key], (m["name"], key)
            if kind == "per_layer":
                assert spec["layer"] == m["layer"] and spec["moves"] == m["moves"] in e2e
            assert os.path.isfile(os.path.join(bench, "readers", spec["reader"] + ".py"))
    on_disk = {f[:-5] for f in os.listdir(os.path.join(bench, "metrics"))}
    assert on_disk == {m["name"] for k in ("end_to_end", "per_layer") for m in manifest[k]}


def test_manifest_keeps_the_contracts_limits():
    """The limits a driver refuses a file over, before any run."""
    import re

    path = os.path.join(ROOT, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    assert os.path.getsize(path) < 64 * 1024
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    cells = [w["name"] for w in manifest["workloads"]]
    assert 1 <= manifest["run_seconds"] <= 51
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and name.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(cells) // 4)
    assert len({(w["config"], w["traffic"]) for w in manifest["workloads"]}) == len(cells)
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"]) and c["file"].startswith("benchmark/")
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200 and len(c["reduced"]) <= 16
        assert any(w["config"] == c["name"] for w in manifest["workloads"])
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0 < m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        # the end-to-end metric it moves is reported in every cell it is in
        assert set(m.get("workloads", cells)) <= set(e2e[m["moves"]].get("workloads", cells))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for cell in cells:  # set-up, one more end-to-end metric, one per-layer metric
        assert sum(cell in m.get("workloads", cells) for m in manifest["end_to_end"]) >= 2
        assert any(cell in m.get("workloads", cells) for m in manifest["per_layer"])
