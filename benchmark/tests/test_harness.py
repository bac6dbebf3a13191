"""The harness end to end at tiny sizes on the CPU: both runners through
their Python entry, cells and metrics added as files only, and the command
refusing to run without a TPU."""

import hashlib
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

    from ddl25spring_tpu.utils.mesh import make_mesh

    bench = os.path.join(ROOT, "benchmark")
    config = run.load_json(os.path.join(HERE, "data", "configs", "tiny-test.json"))
    train = run.load_module(bench, "runners", "train")
    family = run.load_family(bench, config)
    dp, stages, n_layers = train.placement(config, 4)
    assert (dp, stages, n_layers) == (2, 2, 2)
    cfg = family.build(config, n_layers=n_layers)
    mesh = make_mesh(jax.devices()[:4], data=dp, stage=stages)
    staged, opt_state = train.init_state(
        family, cfg, optax.adam(1e-3), mesh, stages, 2**31 + 5)
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


OTHER_CONFIG = {  # the second family's keys are its own; "run" and "placement" are the runner's
    "name": "tiny-other", "source": "none: a toy used only by benchmark/tests on the CPU",
    "family": "tinydec", "width": 32, "heads": 2, "depth": 2, "tokens": 96, "context": 32,
    "run": {"layers_per_stage": 2, "learning_rate": 8e-4, "use_flash": False},
    "placement": {"1": {"data": 1, "stage": 1}},
}
OTHER_CELLS = {
    "serve": {"engine": {"max_slots": 4, "prefill_batch": 2, "max_prompt_len": 16,
                         "page_len": 4, "pages_per_seq": 8, "n_pages": 40, "max_queue": 16},
              "traffic": {"loop": "closed", "clients": 4, "pool_size": 16, "pool_seed": 0,
                          "prompt_len": {"kind": "lognormal", "median": 8, "sigma": 0.5,
                                         "min": 2, "max": 16},
                          "max_new": {"kind": "fixed", "value": 6},
                          "tokens": {"kind": "uniform"}}},
    "train": {"traffic": {"sequences_per_step": 8, "microbatches": 4, "schedule": "gpipe",
                          "tokens": {"kind": "zipf", "a": 1.1}}},
}
FAKE_PEAKS = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9}  # so the peak readers speak


def tree_digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                out[os.path.join(d, f)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def add_family(bench_dir, family, drop_layers=()):
    """What a PR that brings an architecture adds, as files alone: a family
    file, a configuration that names it, and a cell for each runner."""
    with open(os.path.join(HERE, "data", "families", "tinydec.py")) as f:
        source = f.read()
    marker = "DROP_LAYERS: tuple = ()"
    assert marker in source
    with open(os.path.join(bench_dir, "families", f"{family}.py"), "x") as f:
        f.write(source.replace(marker, f"DROP_LAYERS: tuple = {tuple(drop_layers)!r}"))
    with open(os.path.join(bench_dir, "configs", f"{family}-cfg.json"), "x") as f:
        json.dump(dict(OTHER_CONFIG, name=f"{family}-cfg", family=family), f)
    for runner, body in OTHER_CELLS.items():
        with open(os.path.join(bench_dir, "workloads", f"{family}-{runner}.json"), "x") as f:
            json.dump({"name": f"{family}-{runner}", "config": f"{family}-cfg",
                       "runner": runner, "chips": 1, "why": "test only", **body}, f)


def test_a_model_family_is_picked_up_as_new_files(bench_dir):
    """An architecture lands as a family file, a configuration and cells;
    the runners ``serve`` and ``train`` run it, hold it to ITS reference and
    report the benchmark's metrics under their own names."""
    before = tree_digest(bench_dir)
    add_family(bench_dir, "tinydec")
    kw = dict(bench_dir=bench_dir, allow_cpu=True)
    served = run.run_cell("tinydec-serve", 2**31 + 9, 0.7, False, **kw)
    check_line(served, {"setup_s", "serve_tokens_s_chip", "ttft_p95_ms", "tpot_p95_ms"})
    assert served["compared"]["worst_margin"]["limit"] == 1e-3  # the family's own
    assert 0 < served["compared"]["tokens_checked"]["value"]
    trained = run.run_cell("tinydec-train", 2**31 + 9, 0.5, False, **kw)
    check_line(trained, {"setup_s", "train_tokens_s_chip"})
    assert trained["compared"]["loss_rel_step1"]["limit"] == 1e-4
    traced = run.run_cell("tinydec-train", 7, 0.5, True, peaks=FAKE_PEAKS, **kw)
    check_line(traced, {"mfu_pct.train", "step_ms.train"})
    # the reader's count is the family's: 6 x (2 x 16 x 32^2 + 32 x 96) + 6 x 2 x 32 x 32
    rate = 8 * 32 / (traced["metrics"]["step_ms.train"]["value"] / 1e3)
    assert traced["metrics"]["mfu_pct.train"]["value"] == pytest.approx(
        100.0 * rate * 227_328 / 1e9, rel=0.5)  # median step against the window's mean
    after = tree_digest(bench_dir)
    assert {p: h for p, h in after.items() if p in before} == before  # nothing edited


@pytest.mark.parametrize("runner", ["serve", "train"])
def test_a_family_whose_reference_drops_a_layer_is_not_correct(bench_dir, runner):
    """The negative control: the same program against a reference that
    skips a layer it runs."""
    add_family(bench_dir, "tinydec-dropped", drop_layers=(1,))
    r = run.run_cell(f"tinydec-dropped-{runner}", 5, 0.5, False,
                     bench_dir=bench_dir, allow_cpu=True)
    assert r["correct"] is False and r["failed"] == 0, r["compared"]
    first = next(iter(r["compared"].values()))
    assert first["value"] > first["limit"]


def test_a_configuration_names_its_family_or_does_not_run(bench_dir):
    add_family(bench_dir, "tinydec")
    cfg_path = os.path.join(bench_dir, "configs", "tinydec-cfg.json")
    config = run.load_json(cfg_path)
    with open(cfg_path, "w") as f:
        json.dump({k: v for k, v in config.items() if k != "family"}, f)
    with pytest.raises(KeyError, match="names no model family"):
        run.run_cell("tinydec-serve", 1, 0.3, False, bench_dir=bench_dir, allow_cpu=True)
    with open(cfg_path, "w") as f:
        json.dump(dict(config, family="not-there"), f)
    with pytest.raises(FileNotFoundError, match="families/not-there.py"):
        run.run_cell("tinydec-train", 1, 0.3, False, bench_dir=bench_dir, allow_cpu=True)


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
