#!/usr/bin/env python3
"""The one command of the benchmark: run ONE cell once, in this process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--workload X`` opens ``workloads/X.json``; that file names the
configuration (``configs/<config>.json``) and the runner
(``runners/<runner>.py``), and the configuration names its model family
(``families/<family>.py``).  After the run, every ``metrics/*.json`` whose
``runner`` matches (and whose optional ``workloads`` list holds the cell)
is read by the reader it names (``readers/<reader>.py``): the
``end_to_end`` ones with ``--trace 0``, the ``per_layer`` ones with
``--trace 1``.  A later PR adds a cell, a configuration, a model family, a
metric, a reader or a runner kind by adding files; it edits none that is
here.

Everything the harness knows about a model comes from the family file,
which the runner gets as ``ctx["family"]``.  A family file has
``build(config, n_layers=, use_flash=)`` (the program's configuration
object, refusing what the program cannot state), ``init_params(cfg, seed)``
and ``init_staged_params(cfg, seed, stages)`` (seeded weights on the
device), ``vocab(cfg)`` and ``seq_len(cfg)`` (what the traffic draws),
``check_served(cfg, params, done, pad_to=)``, ``reference_loss(cfg, params,
tokens)`` and ``check_train_loss(system, reference)`` (the plain
reference's checks, which decide ``correct``), ``train_flops_per_token(cfg)``
and ``flash_calls(cfg, batch)`` (the counts the peak readers divide by).  A
configuration with no ``"family"`` is an error; there is no default.

The last line of stdout is the one JSON object of the contract
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, when
traced ``breakdown``, and last ``compared``: each number that decided
``correct`` beside its limit, which are also the last lines on stderr).
Earlier lines are notes for a reader, each one JSON object too.  No TPU, or
fewer chips than the cell asks for: exit code 2 and no result line.
``BENCH_RUN`` in the environment is ignored.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell needs: no result is printed."""


def note(**record) -> None:
    """One line for a human reader; never the last line.  Written to the
    process's own stdout even while ``main`` points ``sys.stdout`` at
    stderr for whatever the program prints."""
    print(json.dumps(record), file=sys.__stdout__, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(bench_dir: str, kind: str, name: str):
    """``<bench_dir>/<kind>/<name>.py`` as a module, found by name."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind}/{name}.py: {path} is missing")
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(bench_dir: str, workload: str) -> tuple[dict, dict]:
    cell = load_json(os.path.join(bench_dir, "workloads", f"{workload}.json"))
    config = load_json(os.path.join(bench_dir, "configs", f"{cell['config']}.json"))
    return cell, config


def load_family(bench_dir: str, config: dict):
    """The model family a configuration names, found by name like a runner
    or a reader.  There is no default: a configuration that names none
    would otherwise run as whatever model the harness happened to know."""
    if "family" not in config:
        raise KeyError(
            f"configuration {config.get('name')!r} names no model family: add "
            '"family": "<name>" for families/<name>.py; there is no default'
        )
    return load_module(bench_dir, "families", config["family"])


def metric_specs(bench_dir: str, runner: str, workload: str, kind: str) -> list[dict]:
    """The metric files that apply to this cell, by name."""
    out = []
    for path in sorted(glob.glob(os.path.join(bench_dir, "metrics", "*.json"))):
        spec = load_json(path)
        spec["name"] = os.path.basename(path)[: -len(".json")]
        if spec["kind"] != kind or spec.get("runner") not in (None, runner):
            continue  # "runner": null means every runner's cells
        if "workloads" in spec and workload not in spec["workloads"]:
            continue
        out.append(spec)
    return out


def read_metrics(bench_dir: str, specs: list[dict], record: dict) -> dict:
    """``{name: {"value", "unit"}}``; a reader that finds nothing to read
    returns ``None`` and its metric is left out of the line."""
    out = {}
    for spec in specs:
        reader = load_module(bench_dir, "readers", spec["reader"])
        value = reader.read(record, spec.get("args", {}))
        if value is not None:
            out[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    return out


def compile_cache_dir() -> str:
    """Where ``JAX_COMPILATION_CACHE_DIR`` says, else the fixed path
    ``<checkout>/.jax_cache`` (the path is part of the cache's key)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache"
    )


class CompileLog:
    """Counts programs compiled OR fetched from the persistent cache, with
    the host time of each, so that a window can ask how many fell in it."""

    def __init__(self) -> None:
        self.times: list[float] = []  # host clock at the end of each
        self.cache = {"hits": 0, "misses": 0}

    def install(self, jax) -> None:
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, _seconds: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.times.append(time.perf_counter())

    def _event(self, event: str, **_) -> None:
        if event.endswith("/cache_hits"):
            self.cache["hits"] += 1
        elif event.endswith("/cache_misses"):
            self.cache["misses"] += 1

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t <= t1)


class Tracer:
    """The profiler slice of a ``--trace 1`` run, and the harness's host
    spans.  ``span(name)`` is a ``jax.profiler.TraceAnnotation``: it lands
    in the trace's host plane on the device trace's own clock, and costs
    nothing to speak of while no trace is being taken."""

    def __init__(self, jax, enabled: bool, out_dir: str) -> None:
        self._jax = jax
        self.enabled = enabled
        self.dir = out_dir
        self.names: set[str] = set()
        self.active = False

    def span(self, name: str):
        self.names.add(name)
        return self._jax.profiler.TraceAnnotation(name)

    def start(self) -> None:
        if not self.enabled or self.active:
            return
        opts = self._jax.profiler.ProfileOptions()
        opts.host_tracer_level = 2
        opts.python_tracer_level = 0
        self._jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.active = True

    def stop(self) -> None:
        if not self.active:
            return
        self._jax.profiler.stop_trace()
        self.active = False

    def xplane(self) -> str | None:
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"), recursive=True)
        return max(found, key=os.path.getmtime) if found else None


def device_line(devices, chips: int, record: dict) -> dict:
    """The ``device`` key: as JAX reports it, for the chips the cell used."""
    used = devices[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in used]
    peaks = [p for p in peaks if p is not None]
    out = {
        "platform": used[0].platform,
        "kind": used[0].device_kind,
        "count": len(used),
        "memory_peak_bytes": max(peaks) if peaks else None,
    }
    if record.get("trace"):
        out["busy_s"] = record["trace"]["busy_s"]
        out["window_s"] = record["trace"]["window_s"]
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             bench_dir: str = BENCH_DIR, allow_cpu: bool = False,
             peaks: dict | None = None, keep_trace: str | None = None) -> dict:
    """Run one cell and return the contract's result object.

    ``allow_cpu`` exists for the benchmark's own tests, which drive the
    runners at tiny sizes on the CPU through this entry; the command line
    cannot set it, so nothing a CPU measures is ever printed as a result.
    With it the peak readers find no peaks and stay silent, unless the test
    hands in ``peaks`` of its own to see their arithmetic.
    """
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cell, config = load_cell(bench_dir, workload)
    chips = int(cell["chips"])
    runner = load_module(bench_dir, "runners", cell["runner"])
    family = load_family(bench_dir, config)

    import jax

    devices = jax.devices()
    if not allow_cpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU here (found {devices[0].platform})")
    if len(devices) < chips:
        raise NoChip(f"cell needs {chips} chips, found {len(devices)}")

    if not allow_cpu:
        # every program is cached, however quick to compile or small
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = CompileLog()
    compiles.install(jax)

    from benchmark import flops

    if not allow_cpu:
        peaks = flops.load_peaks(
            devices[0].device_kind, os.path.join(bench_dir, "peaks.json")
        )
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    tracer = Tracer(jax, trace, trace_dir)
    try:
        record = runner.run({
            "cell": cell, "config": config, "family": family, "seed": int(seed),
            "seconds": float(seconds), "trace": bool(trace),
            "devices": devices[:chips], "chips": chips,
            "t_process": T_PROCESS, "tracer": tracer,
        })
        tracer.stop()
        record["trace"] = None
        xplane = tracer.xplane() if trace else None
        if xplane:
            from benchmark import trace_reduce

            record["trace"] = trace_reduce.reduce_trace(
                trace_reduce.load(xplane), tracer.names
            )
            if keep_trace:
                os.makedirs(keep_trace, exist_ok=True)
                shutil.copy(xplane, os.path.join(keep_trace, f"{workload}.xplane.pb"))
    finally:
        tracer.stop()
        shutil.rmtree(trace_dir, ignore_errors=True)

    device = device_line(devices, chips, record)
    record.update(chips=chips, peaks=peaks, cell=cell, config=config,
                  memory_peak_bytes=device["memory_peak_bytes"])
    record["compiles_in_window"] = compiles.between(
        record["t_open_host"], record["t_close_host"]
    )
    kind = "per_layer" if trace else "end_to_end"
    specs = metric_specs(bench_dir, cell["runner"], workload, kind)
    note(workload=workload, seed=seed, window_s=record["window_s"],
         compile_cache=compiles.cache, compiles_in_window=record["compiles_in_window"],
         **({"longest_idle_gaps": record["trace"]["longest_gaps"]} if record["trace"] else {}),
         **record["notes"])
    result = {
        "correct": bool(record["correct"]),
        "attempted": int(record["attempted"]),
        "failed": int(record["failed"]),
        "metrics": read_metrics(bench_dir, specs, record),
        "device": device,
    }
    if record["trace"]:
        result["breakdown"] = {
            "device_ops": record["trace"]["device_ops"],
            "idle_gaps": record["trace"]["idle_gaps"],
        }
    # last in the line, and the last lines on stderr: what decided `correct`
    result["compared"] = record["compared"]
    for name, c in record["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the traced run's .xplane.pb into DIR")
    args = ap.parse_args(argv)
    try:
        # whatever the program prints for its users goes to stderr: stdout
        # carries one JSON object per line and nothing else
        with contextlib.redirect_stdout(sys.stderr):
            result = run_cell(
                args.workload, args.seed, args.seconds, bool(args.trace),
                keep_trace=args.keep_trace,
            )
    except NoChip as e:
        print(f"benchmark: {e}; this command measures the chip and does "
              "not fall back", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
