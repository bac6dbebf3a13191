"""Headline benchmark: the BASELINE.json north-star config.

North star (`BASELINE.json`): DP+PP ResNet-18/CIFAR-10 via the `run-b2.sh`
path at >= 5,000 samples/sec/chip.  The train step is built by
``ddl25spring_tpu.benchmarks.build_resnet_step`` — the same builder
`lab/s01_b2_dp_pp.py` uses, so the bench cannot drift from what run-b2.sh
runs.  Normalization happens device-side inside the jitted step.

**Primary input mode: HBM-resident dataset + on-device epoch shuffle,
K train steps fused per dispatch** (``build_resnet_scan_step``) — the
whole 147 MiB uint8 train split lives on device; the compiled program
draws K fresh, disjoint, epoch-permuted batches and runs K train steps
per Python dispatch (a ``lax.scan`` over the same inner step).  Real
input semantics (every sample once per epoch) with the per-dispatch
host overhead amortized — the idiomatic TPU input design:
data in HBM, input pipeline inside the program, host only ticks epochs.
Three secondary lines keep the bench honest:

- ``hbm-resident-shuffle``: the same input, ONE step per dispatch
  (rounds 1-3's primary; its delta vs the scan line is the measured
  dispatch overhead).

- ``native-stream-uint8``: the C++ prefetcher pushes a fresh batch across
  the host->device link every step; the measured link bandwidth is
  emitted as ``h2d_mib_per_s`` so the number is self-describing.
- ``fixed-device-batch``: one device-resident batch re-fed (pure compute,
  the upper bound).

Topology: DP+PP (2-stage heterogeneous pipeline x DP) when >= 2 chips are
attached, pure DP on a single chip — the emitted JSON names the layout it
actually ran.

A FedAvg round-time line rides in ``secondary`` too: one timed
``make_fedavg_round`` on the tutorial_1a workload (N=10, C=0.1, B=100,
E=1, lr=0.01, seed=10 — the reference's wall-time-accounted FedAvg round,
``lab/tutorial_1a/hfl_complete.py:294,373``), the second metric
BASELINE.json tracks.

Driver contract: print ONE JSON line with at least
``{"metric", "value", "unit", "vs_baseline"}``.  Extra self-describing
fields: ``input``, ``data`` (real vs synthetic CIFAR), ``topology``,
``chip``, ``mfu``, ``achieved_tflops_per_chip``, ``secondary`` (list: the
streaming, fixed-batch, and FedAvg runs).  A backend that cannot be
reached is an error: the process prints ONE JSON line with an ``error``
field and exits non-zero.

**One process for the chip.**  A plain run — CPU or accelerator — is this
one process: it reaches the backend, runs, prints its line, and a failure
is its exit code.  A chip belongs to one process at a time, so nothing
here touches a backend before it is known which process will run.

**Resilience** is opt-in: ``--save-every`` / ``--resume-from`` /
``DDL25_CHAOS`` engage the relaunch parent (:func:`run_with_retries`).
The parent never initialises a backend (pinned in
``tests/test_entrypoints.py``); it re-execs this file with
``DDL25_BENCH_CHILD=1`` in FRESH CHILD SUBPROCESSES, forwards the child's
stderr, relaunches a dead child at once with ``--resume-from`` when a
durable checkpoint exists, and prints the first JSON line that carries no
``error``.  After the last attempt it prints the last error line and
exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import jax


# failure reason codes for per-attempt telemetry (satellite: classify
# retry failures instead of shipping a raw error string)
REASON_DEVICE = "device_unreachable"
REASON_COMPILE = "compile_error"
REASON_RUNTIME = "runtime_error"
REASON_STALLED = "stalled"
REASON_PREEMPTED = "preempted"

_DEVICE_MARKERS = (
    "accelerator unreachable", "device init timed out", "unavailable",
    "deadline_exceeded", "failed to connect", "connection",
    "no devices", "backend 'tpu' failed to initialize", "device loss",
)
_COMPILE_MARKERS = (
    "compil", "lowering", "mosaic", "hlo", "xla_internal",
    "unimplemented",
)
# external-termination exit statuses: SIGTERM as the scheduler's
# preemption notice (subprocess reports -15, a shell-style wrapper 143)
# and SIGKILL as its hard deadline / the OOM killer (-9 / 137)
_PREEMPT_RCS = (143, -15, 137, -9)


def classify_failure(error: str | None, rc: int | None = None) -> str:
    """Map an attempt's error string (+ exit status) to a coarse reason
    code, so a BENCH_r*.json capture states *what kind* of death
    occurred without anyone grepping raw strings: ``preempted`` (killed
    from outside — SIGTERM/143, SIGKILL; the auto-resume path),
    ``device_unreachable`` (backend init/device loss),
    ``stalled`` (watchdog/driver timeout killed a wedged run),
    ``compile_error`` (lowering/XLA compilation), ``runtime_error``
    (everything else)."""
    e = (error or "").lower()
    if rc in _PREEMPT_RCS or "preempt" in e or "sigterm" in e:
        return REASON_PREEMPTED
    if "exceeded" in e and "killed" in e:
        return REASON_STALLED
    if any(m in e for m in _DEVICE_MARKERS):
        return REASON_DEVICE
    if any(m in e for m in _COMPILE_MARKERS):
        return REASON_COMPILE
    return REASON_RUNTIME


def error_record(error: str, **extra) -> dict:
    """The bench line of a run that measured nothing: the driver
    contract's four keys with ``value`` 0.0 and the ``error``.  It names
    no layout — nothing ran in one."""
    return {
        "metric": "cifar10_resnet18_samples_per_sec_per_chip",
        "value": 0.0, "unit": "samples/sec/chip", "vs_baseline": 0.0,
        "error": error, **extra,
    }


def attach_parent_telemetry(
    record: dict, failures: list | None, compile_report: dict | None,
    resume: dict | None = None,
) -> dict:
    """Merge the retry driver's structured failure records and the
    pre-device compile report into a bench record's ``telemetry`` dict
    (creating it when the child ran without ``--obs-dir``).  The result
    is what makes a dead-device BENCH line machine-diagnosable: the
    errors that killed each attempt AND the compile-time perf facts that
    need no device at all.  ``resume`` (the retry driver's recovery
    summary — resume count, total steps lost to replay) merges into the
    child-reported ``telemetry.resume`` cell."""
    tel = record.get("telemetry")
    if not isinstance(tel, dict):
        tel = {"enabled": False}
    if failures:
        tel["retry_failures"] = failures
    if resume:
        child_resume = tel.get("resume")
        tel["resume"] = {
            **(child_resume if isinstance(child_resume, dict) else {}),
            **resume,
        }
    if compile_report is not None:
        tel["compile_report"] = compile_report
        tel["lint"] = lint_summary(compile_report)
    # runtime-health summary: when the record (or any attempt) carries a
    # flight dump, surface it at telemetry.health so a dead run's BENCH
    # line points straight at its post-mortem artifact
    health = tel.get("health") if isinstance(tel.get("health"), dict) else {}
    dump = record.get("flight_dump") or next(
        (f.get("flight_dump") for f in reversed(failures or [])
         if f.get("flight_dump")), None,
    )
    if dump and "flight_dump" not in health:
        health["flight_dump"] = dump
    if "error" in record:
        health.setdefault("reason", classify_failure(record["error"]))
    if health:
        tel["health"] = health
    record["telemetry"] = tel
    return record


def lint_summary(compile_report: dict) -> dict:
    """Condense the per-strategy hazard findings the compile report
    carries (``ddl25spring_tpu/analysis``) into the BENCH line's lint
    cell: total/unwaived counts, the worst unwaived severity, a count of
    strategies the linter could NOT judge (compile/lint errors — never
    conflated with "clean"), and a per-strategy breakdown — next to the
    compile report so a dead-TPU run still states the judgment, not
    just the inventory."""
    from ddl25spring_tpu.analysis.engine import summarize
    from ddl25spring_tpu.analysis.rules import severity_rank

    per: dict = {}
    worst = None
    total = unwaived = errors = 0
    for name, r in (compile_report.get("strategies") or {}).items():
        if "findings" not in r:
            # a strategy the linter never judged must not read as clean:
            # record WHY (compile error / lint crash) and count it
            err = r.get("lint_error") or r.get("error")
            if err is not None:
                errors += 1
                per[name] = {"error": str(err)}
            continue
        s = summarize(r["findings"])
        per[name] = {k: s[k] for k in ("findings", "unwaived", "worst")}
        total += s["findings"]
        unwaived += s["unwaived"]
        if severity_rank(s["worst"]) > severity_rank(worst):
            worst = s["worst"]
    return {
        "findings": total,
        "unwaived": unwaived,
        "worst": worst,
        "errors": errors,
        "per_strategy": per,
    }


def _flight_dump_facts(
    flight_dump: str | None,
) -> tuple[float | None, int | None]:
    """One parse of a dead child's flight.json -> ``(dumped_at_unix,
    last_resumable_step)`` — a single read so the staleness stamp and
    the step it vouches for can never come from two different dumps
    (the file is replaced by atomic rename between attempts).

    - the stamp is the retry driver's staleness check: a dump already
      billed for one death must not be billed again when a later
      attempt dies without managing a dump of its own;
    - the step is the highest CHECKPOINTABLE index recorded.  Only the
      checkpoint-hooked phase's dispatch records count (``timed_run``
      marks them ``resumable``): their indices share units with the
      durable checkpoint steps, while secondary phases re-count from 0
      in single-step units and the sentinel callbacks' per-process
      counter includes warmup — either would corrupt the arithmetic."""
    if not flight_dump:
        return None, None
    try:
        with open(flight_dump) as f:
            doc = json.load(f)
        steps = [
            r["step"] for r in doc.get("records", [])
            if r.get("kind") == "step" and r.get("resumable")
            and isinstance(r.get("step"), int)
        ]
        return doc.get("dumped_at_unix"), max(steps) if steps else None
    except (OSError, ValueError, KeyError):
        return None, None


def _flight_last_step(flight_dump: str | None) -> int | None:
    """See :func:`_flight_dump_facts` (the resumed child's
    steps-replayed annotation needs only the step half)."""
    return _flight_dump_facts(flight_dump)[1]


def run_with_retries(
    argv,
    attempts: int,
    child_timeout_s: float,
    compile_report: dict | None = None,
    ckpt_dir: str | None = None,
    flight_path: str | None = None,
    ledger_path: str | None = None,
) -> None:
    """The relaunch parent of a RESILIENT run (``--save-every`` /
    ``--resume-from`` / ``DDL25_CHAOS``): re-exec the bench in fresh
    subprocesses until one prints a JSON line without an ``error``
    field.  Fresh processes because a preempted child is gone, and a
    failed backend init is sticky in-process.  This parent never
    initialises a backend itself — the chip is the child's.  There is no
    backoff: a dead child is relaunched at once, and when the last
    attempt fails the parent prints that attempt's error line and exits
    non-zero.

    **Auto-resume** (``ckpt_dir``): when a failed attempt left a durable
    checkpoint behind (the ft/ autosave layer commits steps by atomic
    rename — a truncated save is invisible), the next attempt is
    relaunched with ``--resume-from <ckpt_dir>`` instead of restarting
    from scratch: the child restores params/opt-state/data-cursor/rng
    and continues from the step after the durable one.

    Every failed attempt emits one structured JSONL record to stderr
    (``{"record": "bench_retry_failure", attempt, error, reason,
    backoff_s, wall_s, rc}`` — ``reason`` is the coarse
    :func:`classify_failure` code, ``backoff_s`` always 0.0 (kept for
    the goodput merge's schema); ``flight_dump`` rides along when the
    child took a post-mortem dump, ``resumed_from_step`` when the
    attempt itself was a resume, and ``chaos`` when ``DDL25_CHAOS`` is
    armed) and the accumulated records ride the FINAL printed line's
    ``telemetry.retry_failures``.  ``telemetry.resume`` totals the
    recovery story: resume count and steps lost to replay (the gap
    between each death's last flight-recorded step and the durable
    checkpoint it restarted from).  ``compile_report`` (computed by a
    CPU-only child of the parent) rides ``telemetry.compile_report``
    on the same line, success or failure.

    **Run lineage** (graft-goodput, PR 20): the parent mints ONE
    ``lineage_id`` here and hands it to every attempt through the
    sanctioned env boundary (``DDL25_LINEAGE`` / ``DDL25_ATTEMPT``) —
    all attempts of one retry loop, resumed or fresh, are the same
    lineage, and each stamps it into its flight meta and timeline
    header.  Each failure record carries the lineage id plus the dead
    attempt's goodput facts priced off its flight dump (the next
    attempt overwrites the file, so failure time is the only chance);
    after the loop, :func:`ddl25spring_tpu.obs.goodput.merge_lineage`
    folds every attempt onto one wall axis, rewrites the run's
    ``goodput.json`` with the lineage view, appends the
    ``record:"goodput"`` ledger row, and rides ``telemetry.goodput``
    on the final line."""
    import subprocess
    import time

    from ddl25spring_tpu.ft.manifest import latest_durable_step
    from ddl25spring_tpu.obs import goodput as goodput_mod

    chaos_spec = os.environ.get("DDL25_CHAOS")
    lineage_id = goodput_mod.mint_lineage_id()
    run_dir = os.path.dirname(flight_path) if flight_path else None

    def _finish(record: dict) -> dict:
        """Fold the lineage goodput view into the final line (and the
        run dir's goodput.json / the ledger) — best-effort: goodput
        accounting must never cost the bench line itself."""
        try:
            final = (
                goodput_mod.read_run_goodput(run_dir) if run_dir else None
            )
            if isinstance(final, dict) and final.get("scope") != (
                "train_attempt"
            ):
                final = None  # stale serve/lineage doc, not this child's
            merged = goodput_mod.merge_lineage(
                final, failures, lineage_id=lineage_id
            )
            if merged is None:
                return record
            if run_dir:
                goodput_mod.write_run_goodput(merged, run_dir)
            tel = record.setdefault("telemetry", {"enabled": False})
            if isinstance(tel, dict):
                tel["goodput"] = goodput_mod.goodput_cell(merged)
            if final is not None and merged.get("strategy"):
                from ddl25spring_tpu.obs import logger as obs_logger

                obs_logger.append_ledger(
                    goodput_mod.ledger_row(
                        merged,
                        strategy=merged["strategy"],
                        mesh=merged.get("mesh"),
                        host=obs_logger.host_fingerprint(),
                    ),
                    ledger_path or obs_logger.DEFAULT_LEDGER,
                )
        except Exception as e:  # noqa: BLE001 — observability only
            print(f"lineage goodput merge failed: {type(e).__name__}: "
                  f"{e}", file=sys.stderr)
        return record

    last: dict = {}
    failures: list[dict] = []
    resume_step: int | None = None  # durable step the NEXT attempt resumes from
    resume_count = 0
    steps_lost = 0
    seen_dump_stamp: float | None = None
    for i in range(attempts):
        child_argv = list(argv)
        if resume_step is not None:
            child_argv += ["--resume-from", ckpt_dir]
            resume_count += 1
        env = dict(
            os.environ,
            DDL25_BENCH_CHILD="1",
            **{
                goodput_mod.ENV_LINEAGE: lineage_id,
                goodput_mod.ENV_ATTEMPT: str(i + 1),
            },
        )
        t0 = time.perf_counter()
        rc = None
        try:
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__), *child_argv],
                env=env, capture_output=True, text=True,
                timeout=child_timeout_s,
            )
        except subprocess.TimeoutExpired as e:
            # the run wedged: kill and relaunch — a hang must not take
            # the driver with it
            sys.stderr.write((e.stderr or b"").decode("utf-8", "replace")
                             if isinstance(e.stderr, bytes)
                             else (e.stderr or ""))
            err = (f"attempt {i + 1}: bench subprocess exceeded "
                   f"{child_timeout_s:.0f}s and was killed")
            last = error_record(err)
            parsed = None
        else:
            rc = r.returncode
            sys.stderr.write(r.stderr)
            # only dict lines are bench records; a stray printable (bare
            # number, quoted string) must not crash the driver
            from ddl25spring_tpu.obs.compile_report import last_json_dict_line

            parsed = last_json_dict_line(r.stdout)
            if parsed is not None and "error" not in parsed:
                resume = (
                    {"resumes": resume_count, "total_steps_lost": steps_lost}
                    if resume_count else None
                )
                print(json.dumps(_finish(attach_parent_telemetry(
                    parsed, failures, compile_report, resume=resume
                ))))
                return
            last = parsed or error_record(
                f"attempt {i + 1}: bench subprocess exited "
                f"rc={rc} with no JSON line"
                + (f" (killed by signal {-rc})"
                   if rc is not None and rc < 0 else "")
            )
        # structured JSONL failure record (replaces the old bare print):
        # machine-diagnosable on stderr now, and carried in the final
        # line's telemetry below
        err_s = str(last.get("error", "unknown"))
        reason = classify_failure(err_s, rc=rc)
        # a SIGTERM'd/SIGKILL'd child prints no JSON line, but its
        # crash handler (or last end_of_run) dumped into the obs dir —
        # the known flight_path covers the records-only death
        flight_dump = (
            last.get("flight_dump") if isinstance(last, dict) else None
        ) or (
            flight_path
            if flight_path and os.path.exists(flight_path) else None
        )
        prev_resume = resume_step
        # a durable checkpoint turns the next retry into a resume; the
        # replay cost is the gap between where the child died (its last
        # flight-recorded step) and where the next one restarts.  A dump
        # carrying the stamp of one we already billed is a STALE file (a
        # later attempt died before dumping) — don't bill it twice.
        resume_step = latest_durable_step(ckpt_dir) if ckpt_dir else None
        stamp, died_at = _flight_dump_facts(flight_dump)
        dump_fresh = stamp is None or stamp != seen_dump_stamp
        if stamp is not None and dump_fresh:
            seen_dump_stamp = stamp
        if resume_step is not None and dump_fresh and died_at is not None:
            steps_lost += max(0, died_at - resume_step)
        # price the dead attempt for the lineage goodput merge NOW —
        # the relaunched child truncates this exact file.  Same
        # staleness rule as steps_lost: a dump we already billed must
        # not vouch for a second death's useful work.
        attempt_goodput = None
        if flight_dump and dump_fresh:
            try:
                with open(flight_dump) as f:
                    attempt_goodput = goodput_mod.failed_attempt_facts(
                        json.load(f), resume_step
                    )
            except (OSError, ValueError):
                attempt_goodput = None
        rec = {
            "record": "bench_retry_failure",
            "lineage_id": lineage_id,
            "attempt": i + 1,
            "attempts_left": attempts - i - 1,
            "error": err_s,
            "reason": reason,
            "rc": rc,
            "wall_s": round(time.perf_counter() - t0, 3),
            "backoff_s": 0.0,  # relaunch is immediate
            **({"flight_dump": flight_dump} if flight_dump else {}),
            **({"goodput": attempt_goodput} if attempt_goodput else {}),
            **(
                {"resumed_from_step": prev_resume}
                if prev_resume is not None else {}
            ),
            **({"chaos": chaos_spec} if chaos_spec else {}),
        }
        failures.append(rec)
        print(json.dumps(rec), file=sys.stderr)
    last.setdefault("error", "unknown")
    last["error"] = f"exhausted {attempts} attempts; last: {last['error']}"
    resume = (
        {"resumes": resume_count, "total_steps_lost": steps_lost}
        if resume_count else None
    )
    print(json.dumps(_finish(attach_parent_telemetry(
        last, failures, compile_report, resume=resume
    ))), flush=True)
    sys.exit(1)


def fedavg_secondary(n_rounds: int = 10) -> dict:
    """Timed FedAvg round on the tutorial_1a workload — the second metric
    BASELINE.json names (reference wall-time segmentation:
    ``lab/tutorial_1a/hfl_complete.py:294,373``).  N=10 C=0.1 B=100 E=1
    lr=0.01 seed=10, the solved-homework golden config
    (``lab/series01.ipynb`` cell 20).  One warmup round compiles the
    vmapped client program; the timed window is ``n_rounds`` full server
    rounds (host-side client sampling + device-side local epochs +
    weighted aggregation), reported as ms/round.

    ``DDL25_BENCH_NTRAIN`` shrinks the MNIST split for CPU smoke runs
    (the single-core XLA CPU backend takes minutes on the full 60k; the
    TPU headline always uses the full split).  Any failure here must not
    cost the already-measured primary metric: the caller degrades this
    entry to an error note instead of letting the exception escape (and
    burn the retry wrapper's attempts)."""
    import time

    from ddl25spring_tpu.data.mnist import load_mnist
    from ddl25spring_tpu.fl import FedAvgServer

    n_train = int(os.environ.get("DDL25_BENCH_NTRAIN", "0")) or 60_000
    server = FedAvgServer(
        nr_clients=10, client_fraction=0.1, batch_size=100,
        nr_local_epochs=1, lr=0.01, seed=10,
        data=load_mnist(n_train=n_train),
    )
    server.round(0)  # compile
    jax.block_until_ready(jax.tree.leaves(server.params))
    t0 = time.perf_counter()
    for r in range(1, n_rounds + 1):
        server.round(r)
    jax.block_until_ready(jax.tree.leaves(server.params))
    ms = (time.perf_counter() - t0) / n_rounds * 1e3
    return {
        "metric": "fedavg_round_ms",
        "value": round(ms, 2),
        "unit": "ms/round",
        "n_train": n_train,
        "note": "tutorial_1a FedAvg N=10 C=0.1 B=100 E=1; one vmapped "
                "server round incl. host-side sampling",
    }


def main(argv=None) -> None:
    import time as _time

    # anchor for recovery_wall_s: how long a relaunched child takes from
    # process entry to "training again" — the checkpoint-relaunch side
    # of the elastic-vs-relaunch recovery A/B (the elastic side measures
    # its in-process reshape against the same clock kind)
    t_main0 = _time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend (local testing)")
    ap.add_argument("--force-cpu-devices", type=int, default=0, metavar="N",
                    help="simulate an N-device CPU mesh (implies --cpu)")
    ap.add_argument("--per-chip-batch", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--stages", type=int, default=0, metavar="S",
                    help="force the pipeline stage count (0 = auto: "
                         "2 stages when >= 2 chips, else pure DP; "
                         "--stages 1 forces pure DP on any chip count "
                         "— how the perf ledger gets multi-chip "
                         "bench-dp records)")
    ap.add_argument("--overlap", action="store_true",
                    help="backward-overlapped grad-bucket collectives "
                         "(parallel/dp.py overlap mode; implies pure "
                         "DP): the BENCH line and perf-ledger records "
                         "carry layout dp-overlap so before/after "
                         "measurements never mix")
    ap.add_argument("--scan-steps", type=int, default=0, metavar="K",
                    help="train steps fused per dispatch in the primary "
                         "mode (0 = auto: largest divisor of "
                         "batches_per_epoch <= 16)")
    ap.add_argument("--attempts", type=int, default=3,
                    help="resilient runs only (--save-every / "
                         "--resume-from / DDL25_CHAOS): how many fresh "
                         "child processes the relaunch parent may start")
    ap.add_argument("--child-timeout", type=float, default=2400.0,
                    help="resilient runs only: wall-clock bound per "
                         "child process")
    ap.add_argument("--no-fedavg", action="store_true",
                    help="skip the FedAvg round-time secondary metric")
    ap.add_argument("--obs-dir", default=None, metavar="DIR",
                    help="enable run telemetry (ddl25spring_tpu.obs) and "
                         "write metrics.jsonl / counters.json / trace.json "
                         "there; summarize with tools/obs_report.py")
    ap.add_argument("--save-every", type=int, default=0, metavar="N",
                    help="checkpoint the primary phase every N train "
                         "steps (ddl25spring_tpu.ft autosave: async, "
                         "sentinel-gated, atomic manifest); 0 disables. "
                         "Defaults to 2 when DDL25_CHAOS is armed")
    ap.add_argument("--ckpt-dir", default=None, metavar="DIR",
                    help="checkpoint directory (default: <obs-dir>/ckpt, "
                         "or runs/bench_ckpt)")
    ap.add_argument("--resume-from", default=None, metavar="CKPT_DIR",
                    help="restore params/opt-state/data-cursor/rng from "
                         "the latest durable checkpoint and continue the "
                         "primary phase from the next step (the retry "
                         "driver passes this automatically on relaunch)")
    ap.add_argument("--elastic", action="store_true",
                    help="survive device_loss / capacity_change chaos "
                         "IN-PROCESS by reshaping onto the surviving "
                         "mesh (ddl25spring_tpu.ft.elastic): live state "
                         "re-lands device-to-device, the step re-lowers "
                         "on the survivor mesh, the run continues from "
                         "the data cursor — no relaunch, no checkpoint "
                         "round-trip.  Implies pure DP at single-step "
                         "dispatch granularity; with --smoke a 2-device "
                         "CPU mesh so a loss is survivable.  A "
                         "capacity_change target that does not divide "
                         "the global batch is lowered to the largest "
                         "device count that does")
    ap.add_argument("--perf-ledger", default=None, metavar="JSONL",
                    help="append the run's mem / goodput / serve "
                         "trend rows here (default "
                         "runs/perf_ledger.jsonl)")
    ap.add_argument("--smoke", action="store_true",
                    help="CPU smoke run with telemetry: single-device DP, "
                         "tiny dataset/steps, no FedAvg; writes "
                         "--obs-dir (default runs/bench_smoke)")
    # --- serving mode (ddl25spring_tpu/serve): the inference bench -----
    ap.add_argument("--serve", action="store_true",
                    help="run the continuous-batching LLaMA serving bench "
                         "instead of the training bench: seeded open-loop "
                         "traffic through the paged-KV decode engine, "
                         "BENCH line with telemetry.serve (tokens/sec/"
                         "chip, TTFT + per-token p50/p95, admission "
                         "counters, pool occupancy) and a continuous-vs-"
                         "static A/B in the perf ledger; with --smoke: "
                         "tiny fp32 model, CPU, obs-dir runs/serve_smoke. "
                         "Engine knobs via DDL25_SERVE_* (see README)")
    ap.add_argument("--serve-duration", type=float, default=None,
                    metavar="S", help="traffic trace duration (seconds of "
                                      "arrival clock)")
    ap.add_argument("--serve-rate", type=float, default=None, metavar="RPS",
                    help="peak arrival rate (requests/sec)")
    ap.add_argument("--serve-profile", default=None,
                    choices=("flat", "ramp", "spike", "shared"),
                    help="arrival-rate shape (default ramp; 'shared' = "
                         "K seeded system prompts x Poisson arrivals — "
                         "the radix-prefix-cache workload)")
    ap.add_argument("--serve-seed", type=int, default=None,
                    help="traffic trace seed (two runs on the same seed "
                         "replay the identical workload)")
    ap.add_argument("--serve-budget", type=float, default=None, metavar="S",
                    help="wall-clock bound on the ramp phase (default: "
                         "run to drain)")
    ap.add_argument("--serve-model", default=None,
                    choices=("tiny", "tiny-deep", "ref"),
                    help="model to serve (default: tiny under --smoke, "
                         "else the reference LLaMA constants; tiny-deep "
                         "= 6-layer tiny, the speculative-decoding "
                         "smoke target whose 1-layer drafter is "
                         "genuinely cheap)")
    ap.add_argument("--no-serve-ab", action="store_true",
                    help="skip the continuous-vs-static A/B phase")
    ap.add_argument("--no-serve-prefix-ab", action="store_true",
                    help="skip the cached-vs-cold prefix-cache A/B "
                         "phase (it also never runs with "
                         "DDL25_SERVE_PREFIX=0)")
    ap.add_argument("--no-serve-spec-ab", action="store_true",
                    help="skip the speculative spec-on-vs-off A/B "
                         "phase (it also never runs without "
                         "DDL25_SERVE_SPEC=1)")
    ap.add_argument("--serve-tp", type=int, default=None, metavar="N",
                    help="TP-shard the serving engine N ways over a "
                         "1-D model mesh (KV head dim + Megatron "
                         "params divided per chip; overrides "
                         "DDL25_SERVE_TP).  N>1 also runs the "
                         "sharded-vs-dense A/B serve_report "
                         "--check-tp gates")
    ap.add_argument("--no-serve-tp-ab", action="store_true",
                    help="skip the tp-sharded-vs-dense A/B phase (it "
                         "also never runs at tp=1)")
    ap.add_argument("--compile-report", action="store_true",
                    help="force the pre-device compile report on CPU runs "
                         "(the accelerator path always computes it; see "
                         "ddl25spring_tpu/obs/compile_report.py)")
    ap.add_argument("--no-compile-report", action="store_true",
                    help="skip the compile report on the accelerator path")
    args = ap.parse_args(argv)

    # 0/negative would skip the retry loop entirely and print a
    # contract-violating `last={}` line with only an `error` key
    if args.attempts < 1:
        print(f"clamping --attempts {args.attempts} -> 1", file=sys.stderr)
        args.attempts = 1

    if args.serve and args.smoke:
        # the serving smoke gets its own obs dir so a bench smoke and a
        # serve smoke in one CI run never clobber each other's artifacts
        args.obs_dir = args.obs_dir or os.path.join("runs", "serve_smoke")
    if args.smoke:
        args.cpu = True
        args.no_fedavg = True
        args.per_chip_batch = min(args.per_chip_batch, 64)
        args.steps = min(args.steps, 8)
        args.warmup = min(args.warmup, 2)
        args.scan_steps = args.scan_steps or 1
        args.obs_dir = args.obs_dir or "runs/bench_smoke"
        os.environ.setdefault("DDL25_BENCH_NTRAIN", "512")
    if args.elastic:
        # the reshape boundary is a dispatch boundary: elastic runs at
        # single-step granularity (a K-fused scan dispatch would make
        # "the in-flight step" K steps wide) and in pure DP — the
        # layout whose re-lower the reshape path covers today
        if args.scan_steps not in (0, 1):
            print("--elastic forces --scan-steps 1 (reshape operates at "
                  "single-dispatch granularity)", file=sys.stderr)
        args.scan_steps = 1
        if args.smoke and not args.force_cpu_devices:
            # a 1-device smoke has nothing to lose; fake two CPU
            # devices so device_loss@k has a survivor to reshape onto
            args.force_cpu_devices = 2

    on_cpu = args.cpu or args.force_cpu_devices
    is_child = os.environ.get("DDL25_BENCH_CHILD") == "1"

    # fault-tolerance wiring (ddl25spring_tpu/ft): armed chaos implies
    # autosave (a kill with nothing durable proves nothing), and a
    # resilient run gets the relaunch parent — the relaunch IS the
    # recovery mechanism the chaos exists to exercise
    chaos_spec = os.environ.get("DDL25_CHAOS")
    if chaos_spec and not args.save_every and not args.serve:
        # serve mode has no checkpoint loop: its chaos kinds drive the
        # elastic replica reshaping inside the serve driver instead
        args.save_every = 2
    resilient = bool(args.save_every or args.resume_from)
    ckpt_dir = args.ckpt_dir or args.resume_from or (
        os.path.join(args.obs_dir, "ckpt") if args.obs_dir
        else os.path.join("runs", "bench_ckpt")
    )
    # fresh-start hygiene happens at the TOP of the run, never on a
    # retry: only the first process (parent, or the in-process CPU
    # path) wipes the stale checkpoint dir and the previous run's
    # flight.json.  A relaunched child must keep both — the chaos
    # one-shot journal lives in the ckpt dir (wiping it on a
    # nothing-durable-yet restart would re-fire the fault forever),
    # and a stale dump would corrupt the steps-lost accounting.
    if args.resume_from and args.ckpt_dir and (
        os.path.abspath(args.resume_from) != os.path.abspath(args.ckpt_dir)
    ):
        # silently saving into one dir while "resuming" from another
        # would restart from scratch behind the user's back
        print("--resume-from and --ckpt-dir point at different "
              "directories; pass one (the resume source is also where "
              "new checkpoints land)", file=sys.stderr)
        sys.exit(2)
    if resilient and not args.resume_from and not is_child and (
        os.path.isdir(ckpt_dir)
    ):
        import shutil

        # wipe ONLY something that is recognizably ours: the autosave
        # manifest, a chaos journal, or orbax step dirs.  A typo'd
        # --ckpt-dir pointing at user data must refuse, not recurse.
        ours = {"manifest.json", "chaos_fired.jsonl"}
        entries = os.listdir(ckpt_dir)
        if not entries or any(e in ours for e in entries) or all(
            os.path.isdir(os.path.join(ckpt_dir, e))
            and (e.isdigit() or ".orbax-checkpoint-tmp" in e)
            for e in entries
        ):
            shutil.rmtree(ckpt_dir)
        else:
            print(f"refusing to wipe {ckpt_dir}: it does not look like "
                  "a bench checkpoint dir (no manifest.json / chaos "
                  "journal / orbax step dirs); clear it yourself or "
                  "pass --resume-from to continue from it",
                  file=sys.stderr)
            sys.exit(2)
    if not is_child and not args.resume_from and args.obs_dir:
        stale_flight = os.path.join(args.obs_dir, "flight.json")
        if os.path.exists(stale_flight):
            os.remove(stale_flight)

    # compile-time analytics BEFORE any device contact: lowered on a fake
    # CPU mesh in a fresh CPU-only subprocess (it needs no chip and takes
    # none), so the report never touches this process's backend state.
    # Accelerator runs always; CPU runs opt in.
    compile_report = None
    # the child never recomputes: the parent did, once, and attaches it
    want_cr = not is_child and (
        args.compile_report or (not on_cpu and not args.no_compile_report)
    )
    if want_cr:
        from ddl25spring_tpu.obs.compile_report import (
            bench_compile_report_subprocess,
            write_compile_report,
        )

        compile_report = bench_compile_report_subprocess()
        if args.obs_dir:
            write_compile_report(args.obs_dir, compile_report)

    # the relaunch parent: resilient runs only.  Nothing above touched a
    # backend, so the chip is free for the children it starts
    if (resilient or chaos_spec) and not is_child:
        run_with_retries(
            argv if argv is not None else sys.argv[1:],
            args.attempts, args.child_timeout,
            compile_report=compile_report,
            ckpt_dir=ckpt_dir if resilient else None,
            flight_path=(
                os.path.join(args.obs_dir, "flight.json")
                if args.obs_dir else None
            ),
            ledger_path=args.perf_ledger,
        )
        return

    if args.force_cpu_devices:
        from ddl25spring_tpu.utils.platform import force_cpu_devices

        force_cpu_devices(args.force_cpu_devices)
    elif args.cpu:
        jax.config.update("jax_platforms", "cpu")
    else:
        # accelerator runs keep their compiles (ResNet-18 and the serve
        # programs are tens of seconds cold); CPU smokes compile cold — a
        # cache-deserialized CPU executable has flaked the collective
        # rendezvous (SKILL.md)
        from ddl25spring_tpu.utils.platform import enable_compilation_cache

        enable_compilation_cache()

    # arm the crash paths before any device contact: from here on an
    # unhandled exception, SIGTERM, or exit leaves a flight.json behind
    from ddl25spring_tpu.obs import flight

    flight.configure(run_dir=args.obs_dir)
    flight.install()
    flight.annotate(
        driver="bench",
        argv=list(argv if argv is not None else sys.argv[1:]),
    )

    # graft-goodput (PR 20): this process's place in its run lineage.
    # A retry child inherits the parent's id through the env boundary
    # (so a resumed attempt carries the SAME lineage_id); an in-process
    # run (plain CPU smoke, serve) is its own one-attempt lineage.
    from ddl25spring_tpu.obs import goodput as goodput_mod

    lineage_id, attempt = goodput_mod.lineage_from_env()
    own_lineage = lineage_id is None  # nobody upstream will merge for us
    if own_lineage:
        lineage_id = goodput_mod.mint_lineage_id()
    flight.annotate(lineage_id=lineage_id, attempt=attempt)
    lineage_meta = {"lineage_id": lineage_id, "attempt": attempt}
    gp_meter = goodput_mod.GoodputMeter(
        lineage_id, attempt, t0_perf=t_main0
    )

    try:
        devices = jax.devices()
    except Exception as e:  # noqa: BLE001 — print the line, then fail
        record = error_record(
            f"accelerator unreachable: {type(e).__name__}: {e}"
        )
        attach_parent_telemetry(record, None, compile_report)
        print(json.dumps(record), flush=True)
        sys.exit(1)

    # --- serving mode: traffic -> paged-KV engine -> telemetry.serve ---
    # (the training phases below never run; the serve driver owns the
    # ramp, the continuous-vs-static A/B, serve.json, and the ledger row)
    if args.serve:
        from ddl25spring_tpu import obs
        from ddl25spring_tpu.obs import sentinels as _sentinels
        from ddl25spring_tpu.obs.timeline import timeline
        from ddl25spring_tpu.serve.driver import run_serve_bench, serve_cell

        if args.obs_dir:
            # graft-trace (PR 16): enable BEFORE the engines build so
            # the serve spans + request timeline record (the flag is
            # read at emission time; everything here is host-side, so
            # the compiled serve programs are byte-identical either
            # way — pinned in tests/test_timeline.py)
            obs.enable()
            obs.set_recorder(obs.SpanRecorder(process_name="serve"))
            timeline.configure(run_dir=args.obs_dir, meta=lineage_meta)

        record = run_serve_bench(
            smoke=args.smoke,
            model=args.serve_model,
            obs_dir=args.obs_dir,
            duration_s=args.serve_duration,
            rate_rps=args.serve_rate,
            profile=args.serve_profile,
            seed=args.serve_seed,
            budget_s=args.serve_budget,
            ledger_path=args.perf_ledger or "runs/perf_ledger.jsonl",
            skip_ab=args.no_serve_ab,
            skip_prefix_ab=args.no_serve_prefix_ab,
            skip_spec_ab=args.no_serve_spec_ab,
            skip_tp_ab=args.no_serve_tp_ab,
            serve_tp=args.serve_tp,
            lineage=lineage_meta,
        )
        telemetry: dict = {
            "enabled": bool(args.obs_dir),
            "serve": serve_cell(record),
        }
        # graft-goodput: the SLO-denominated serving goodput cell the
        # driver computed (attainment, goodput tokens/sec/chip,
        # availability) — lineage identity rides along so serve lines
        # group like training lines in the ledger
        if record.get("goodput"):
            telemetry["goodput"] = {
                **lineage_meta, **goodput_mod.goodput_cell(
                    record["goodput"]
                ),
            }
        # graft-mem (PR 17): the runtime memory cell — measured
        # live-bytes high-water vs the engine's static bill, pool
        # telemetry, drain-time leak verdict (tools/mem_report.py)
        from ddl25spring_tpu.obs import memscope

        telemetry["mem"] = (
            memscope.mem_cell(record["mem"]) if record.get("mem")
            else {"enabled": False}
        )
        if record.get("mem_json"):
            telemetry["mem"]["mem_json"] = record["mem_json"]
        if compile_report is not None:
            telemetry["compile_report"] = compile_report
            telemetry["lint"] = lint_summary(compile_report)
        snap = flight.snapshot()
        health = {
            "sentinels": _sentinels.enabled(),
            "policy": _sentinels.policy(),
            "violations": snap["violations"],
            "stalls": snap["stalls"],
            "flight_records": snap["recorded"],
        }
        if args.obs_dir:
            health["flight_dump"] = flight.dump(reason="end_of_run")
            # the other two thirds of the merged trace: host spans
            # (trace.json) + the request timeline — what
            # tools/trace_export.py folds into one Perfetto view
            telemetry["trace"] = obs.get_recorder().save(
                os.path.join(args.obs_dir, "trace.json")
            )
            timeline.flush()
            telemetry["timeline"] = timeline.path
            telemetry["timeline_events"] = timeline.snapshot()["emitted"]
        telemetry["health"] = health
        ramp = record["ramp"]
        print(json.dumps({
            "metric": "serve_tokens_per_sec_per_chip",
            "value": ramp.get("tokens_per_sec_per_chip"),
            "unit": "tokens/sec/chip",
            # no committed serving baseline yet: the perf ledger trend
            # (tools/serve_report.py --check) is the regression gate
            "vs_baseline": None,
            "model": record["key"]["model"],
            "profile": record["key"]["profile"],
            "chip": f"{devices[0].device_kind} x{ramp.get('n_chips', 1)}",
            "telemetry": telemetry,
        }), flush=True)
        return

    import time

    from ddl25spring_tpu import obs
    from ddl25spring_tpu.benchmarks import (
        DeviceDataset,
        InputFeed,
        build_resnet_scan_step,
        build_resnet_step,
        report_line,
        timed_run,
    )
    from ddl25spring_tpu.utils.flops import chip_peak_flops, compiled_flops, mfu

    lg = None
    if args.obs_dir:
        # enable BEFORE building the step so the on-device counters are
        # traced in (the flag is read at trace time — obs/state.py)
        obs.enable()
        obs.set_recorder(obs.SpanRecorder(process_name="bench"))
        obs.counters.reset()
        # graft-goodput: the training run gets the unified timeline too
        # (serve always had one) — its header names the lineage, and
        # the flight tap mirrors save/restore/stall/chaos events in,
        # so one artifact correlates every attempt of a retry lineage
        from ddl25spring_tpu.obs.timeline import timeline

        timeline.configure(run_dir=args.obs_dir, meta=lineage_meta)

    n = len(devices)
    if args.stages:
        S = args.stages
        dp = max(n // S, 1)
    elif args.overlap or args.elastic:
        # overlap restructures the DP gradient path; elastic reshapes
        # it — both pin the pure-DP layout
        dp, S = n, 1
    else:
        dp, S = (n // 2, 2) if n >= 2 else (1, 1)
    # any pipelined layout takes the microbatch arg (S was only ever 1
    # or 2 before --stages existed; an S=3/4 run must not silently
    # degrade to the full-bubble M=1 schedule)
    M = args.microbatches if S >= 2 else 1
    batch = (args.per_chip_batch * dp * S) // (dp * M) * (dp * M)

    # DDL25_BENCH_NTRAIN: shrink the HBM dataset for CPU smoke runs of the
    # full bench flow (the TPU headline always uses the full 50k split)
    n_train = int(os.environ.get("DDL25_BENCH_NTRAIN", "0")) or None
    ds = DeviceDataset(batch, n_train=n_train)
    # scan fusion is TPU-only by default: lax.scan over a conv body is
    # pathologically slow on the XLA CPU backend (measured 55x — see
    # build_resnet_scan_step's docstring), so CPU smoke runs take K=1
    on_tpu = devices[0].platform == "tpu"
    K = args.scan_steps or (
        max(k for k in range(1, 17) if ds.batches_per_epoch % k == 0)
        if on_tpu else 1
    )
    with obs.span("build_step", scan_steps=K):
        if K > 1:
            multi, step, params, opt_state, meta = build_resnet_scan_step(
                devices, dp, S, M, batch, K, ds.n, overlap=args.overlap
            )
        else:
            multi = None
            step, params, opt_state, meta = build_resnet_step(
                devices, dp, S, M, batch, overlap=args.overlap
            )
    n_chips = meta["n_chips"]
    gp_meter.chips = n_chips  # windows before a reshape bill this width
    flight.annotate(
        layout=meta["layout"], topology=meta["topology"],
        n_chips=n_chips, batch=batch, scan_steps=K,
        rng_seed=ds.seed,  # the DeviceDataset epoch-shuffle key
    )

    # --- fault tolerance (ddl25spring_tpu/ft): restore + chaos + autosave --
    # the primary phase becomes resumable: periodic sentinel-gated async
    # checkpoints of the FULL resume state (params, opt state, data
    # cursor, rng seed), chaos faults armed from DDL25_CHAOS, and — when
    # the retry driver relaunched us with --resume-from — restoration of
    # the latest durable step instead of a restart from scratch.
    saver = None
    chaos = None
    chaos_exc: tuple = ()
    start_step = 0
    replayed = None
    recovery_wall_s = None
    # chaos kinds an elastic run CLAIMS at segment boundaries via
    # chaos.take (ft/elastic.py): on_step must not execute their
    # default raise-and-die action out from under the reshape path
    elastic_skip = (
        ("device_loss", "capacity_change") if args.elastic else ()
    )
    reshape_events: list = []
    if resilient or chaos_spec:
        from ddl25spring_tpu.ft import (
            AutoSaver,
            ChaosInjector,
            DeviceLossError,
            resume_bundle,
        )
        from ddl25spring_tpu.utils.checkpoint import with_mesh_placement

        if resilient:
            saver = AutoSaver(
                ckpt_dir, save_every=args.save_every,
                meta={"driver": "bench", "layout": meta["layout"]},
            )
        chaos = ChaosInjector.from_env(state_dir=ckpt_dir)
        chaos_exc = (DeviceLossError,)
        if chaos.pending("nan_grad"):
            print("chaos: nan_grad does not reach the bench's uint8 input "
                  "path; exercise it via ft/demo.py or the ft tests",
                  file=sys.stderr)
        if args.resume_from and saver is not None:
            # the template pins placement: restored leaves land exactly
            # where a fresh build put them (mesh-replicated here)
            init = with_mesh_placement(
                resume_bundle(params, opt_state,
                              data_cursor=ds.cursor, rng_seed=ds.seed),
                meta["mesh"],
            )
            state, start_step = saver.restore_or_init(init)
            # the relaunch path's recovery bill: process entry ->
            # restored and ready to train (imports, backend dial, and
            # the checkpoint read all inside); the elastic path's
            # reshape wall is the in-process counterpart
            recovery_wall_s = round(_time.perf_counter() - t_main0, 3)
            # goodput: everything from process entry to "restored" is
            # the relaunch path's recovery bill — one window on the
            # meter's axis (which is anchored at the same t_main0)
            gp_meter.add(
                "recovery", 0.0, gp_meter.now(), reason="relaunch_restore"
            )
            if start_step:
                params, opt_state = state["params"], state["opt_state"]
                ds.cursor = int(state["data_cursor"])
                # steps replayed = the gap between the dead attempt's
                # last flight-recorded step (its dump is still in the
                # obs dir — we haven't overwritten it yet) and our
                # restart point
                prev_last = _flight_last_step(
                    os.path.join(args.obs_dir, "flight.json")
                    if args.obs_dir else None
                )
                if prev_last is not None:
                    replayed = max(0, prev_last + 1 - start_step)
                    flight.annotate(steps_replayed=replayed)
                    # the durable-gap steps re-run now: timed_run bills
                    # their dispatch walls `replayed_steps`, not useful
                    gp_meter.set_replay_window(start_step, prev_last)

        def ft_on_step(i, p, o, lval):
            """timed_run's per-step hook: kill-type chaos first (a fault
            at step i fires BEFORE step i's state can become durable —
            maximum honest replay), then the gated autosave."""
            if chaos is not None:
                chaos.on_step(i, skip=elastic_skip)
            if saver is not None:
                # goodput: the save's host-blocking enqueue wall (the
                # async write itself overlaps training) — billed only
                # when the cadence gate actually fired
                t0_save = gp_meter.now()
                if saver.maybe_save(
                    i,
                    resume_bundle(p, o, data_cursor=ds.cursor,
                                  rng_seed=ds.seed),
                    loss=lval,
                ):
                    gp_meter.add(
                        "checkpoint_save", t0_save, gp_meter.now(), step=i
                    )
    else:
        ft_on_step = None

    # graft-mem (PR 17): the training-loop memory observatory — live
    # bytes + host RSS sampled once per step through the same on_step
    # hook the ft machinery rides, with the windowed monotone-growth
    # detector watching the host side (a growing Python-side resource
    # fires a flight ``kind="mem"`` violation).  All of it is host
    # observation: with DDL25_MEMSCOPE=0 (or obs off) the hook reduces
    # to the ft chain and the compiled step is untouched.
    from ddl25spring_tpu.obs import memscope

    mem_scope = memscope.MemScope(label="train")
    if memscope.enabled():
        _ft_chain = ft_on_step

        def ft_on_step(i, p, o, lval):  # noqa: F811 — deliberate wrap
            mem_scope.sample(i)
            if _ft_chain is not None:
                _ft_chain(i, p, o, lval)

    if args.obs_dir:
        lg = obs.MetricsLogger(
            args.obs_dir,
            meta=obs.run_metadata(
                mesh=meta["mesh"],
                layout=meta["layout"],
                topology=meta["topology"],
                n_chips=n_chips,
                batch=batch,
                num_stages=meta["num_stages"],
                num_microbatches=meta["num_microbatches"],
                scan_steps=K,
                input_mode=ds.input_mode,
            ),
        )

    # --- primary: HBM shuffle; K steps fused per dispatch on TPU -----------
    # A chaos-simulated device loss mid-phase degrades to the standard
    # error line (classified ``device_unreachable``) so the retry driver
    # relaunches — with --resume-from, since the autosave left a durable
    # step behind.  Chaos/checkpoint step indices count DISPATCHES on
    # the scan path (each dispatch = K fused steps); a resumed attempt
    # runs only the remaining steps (warmup still re-runs — compilation
    # is per-process — so the resumed data cursor drifts by the warmup
    # batches, which a throughput bench tolerates and the pinned
    # equivalence tests in tests/test_ft.py avoid by construction).
    # the budget anchor is the FIRST sampled step (memscope auto-
    # baselines): steady-state live bytes on the actual placement —
    # a post-build probe undercounts DP replication, which only
    # materializes on the first dispatch
    try:
        if multi is not None:
            def feed_scan():
                return (ds.x, ds.y) + ds.scan_window(K)

            def multi_packed(params, opt_state, packed):
                return multi(params, opt_state, *packed)

            # warmup MUST be >= 2 dispatches: the first call compiles,
            # and the SECOND recompiles once more (the first call's
            # outputs come back with TPU-chosen layouts that differ from
            # the freshly-initialized input arrays; the layout fix point
            # is reached after one round).  With a 1-dispatch warmup that
            # ~24 s recompile lands in the timed window and craters the
            # reported number ~25x (measured).
            resumed_past_end = start_step >= max(3, args.steps // K)
            n_disp = max(max(3, args.steps // K) - start_step, 1)
            dt, params, opt_state = timed_run(
                multi_packed, params, opt_state, feed_scan, n_disp,
                max(2, args.warmup // 2),
                logger=lg, label="hbm-scan", samples_per_step=batch,
                steps_per_call=K, on_step=ft_on_step,
                step_offset=start_step, goodput=gp_meter,
            )
            sps_chip = n_disp * K * batch / dt / n_chips
            dt_per_step = dt / (n_disp * K)

            # --- secondary 0: same input, one step per dispatch ------------
            # reset the stream counter: scan_window and feed interpret it
            # at different granularities (K-windows vs single batches), so
            # the single-dispatch run starts a fresh epoch instead of
            # interleaving
            ds._i = 0
            dt0, params, opt_state = timed_run(
                step, params, opt_state, ds.feed, args.steps, args.warmup,
                logger=lg, label="hbm-single", samples_per_step=batch,
                goodput=gp_meter,
            )
            sps_chip_single = args.steps * batch / dt0 / n_chips
        else:
            resumed_past_end = start_step >= args.steps
            steps_run = max(args.steps - start_step, 1)
            end_step = start_step + steps_run
            # the elastic plan: armed device_loss / capacity_change
            # faults inside this run's step window become SEGMENT
            # boundaries — each segment is an ordinary timed_run, and
            # between segments the taken fault is answered with an
            # in-process reshape instead of a death (ft/elastic.py).
            # Chaos fires post-step by contract, so the boundary split
            # is observationally identical to an in-loop fault: step k
            # completes, THEN the mesh changes.
            elastic_plan = sorted(
                (
                    f for f in (chaos.pending() if chaos else ())
                    if f.kind in elastic_skip
                    and start_step <= f.step < end_step
                ),
                key=lambda f: f.step,
            ) if args.elastic else []
            dt = 0.0
            chip_s = 0.0  # chip-seconds: each segment billed at ITS width
            seg_start = start_step
            mesh_now = meta["mesh"]
            for fault in [*elastic_plan, None]:
                seg_end = end_step if fault is None else fault.step + 1
                if seg_end > seg_start:
                    dt_i, params, opt_state = timed_run(
                        step, params, opt_state, ds.feed,
                        seg_end - seg_start,
                        # the continuation segment must not burn feed
                        # batches (and mutate params) on re-warmup; the
                        # rebuilt step compiles on its first timed
                        # dispatch — that compile IS part of the
                        # recovery story and stays in the measurement
                        args.warmup if seg_start == start_step else 0,
                        logger=lg, label="hbm-single",
                        samples_per_step=batch,
                        on_step=ft_on_step, step_offset=seg_start,
                        goodput=gp_meter,
                    )
                    dt += dt_i
                    chip_s += dt_i * n_chips
                    seg_start = seg_end
                if fault is None:
                    break
                if not chaos.take(fault.step, kinds=(fault.kind,)):
                    continue  # journaled in a previous life: one-shot
                from ddl25spring_tpu.ft import elastic

                t0r = time.perf_counter()
                g0r = gp_meter.now()
                # graft-mem: the survivor-mesh memory step — live bytes
                # before the reshard vs after the old-mesh state is
                # dropped rides the reshape record (mem_report gates
                # its presence on the elastic smoke)
                mem_before = (
                    memscope.live_total_bytes()
                    if memscope.enabled() else None
                )
                n_now = meta["n_chips"]
                target = (
                    fault.arg if fault.kind == "capacity_change"
                    and fault.arg else max(1, n_now // 2)
                )
                if target > len(devices):
                    # a capacity grant beyond the attached devices
                    # lowers to what exists — growing is best-effort,
                    # only shrinking is forced on us
                    print(f"elastic: capacity_change target {target} "
                          f"exceeds {len(devices)} attached device(s); "
                          "lowering", file=sys.stderr)
                    target = len(devices)
                while batch % target:  # keep the global batch exact
                    target -= 1
                new_devs = elastic.surviving_devices(
                    devices, size=target
                )
                step, p_t, o_t, meta = build_resnet_step(
                    new_devs, target, 1, 1, batch, overlap=args.overlap
                )
                state = elastic.reshape_state(
                    {"params": params, "opt_state": opt_state},
                    with_mesh_placement(
                        {"params": p_t, "opt_state": o_t}, meta["mesh"]
                    ),
                )
                params, opt_state = state["params"], state["opt_state"]
                # the freshly-initialized template state from the
                # rebuild is only a placement donor — holding it for
                # the rest of the run doubles the survivor mesh's
                # live bytes (found by the graft-mem step-down gate)
                del state, p_t, o_t
                wall = time.perf_counter() - t0r
                gp_meter.add(
                    "reshape_window", g0r, g0r + wall,
                    step=fault.step, reason=fault.kind,
                )
                # the faulted step completed and its loss synced before
                # the post-step fault fired — nothing was in flight, so
                # steps_lost is 0 by construction (vs the relaunch
                # path's died_at - durable gap)
                reshape_events.append(elastic.record_reshape(
                    old=mesh_now, new=meta["mesh"], wall_s=wall,
                    steps_lost=0, reason=fault.kind, step=fault.step,
                    **({
                        "live_bytes_before": mem_before,
                        "live_bytes_after": memscope.live_total_bytes(),
                    } if mem_before is not None else {}),
                ))
                if saver is not None:
                    saver.note_reshape(
                        old=reshape_events[-1]["old"],
                        new=reshape_events[-1]["new"],
                        step=fault.step,
                    )
                mesh_now = meta["mesh"]
                n_chips = meta["n_chips"]
                gp_meter.chips = n_chips  # later windows bill survivor width
                flight.annotate(
                    layout=meta["layout"], topology=meta["topology"],
                    n_chips=n_chips,
                )
            # per-chip throughput over chip-seconds: a mid-run reshape
            # means segments ran at DIFFERENT widths — dividing the
            # whole wall by the final width would overstate the number
            sps_chip = steps_run * batch / chip_s
            dt_per_step = dt / steps_run
            sps_chip_single = None
    except chaos_exc as e:
        if saver is not None:
            saver.close()  # the relaunch resumes from what we drained
        import contextlib

        dump = None
        with contextlib.suppress(Exception):  # the error line must print
            dump = flight.dump(reason="device_loss")
        record = error_record(
            str(e), **({"flight_dump": dump} if dump else {})
        )
        print(json.dumps(record), flush=True)
        sys.exit(1)

    # --- secondary 1: host streaming through the native C++ loader ---------
    # Constructed only now, and warmed past the prefetch queue's capacity
    # (depth + in-flight workers), so the timed window starts with an empty
    # queue and measures steady-state producer-bound throughput — a queue
    # pre-filled during the primary run would hand the timed loop several
    # batches for free and inflate the number.
    workers = max(2, (os.cpu_count() or 4) // 2)
    depth = 6
    feed = InputFeed(batch, stream=True, workers=workers, prefetch_depth=depth)
    stream_warm = args.warmup + depth + workers
    dt_s, params, opt_state = timed_run(
        step, params, opt_state, feed.feed, args.steps, stream_warm,
        logger=lg, label="stream", samples_per_step=batch,
        goodput=gp_meter,
    )
    sps_chip_stream = args.steps * batch / dt_s / n_chips

    # --- secondary 2: one fixed device-resident batch (compute bound) ------
    dt2, params, opt_state = timed_run(
        step, params, opt_state, feed.feed_fixed, args.steps, args.warmup,
        logger=lg, label="fixed-batch", samples_per_step=batch,
        goodput=gp_meter,
    )
    sps_chip_fixed = args.steps * batch / dt2 / n_chips

    # measure the host->device link so the streaming line explains itself
    import numpy as np

    # median of 3 transfers: one hiccup must not skew the
    # self-describing bandwidth number
    buf = np.zeros(4 * 1024 * 1024, np.uint8)
    jax.device_put(buf[:1024], devices[0]).block_until_ready()
    rates = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.device_put(buf, devices[0]).block_until_ready()
        rates.append(4.0 / (time.perf_counter() - t0))
    h2d_mib_s = sorted(rates)[1]

    # --- secondary 3: FedAvg round time (BASELINE.json's second metric) ----
    # guarded: a FedAvg-side failure must degrade to an error note, not
    # discard the already-measured primary metric (and trigger retries)
    if args.no_fedavg:
        fedavg_line = []
    else:
        try:
            fedavg_line = [fedavg_secondary()]
        except Exception as e:  # noqa: BLE001 — keep the primary metric
            fedavg_line = [{
                "metric": "fedavg_round_ms", "value": None,
                "unit": "ms/round",
                "note": f"failed: {type(e).__name__}: {e}",
            }]

    flops_step = compiled_flops(step, params, opt_state, feed.fixed)
    achieved_tf, frac = mfu(flops_step, dt_per_step, n_chips, meta["device"])
    peak = chip_peak_flops(meta["device"])

    telemetry = {"enabled": False}
    if compile_report is not None:
        telemetry["compile_report"] = compile_report
    if lg is not None:
        # supplementary header: facts only known after the timed phases
        # (summarize_run merges header records in order)
        lg.log(
            record="header",
            flops_per_step=flops_step,
            peak_flops_per_chip=peak,
            h2d_mib_per_s=h2d_mib_s,
        )
        lg.close()
        obs.counters.save(args.obs_dir)
        obs.get_recorder().save(os.path.join(args.obs_dir, "trace.json"))
        from ddl25spring_tpu.obs.report import summarize_run

        s = summarize_run(args.obs_dir)
        telemetry = {
            "enabled": True,
            **(
                {"compile_report": compile_report}
                if compile_report is not None else {}
            ),
            "run_dir": args.obs_dir,
            "bubble_fraction": s.get("bubble_fraction"),
            "tick_interval_s_p50": s.get("tick_interval_s_p50"),
            "phases": {
                name: {
                    k: ph.get(k)
                    for k in (
                        "steps",
                        "step_s_p50",
                        "step_s_p95",
                        "samples_per_sec_per_chip_p50",
                        "mfu",
                    )
                    if ph.get(k) is not None
                }
                for name, ph in s.get("phases", {}).items()
            },
        }

    # the runtime-memory cell + artifacts (graft-mem, PR 17): mem.json
    # in the run dir for obs_report's Memory section, a record:"mem"
    # ledger row for tools/mem_report.py --check, and the reshape
    # memory step-downs for the elastic gate
    telemetry["mem"] = {"enabled": False}
    if memscope.enabled():
        try:
            mesh_axes = {
                str(ax): int(s) for ax, s in zip(
                    meta["mesh"].axis_names, meta["mesh"].devices.shape
                )
            }
        except Exception:  # noqa: BLE001 — identity only
            mesh_axes = {}
        mem_steps = [
            {
                "scope": "train",
                "reason": ev.get("reason"),
                "step": ev.get("step"),
                "live_bytes_before": ev["live_bytes_before"],
                "live_bytes_after": ev["live_bytes_after"],
                "step_down_bytes": (
                    ev["live_bytes_before"] - ev["live_bytes_after"]
                ),
            }
            for ev in reshape_events
            if ev.get("live_bytes_before") is not None
        ]
        mem_record = memscope.mem_record(
            strategy=meta["layout"],
            mesh=mesh_axes,
            scope_cell=mem_scope.cell(),
            budget=memscope.budget_cell(
                mem_scope.live_bytes_peak,
                mem_scope.live_bytes_baseline,
                source="first_sample_live_bytes",
            ),
            reshape_steps=mem_steps or None,
        )
        telemetry["mem"] = memscope.mem_cell(mem_record)
        try:
            from ddl25spring_tpu.obs import logger as obs_logger

            telemetry["mem"]["ledger"] = obs_logger.append_ledger(
                mem_record, args.perf_ledger or obs_logger.DEFAULT_LEDGER
            )
            if args.obs_dir:
                telemetry["mem"]["mem_json"] = memscope.write_run_mem(
                    mem_record, args.obs_dir
                )
        except OSError as e:  # a read-only FS must not kill the line
            telemetry["mem"]["ledger_error"] = str(e)

    # drain the last async checkpoint and finalize the manifest BEFORE
    # the end-of-run flight dump, so the dump's meta names the final
    # durable step (close is idempotent — the shutdown chain would have
    # run it anyway on a crash)
    if saver is not None:
        saver.close()
        telemetry["resume"] = {
            "start_step": start_step,
            **({"resumed_from_step": start_step - 1} if start_step else {}),
            **({"steps_replayed": replayed} if replayed is not None else {}),
            # honesty flag: the run was already done when it resumed —
            # the floor re-ran a minimal window just to print a metric
            **({"resumed_past_end": True} if resumed_past_end else {}),
            "save_every": args.save_every,
            "ckpt_dir": ckpt_dir,
            "saves": saver.saves,
            "saves_skipped": saver.skipped,
            # the elastic-vs-relaunch A/B facts (ft/elastic.py): the
            # in-process reshape count + walls on the elastic side, the
            # entry->restored wall on the relaunch side — steps lost
            # ride total_steps_lost either way (0 for a reshape, the
            # died_at - durable gap for a relaunch, merged by the retry
            # parent)
            **({
                "reshapes": len(reshape_events),
                "reshape": reshape_events,
                "reshape_wall_s": round(
                    sum(e["wall_s"] for e in reshape_events), 3
                ),
                "recovery_wall_s": round(
                    sum(e["wall_s"] for e in reshape_events), 3
                ),
                "total_steps_lost": sum(
                    e["steps_lost"] for e in reshape_events
                ),
            } if reshape_events else {}),
            **({
                "recovery_wall_s": recovery_wall_s,
            } if recovery_wall_s is not None and not reshape_events
              else {}),
        }

    # runtime-health cell: sentinel state + flight-recorder facts, and a
    # flight.json in the run dir so obs_report's Health section (and any
    # post-mortem) reads the same artifact a crash would have left
    from ddl25spring_tpu.obs import sentinels as _sentinels

    _snap = obs.flight.snapshot()
    health = {
        "sentinels": _sentinels.enabled(),
        "policy": _sentinels.policy(),
        # cumulative counter, not a ring recount: a violation hundreds
        # of steps back must still show after the ring evicted it
        "violations": _snap["violations"],
        "stalls": _snap["stalls"],
        "flight_records": _snap["recorded"],
    }
    if args.obs_dir:
        health["flight_dump"] = obs.flight.dump(reason="end_of_run")
    telemetry["health"] = health

    # graft-goodput (PR 20): close this attempt's badput decomposition.
    # Watchdog stall idle rides as seconds-only (its span overlaps the
    # step that eventually completed); everything never measured
    # (imports, FedAvg, the h2d probe) is the honest
    # ``other`` residual.  A retry child's doc is the attempt view the
    # parent merges into the lineage view; an in-process run (plain CPU
    # smoke) is its own one-attempt lineage and appends its own ledger
    # row.
    for _r in obs.flight.last():
        if _r.get("kind") == "stall" and isinstance(
            _r.get("idle_s"), (int, float)
        ):
            gp_meter.add_seconds("stall", _r["idle_s"])
    try:
        gp_mesh = {
            str(ax): int(s) for ax, s in zip(
                meta["mesh"].axis_names, meta["mesh"].devices.shape
            )
        }
    except Exception:  # noqa: BLE001 — identity only
        gp_mesh = {}
    attempt_goodput = gp_meter.finalize(
        scope="train_attempt", strategy=meta["layout"], mesh=gp_mesh,
    )
    telemetry["goodput"] = goodput_mod.goodput_cell(attempt_goodput)
    if args.obs_dir:
        goodput_mod.write_run_goodput(attempt_goodput, args.obs_dir)
    if own_lineage:
        try:
            from ddl25spring_tpu.obs import logger as obs_logger

            telemetry["goodput"]["ledger"] = obs_logger.append_ledger(
                goodput_mod.ledger_row(
                    attempt_goodput, strategy=meta["layout"],
                    mesh=gp_mesh, host=obs_logger.host_fingerprint(),
                ),
                args.perf_ledger or obs_logger.DEFAULT_LEDGER,
            )
        except OSError as e:  # a read-only FS must not kill the line
            telemetry["goodput"]["ledger_error"] = str(e)

    primary_mode = (
        f"{ds.input_mode}-scan{K}" if multi is not None else ds.input_mode
    )
    single_line = [
        {
            "input": ds.input_mode,
            "value": round(sps_chip_single, 1),
            "unit": "samples/sec/chip",
            "note": "one step per dispatch; the delta vs the primary "
                    "is the measured per-dispatch host overhead",
        },
    ] if sps_chip_single is not None else []
    print(report_line(
        meta["layout"], sps_chip, primary_mode, frac, achieved_tf,
        data=ds.provenance,
        topology=meta["topology"],
        dtype=meta["dtype"],
        chip=f"{meta['device'].device_kind} x{n_chips}",
        flops_per_step=flops_step,
        scan_steps=K,
        peak_tflops_per_chip=peak / 1e12 if peak else None,
        h2d_mib_per_s=round(h2d_mib_s, 1),
        # the effective grad-bucket threshold (DDL25_BUCKET_BYTES-aware)
        # so sweep results compare like-for-like across runs
        bucket_bytes=meta.get("bucket_bytes"),
        telemetry=telemetry,
        secondary=single_line + [
            {
                "input": feed.input_mode,
                "value": round(sps_chip_stream, 1),
                "unit": "samples/sec/chip",
                # only claim link-bound streaming when the native loader
                # actually streamed; on NativeLoaderUnavailable this run
                # degraded to the fixed batch and says so via input_mode
                **({"note": "bounded by the host->device link "
                            f"(~{h2d_mib_s:.0f} MiB/s measured here)"}
                   if feed.streaming else
                   {"note": "native loader unavailable; fell back to the "
                            "fixed device-resident batch"}),
            },
            {
                "input": "fixed-device-batch",
                "value": round(sps_chip_fixed, 1),
                "unit": "samples/sec/chip",
            },
        ] + fedavg_line,
    ))

    feed.close()


if __name__ == "__main__":
    main()
