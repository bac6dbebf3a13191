"""Static hazard analysis: compile-time judgment over every strategy.

PR 2/3 gave the framework compile-time *accounting* — collective
inventories, HBM footprints, donation savings — all measured off the
optimized HLO on CPU.  This package adds compile-time *judgment*: a
rule engine (:mod:`.engine`) that runs a hazard pack (:mod:`.rules`,
H001-H013) over those same structured facts for every registered
parallel strategy — the collective hazards (H001-H007), the schedule
verifier graft-sched (:mod:`.sched`, H008-H009), and the sharding-flow
verifier graft-shard (:mod:`.shard_flow`, H011-H013: implicit
reshards, partition-rule coverage proofs, cross-program layout
contracts) — plus an AST linter (:mod:`.source_lint`, S101-S103)
for the Python idioms that cause them, with a shared waiver workflow
(:mod:`.waivers`, ``analysis/waivers.toml``).  Drive it via
``python -m tools.graft_lint --strategy all --shard-flow --check`` —
the CI gate — or read findings straight off any strategy's compile
report (``report["findings"]``).
"""

from ddl25spring_tpu.analysis.rules import (  # noqa: F401
    Finding,
    severity_rank,
    worst_severity,
)
