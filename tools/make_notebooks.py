#!/usr/bin/env python
"""Generate the notebook-form homework deliverables.

The reference ships its solved homework as notebooks
(``lab/series01.ipynb``, 46 cells; blank assignment
``lab/homework-1.ipynb``) while this framework ships the same experiments
as executable scripts (``examples/``).  This tool closes the FORM gap: it
emits ``lab/series01_tpu.ipynb`` — markdown narration + code cells that
call the example entry points — mirroring the reference notebook's
A1/A2/A3/B1/B2 section structure.  Cells are committed UNEXECUTED, the
same convention the reference enforces with its metadata scrubber
(``lab/clear-metadata-notebooks.py``); run them top to bottom (or the
scripts directly) to reproduce RESULTS.md §1-§2.

Regenerate: ``python tools/make_notebooks.py``.
"""

from pathlib import Path

import nbformat as nbf

ROOT = Path(__file__).resolve().parent.parent


def md(text: str):
    return nbf.v4.new_markdown_cell(text.strip())


def code(src: str):
    c = nbf.v4.new_code_cell(src.strip())
    return c


CELLS = [
    md("""
# Series 01 — solved homework (TPU framework)

The reference's solved notebook (`lab/series01.ipynb`) runs homework 1 on
`torch` FL servers; this notebook runs the SAME experiments on the
vmapped TPU servers (`ddl25spring_tpu.fl`).  Every section names the
reference cells it mirrors.  Seeds follow the homework mandate
(`seed=10`).

On a zero-egress image the MNIST loader falls back to a deterministic
synthetic set and the golden accuracies shift; set `DDL25_MNIST_DIR` to a
directory holding the four raw IDX files to reproduce the notebook's
golden table (93.2% FedAvg at N=10, C=0.1 — cell 20).
"""),
    code("""
import sys, pathlib
sys.path.insert(0, str(pathlib.Path.cwd().parent))  # repo root
# Simulate an 8-device CPU mesh (reference analogue: gloo-on-localhost).
# Must run BEFORE the first jax backend init — so do NOT query
# jax.devices()/default_backend() first; on a real TPU VM comment this
# out instead.
from ddl25spring_tpu.utils.platform import force_cpu_devices
force_cpu_devices(8)
"""),
    md("""
## A1 — FedSGD-with-gradients ≡ FedSGD-with-weights
(reference cells 9-12; tolerance 0.02% per round)

One full-batch SGD step + weighted weight averaging is linear in the
gradients, so the two transports must produce identical rounds.
"""),
    code("""
from examples.homework1_a1_equivalence import main as a1
a1(["--rounds", "5", "--n-train", "4096"])
"""),
    md("""
## A2 — client count N and participation fraction C
(reference cells 13-24; golden table in cell 20)
"""),
    code("""
from examples.homework1_a2_a3_sweeps import main as sweeps
sweeps(["--rounds", "5", "--quick", "--only", "a2"])
"""),
    md("""
## A3 — local epochs E and IID vs non-IID splits
(reference cells 25-38)
"""),
    code("""
sweeps(["--rounds", "5", "--quick", "--only", "a3"])
"""),
    md("""
## Golden-table runner

Prints the framework's accuracies side-by-side with the reference's
golden values (and says which dataset actually ran).
"""),
    code("""
from examples.golden_tables import main as golden
golden(["--rounds", "5", "--quick"])
"""),
    md("""
## B1/B2 — microbatch pipeline and DP×PP

The pipeline halves of the homework are driver scripts (they manage
meshes and long-running training):

```
./lab/run-b1.sh        # B1: 3-stage microbatch pipeline (LLaMA)
./lab/run-b2.sh        # B2: DP x PP (+ the ResNet benchmark config)
```

Schedules: `--schedule {gpipe,1f1b,1f1b-stash,interleaved,interleaved-1f1b}`.
Equivalence with the serial model is pinned in `tests/test_pipeline.py`;
measured schedule memory tables live in `RESULTS.md` §4.
"""),
]


def main():
    nb = nbf.v4.new_notebook()
    nb.cells = CELLS
    nb.metadata["kernelspec"] = {
        "display_name": "Python 3", "language": "python", "name": "python3",
    }
    out = ROOT / "lab" / "series01_tpu.ipynb"
    nbf.write(nb, out)
    print(f"wrote {out} ({len(CELLS)} cells)")


if __name__ == "__main__":
    main()
