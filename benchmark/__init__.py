"""The repo's benchmark: one cell per process, driven by data files.

``BENCHMARK.json`` at the root of the repo is the manifest; ``run.py`` is
the one command.  See ``PERF.md`` for what each cell and metric is for.
"""
