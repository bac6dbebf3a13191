"""The reader of ``tick_ahead_pct.serve`` on hand-made rings whose answer
is known, silent where the program samples no such ring, and its metric
file found by name in every serving cell."""

import importlib
import os

import pytest

from benchmark import run

counters_mod = importlib.import_module("ddl25spring_tpu.obs.counters")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
SERVING = ["olmo1b-serve-closed32", "mistral4-serve-decode64",
           "qwen3next-serve-decode128", "zaya1-serve-decode64"]


def read(record):
    return run.load_module(BENCH, "readers", "tick_ahead_pct").read(record, {})


@pytest.fixture
def rings(monkeypatch):
    fresh = counters_mod.CounterSet()
    monkeypatch.setattr(counters_mod, "counters", fresh)
    return fresh


def record():
    return {"t_open_host": 100.0, "t_close_host": 200.0}


def test_no_ring_reads_nothing(rings):
    """A program from before the ring, or a window without a tick."""
    assert read(record()) is None
    rings.sample("serve.tick_ahead", 1, t=90.0)  # before the window only
    assert read(record()) is None


def test_the_share_of_the_windows_ticks_that_went_ahead(rings):
    # two ticks outside the window, eight in it, of which six went ahead
    for t, v in [(90, 0), (110, 0), (120, 1), (130, 1), (140, 0),
                 (150, 1), (160, 1), (170, 1), (180, 1), (210, 0)]:
        rings.sample("serve.tick_ahead", v, t=float(t))
    assert read(record()) == pytest.approx(75.0)


def test_the_metric_is_read_in_every_serving_cell_and_no_training_one():
    for cell in SERVING + ["olmo1b-train-2k", "olmo1b-dppp-2k"]:
        runner = run.load_cell(BENCH, cell)[0]["runner"]
        names = {s["name"] for s in run.metric_specs(BENCH, runner, cell, "per_layer")}
        assert ("tick_ahead_pct.serve" in names) == (cell in SERVING), cell
