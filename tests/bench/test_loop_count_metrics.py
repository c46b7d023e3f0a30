"""``engine.outer_sweeps`` and ``engine.longest_key_sweeps``: each reader
averages its device count (``engine.memento.outer_sweeps``,
``engine.memento.longest_lane``) over the window's batches and finds
nothing where the program does not count it, and a traced run of each cell
that lists the metrics reports both beside ``engine.sweeps``."""
from __future__ import annotations

import json
from types import SimpleNamespace

import pytest

from bench.harness import Context, metric_reader
from conftest import ROOT, run_tiny

#: metric → the device histogram its reader averages
COUNTS = {"engine.outer_sweeps": "engine.memento.outer_sweeps",
          "engine.longest_key_sweeps": "engine.memento.longest_lane"}
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = next(m["workloads"] for m in SPEC["per_layer"]
             if m["name"] == "engine.longest_key_sweeps")


def _read(name, obs):
    cell = SimpleNamespace(traffic={"batch_keys": 1024}, root=ROOT)
    ctx = Context(cell, "TPU v5 lite", 1, obs, None)
    return metric_reader(cell, name)(ctx)


@pytest.mark.parametrize("name", COUNTS)
def test_reader_averages_the_counts_over_batches(name):
    assert _read(name, {COUNTS[name]: (4, 38.0)}) == pytest.approx(9.5)


@pytest.mark.parametrize("name", COUNTS)
def test_reader_finds_nothing_where_the_program_does_not_count(name):
    assert _read(name, {"engine.memento.sweeps": (4, 100.0)}) is None


def test_both_metrics_list_the_same_cells():
    lists = [m["workloads"] for m in SPEC["per_layer"] if m["name"] in COUNTS]
    assert len(lists) == 2 and lists[0] == lists[1]


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reports_the_loop_counts(name):
    r = run_tiny(name, trace=True)
    assert r["correct"] is True
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert all(r["metrics"][k]["unit"] == "sweeps" for k in COUNTS)
    # the outer loop's iterations never outnumber the slowest key's steps,
    # nor those the batch's sweeps
    assert 0 <= m["engine.outer_sweeps"] <= m["engine.longest_key_sweeps"] <= m["engine.sweeps"]
    if "failed" in name:
        assert 1 <= m["engine.longest_key_sweeps"] < m["engine.sweeps"]
