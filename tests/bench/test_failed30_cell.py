"""The cell ``memento-1m-failed30.bulk`` at a small size on the CPU: 30% of
20,000 buckets removed, 2,048-key batches.  Its answers match the
reference, a traced run reports the Memento loop's outer and longest-key
counts, and one altered answer or the control makes the run incorrect."""
from __future__ import annotations

from conftest import run_tiny
from test_bench_faults import _plant

CELL = "memento-1m-failed30.bulk"
SIZE = {"n_buckets": 20_000, "batch_keys": 2048}


def test_untraced_run_is_correct():
    r = run_tiny(CELL, **SIZE)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert {"keys_per_s", "batch_p95_ms", "setup_s"} <= set(r["metrics"])


def test_traced_run_reports_the_loop_counts():
    r = run_tiny(CELL, trace=True, **SIZE)
    assert r["correct"] is True
    m = {k: v["value"] for k, v in r["metrics"].items()}
    # 30% removed: a few outer iterations, each waiting for the slowest chain
    assert 1 <= m["engine.outer_sweeps"] < m["engine.longest_key_sweeps"] < m["engine.sweeps"]
    assert all(r["metrics"][k]["unit"] == "sweeps"
               for k in ("engine.outer_sweeps", "engine.longest_key_sweeps"))


def test_altered_answer_makes_the_run_incorrect(monkeypatch):
    _plant(monkeypatch, "altered_answer")
    r = run_tiny(CELL, **SIZE)
    assert r["correct"] is False and r["failed"] > 0
    assert r["checks"]["wrong_keys"]["value"] > 0


def test_control_makes_the_run_incorrect():
    """The control's errors are rare in small fleets: it is run at a size
    where a batch shows them."""
    from bench.control import reciprocal_jump_step

    r = run_tiny(CELL, plant=reciprocal_jump_step(), n_buckets=100_000,
                 batch_keys=4096)
    assert r["correct"] is False and r["checks"]["wrong_keys"]["value"] > 0
