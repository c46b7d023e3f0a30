"""Shared set-up of the benchmark's tests: the repository root on the
import path (the benchmark is the ``bench`` package there), and cells at a
size a test run can hold, run on the CPU without the harness's look for a
chip."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

#: every cell shrunk alike: 5,000 buckets, 1,024-key batches, 4 in the pool
TINY = {"config": {"n_buckets": 5000},
        "traffic": {"batch_keys": 1024, "pool_batches": 4, "check_keys": 0,
                    "trace_seconds": 0.3}}


def run_tiny(name: str, seed: int = 2**31 + 7, *, seconds: float = 0.3,
             trace: bool = False, plant=None, root: Path = ROOT,
             n_buckets: int | None = None, batch_keys: int | None = None) -> dict:
    """One run of cell ``name`` at the tiny size on the CPU devices, or at
    ``n_buckets``/``batch_keys`` where given."""
    import jax

    from bench.harness import load_cell, run_cell

    size = {k: dict(v) for k, v in TINY.items()}
    if n_buckets:
        size["config"]["n_buckets"] = n_buckets
    if batch_keys:
        size["traffic"]["batch_keys"] = batch_keys
    cell = load_cell(name, root=root, overrides=size)
    return run_cell(cell, seed, seconds, trace, jax.devices()[:cell.chips],
                    time.perf_counter(), plant=plant)


@pytest.fixture
def tiny():
    return run_tiny
