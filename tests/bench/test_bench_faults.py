"""The comparison catches what it is there to catch.

Each cell is run at a tiny size on the CPU, skipping only the harness's
look for a chip, with the timed path broken underneath, once for each
fault the cell can have; ``correct`` must come out false.  The control
(``bench/control.py``: Jump's step rounded by the hardware) must fail
every cell too.  The four-chip cell runs in a child process with four
virtual CPU devices."""
from __future__ import annotations

import functools
import json
import os
import shutil
import subprocess
import sys
import tempfile
import textwrap
from pathlib import Path

import numpy as np
import pytest

from conftest import ROOT, run_tiny


def _half_batch(orig):
    """Half of the batch left out: the second half never looked up."""
    def lookup(keys, image, **kw):
        out = np.asarray(orig(keys, image, **kw)).copy()
        out[out.size // 2:] = 0
        return out
    return lookup


def _altered_answer(orig):
    """One answer altered where it is produced."""
    def lookup(keys, image, **kw):
        out = np.asarray(orig(keys, image, **kw)).copy()
        out[out.size // 3] = (out[out.size // 3] + 1) % int(image.n)
        return out
    return lookup


def _plant(monkeypatch, fault):
    from repro.core.image_store import DeviceImageStore
    from repro.kernels import engine

    if fault == "state_unchanged":
        monkeypatch.setattr(DeviceImageStore, "sync", lambda self: self.last_sync)
    else:
        wrap = {"half_batch": _half_batch, "altered_answer": _altered_answer}[fault]
        monkeypatch.setattr(engine, "engine_lookup", wrap(engine.engine_lookup))


ONE_CHIP = [("memento-1m.bulk", "half_batch"), ("memento-1m.bulk", "altered_answer"),
            ("memento-1m-failed90.bulk", "half_batch"),
            ("memento-1m-failed90.bulk", "altered_answer"),
            ("memento-1m.churn", "half_batch"), ("memento-1m.churn", "altered_answer"),
            ("memento-1m.churn", "state_unchanged")]


@pytest.mark.parametrize("name,fault", ONE_CHIP)
def test_fault_makes_the_run_incorrect(monkeypatch, name, fault):
    _plant(monkeypatch, fault)
    r = run_tiny(name)
    assert r["correct"] is False
    assert r["failed"] > 0 or r["checks"]["disrupted_keys"]["value"] > 0


#: the control's errors are a few keys in a thousand at 10^6 buckets, and
#: rarer in smaller fleets: it is run at a size where a batch shows them
CONTROL_SIZE = {"memento-1m.bulk": (10**6, 8192),
                "memento-1m-failed90.bulk": (100_000, 4096),
                "memento-1m.churn": (10**6, 8192)}


@pytest.mark.parametrize("name", sorted(CONTROL_SIZE))
def test_control_makes_the_run_incorrect(name):
    from bench.control import reciprocal_jump_step

    n, keys = CONTROL_SIZE[name]
    r = run_tiny(name, plant=reciprocal_jump_step(), n_buckets=n, batch_keys=keys)
    assert r["correct"] is False and r["checks"]["wrong_keys"]["value"] > 0


@pytest.mark.parametrize("name", ["memento-1m.bulk", "memento-1m.churn"])
def test_unbroken_run_after_the_control_is_correct(name):
    """The control's plant is lifted afterwards: no stale program is left
    in jax's caches."""
    from bench.control import reciprocal_jump_step

    run_tiny(name, plant=reciprocal_jump_step())
    assert run_tiny(name)["correct"] is True


_FOUR_CHIPS = textwrap.dedent("""
    import json, sys
    from pathlib import Path
    import numpy as np
    sys.path[:0] = [{root!r}, {root!r} + "/tests/bench"]
    from conftest import run_tiny as _run_tiny
    spec_root = Path({spec_root!r})

    def run_tiny(*a, **kw):
        return _run_tiny(*a, root=spec_root, **kw)
    from bench.control import reciprocal_jump_step
    from repro.core.image_store import DeviceImageStore
    from repro.serve.plane import ShardedLookupPlane

    finish = ShardedLookupPlane._finish
    sync = DeviceImageStore.sync

    def half_batch(self, out, n):
        res = finish(self, out, n).copy()
        res[n // 2:] = 0
        return res

    def altered_answer(self, out, n):
        res = finish(self, out, n).copy()
        res[n // 3] = (res[n // 3] + 1) % int(self._image.n)
        return res

    def no_exchange(self, out, n):
        shard = out.addressable_shards[0]
        full = np.zeros(out.shape, np.int32)
        full[shard.index] = np.asarray(shard.data)
        return full[:n]

    faults = {{"half_batch": ("_finish", half_batch),
              "altered_answer": ("_finish", altered_answer),
              "no_exchange": ("_finish", no_exchange),
              "state_unchanged": ("sync", lambda self: self.last_sync)}}
    out = {{"clean": run_tiny("memento-1m-x4.stream")["correct"]}}
    for name, (attr, fn) in faults.items():
        owner = DeviceImageStore if attr == "sync" else ShardedLookupPlane
        setattr(owner, attr, fn)
        try:
            out[name] = run_tiny("memento-1m-x4.stream")["correct"]
        finally:
            ShardedLookupPlane._finish, DeviceImageStore.sync = finish, sync
    out["control"] = run_tiny("memento-1m-x4.stream", plant=reciprocal_jump_step(),
                              n_buckets=10**6, batch_keys=8192)["correct"]
    print(json.dumps(out))
""")


X4 = {"config": {"name": "memento-1m-x4", "source": "test",
                  "file": "bench/configs/memento-1m-x4.json", "reduced": [], "why": "test"},
      "workload": {"name": "memento-1m-x4.stream", "config": "memento-1m-x4",
                   "traffic": "stream", "chips": 4, "why": "test"}}


def _spec_with_four_chip_cell(root: Path) -> Path:
    """The benchmark's files under ``root``, with the four-chip cell in its
    ``BENCHMARK.json`` whether or not the committed one lists it."""
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    if X4["config"]["name"] not in {c["name"] for c in spec["configs"]}:
        spec["configs"].append(X4["config"])
    if X4["workload"]["name"] not in {w["name"] for w in spec["workloads"]}:
        spec["workloads"].append(X4["workload"])
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@functools.lru_cache(maxsize=None)
def _four_chip_results() -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS=("--xla_force_host_platform_device_count=4 "
                          + os.environ.get("XLA_FLAGS", "")).strip())
    with tempfile.TemporaryDirectory() as tmp:
        code = _FOUR_CHIPS.format(root=str(ROOT),
                                  spec_root=str(_spec_with_four_chip_cell(Path(tmp))))
        res = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-4000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_four_chip_cell_is_correct_unbroken():
    assert _four_chip_results()["clean"] is True


@pytest.mark.parametrize("fault", ["half_batch", "altered_answer", "no_exchange",
                                   "state_unchanged", "control"])
def test_four_chip_fault_makes_the_run_incorrect(fault):
    assert _four_chip_results()[fault] is False
