"""The harness at a tiny size on the CPU: the shape of the last line, the
refusals, and a new configuration, traffic mix and metric found by their
file names alone."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT, run_tiny

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"] if w["chips"] == 1]


@pytest.mark.parametrize("name", CELLS)
def test_result_line_has_the_contract_shape(name):
    r = run_tiny(name)
    json.dumps(r)
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(r)[-1] == "checks"
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    want = {m["name"]: m["unit"] for m in SPEC["end_to_end"]
            if name in m.get("workloads", [name])}
    assert {k: v["unit"] for k, v in r["metrics"].items()} == want
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())


def test_traced_result_reports_per_layer_metrics_only():
    r = run_tiny("memento-1m.churn", trace=True)
    assert {"busy_s", "window_s"} <= set(r["device"])
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    # the CPU has no device trace: only the telemetry readers find anything
    assert set(r["metrics"]) == {"router.host_ms", "store.sync_ms"}
    assert all(m["unit"] == "ms" and m["value"] > 0 for m in r["metrics"].values())


def test_same_seed_same_inputs():
    a, b = run_tiny("memento-1m.churn", seed=5), run_tiny("memento-1m.churn", seed=5)
    assert a["checks"] == b["checks"]
    from bench.harness import IDS, rng, session_ids
    assert (session_ids(rng(5, IDS), 8) == session_ids(rng(5, IDS), 8)).all()
    assert (session_ids(rng(5, IDS), 8) != session_ids(rng(6, IDS), 8)).any()


def test_fixed_population_changes_only_the_order():
    from bench.harness import ORDER, Schedule, population_seed, rng

    assert population_seed({"ids_seed": 9}, "ids_seed", 5) == 9
    assert population_seed({"ids_seed": None}, "ids_seed", 5) == 5
    a = Schedule(4, rng(5, ORDER))
    slots = [a[i] for i in range(12)]
    assert all(sorted(slots[k:k + 4]) == [0, 1, 2, 3] for k in (0, 4, 8))
    b = Schedule(4, rng(5, ORDER))
    assert [b[i] for i in range(12)] == slots


def _run_script(*args, cwd=ROOT, platform="cpu"):
    env = dict(os.environ, JAX_PLATFORMS=platform)
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_machine_without_a_tpu():
    res = _run_script("--workload", "memento-1m.bulk", "--seed", "1",
                      "--seconds", "1", "--trace", "0")
    assert res.returncode == 2
    assert res.stdout.strip() == "" and "TPU" in res.stderr


def test_run_refuses_a_tree_of_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    res = _run_script("--workload", "memento-1m.bulk", "--seed", "1",
                      "--seconds", "1", "--trace", "0", cwd=tmp_path)
    assert res.returncode != 0 and res.stdout.strip() == ""


NEW_METRIC = '''"""A test metric: batches per traced window, from the telemetry."""


def read(ctx):
    n, _ = ctx.hist("router.route_batch.us")
    return float(n) if n else None
'''


def test_new_config_traffic_and_metric_found_by_file_name(tmp_path):
    """A copy of the benchmark plus new files and new entries: the harness
    runs the new cell, with no file of the harness edited."""
    for p in ("bench",):
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((ROOT / "bench/configs/memento-1m.json").read_text())
    cfg.update(name="memento-2k", n_buckets=2000, removed_fraction=0.5)
    (tmp_path / "bench/configs/memento-2k.json").write_text(json.dumps(cfg))
    traffic = json.loads((ROOT / "bench/traffic/churn.json").read_text())
    traffic.update(batch_keys=512, event_every=3)
    (tmp_path / "bench/traffic/tiny-churn.json").write_text(json.dumps(traffic))
    (tmp_path / "bench/metrics/router.batches.py").write_text(NEW_METRIC)
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "memento-2k", "source": "test",
                            "file": "bench/configs/memento-2k.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "memento-2k.tiny-churn", "config": "memento-2k",
                              "traffic": "tiny-churn", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "router.batches", "unit": "batches",
                              "better": "higher", "source": "program_span",
                              "layer": "router", "moves": "keys_per_s",
                              "workloads": ["memento-2k.tiny-churn"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    from bench.harness import load_cell
    cell = load_cell("memento-2k.tiny-churn", root=tmp_path)
    assert cell.config["n_buckets"] == 2000 and cell.traffic["event_every"] == 3
    assert [m["name"] for m in cell.per_layer] == ["router.batches"]
    r = run_tiny("memento-2k.tiny-churn", root=tmp_path, trace=True)
    assert r["correct"] is True
    assert r["metrics"]["router.batches"]["value"] > 0
