"""``BENCHMARK.json`` keeps the benchmark's contract, and every name in it
resolves to a file: the static half of what is refused before any run."""
from __future__ import annotations

import json
import re

import pytest

from bench import peaks
from conftest import ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51 and isinstance(SPEC["run_seconds"], int)
    assert len(json.dumps(SPEC)) <= 64 * 1024
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert (ROOT / p).is_dir()
    assert len(SPEC["command"]) <= 32 and all(_line(w) for w in SPEC["command"])
    script = SPEC["command"][1]
    assert any(script.startswith(p + "/") for p in SPEC["paths"])


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries_have_just_their_keys(section):
    for e in SPEC[section]:
        extra = {"workloads"} if section in ("end_to_end", "per_layer") else set()
        assert KEYS[section] <= set(e) <= KEYS[section] | extra, e["name"]
        assert NAME.match(e["name"])
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
    names = [e["name"] for e in SPEC[section]]
    assert len(names) == len(set(names))


def test_configs_resolve_and_state_their_cuts():
    used = {w["config"] for w in SPEC["workloads"]}
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    for c in SPEC["configs"]:
        assert c["name"] in used and _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert (ROOT / "bench" / "references" / f"{cfg['algo']}.py").is_file()


def test_workloads_resolve():
    chips4 = 0
    pairs = set()
    configs = {c["name"]: c for c in SPEC["configs"]}
    for w in SPEC["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4) and _line(w["why"])
        assert NAME.match(w["traffic"])
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        cfg = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
        assert cfg["chips"] == w["chips"]
        pairs.add((w["config"], w["traffic"]))
        chips4 += w["chips"] == 4
    assert len(pairs) == len(SPEC["workloads"])
    assert chips4 <= max(1, len(SPEC["workloads"]) // 2)


def test_metrics_cover_every_cell():
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and _line(m["layer"])
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        for w in m.get("workloads", cells):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", cells)
    for w in cells:
        reported = [m for m in SPEC["end_to_end"] if w in m.get("workloads", cells)]
        assert len(reported) >= 2
        assert any(w in m.get("workloads", cells) for m in SPEC["per_layer"])


def test_layers_are_named_alike():
    layers = {}
    for m in SPEC["per_layer"]:
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no peaks"):
        peaks.peaks("TPU v99")
    with pytest.raises(ValueError):
        peaks.lookup_floor_s(1024, "cpu")


def test_lookup_floor_is_eight_bytes_a_key_over_hbm():
    assert peaks.LOOKUP_BYTES_PER_KEY == 8
    assert peaks.lookup_floor_s(819, "TPU v5 lite") == pytest.approx(8e-9)
