"""The trace reduction and the readers that use it, on a small trace written
out by hand (every number below follows from it on paper) and on a small
trace recorded on one TPU v5e (six 2^16-key ``route_batch`` calls of a
healthy 10^6-bucket Memento and one ``fail_replica``)."""
from __future__ import annotations

import json
from types import SimpleNamespace

import pytest

from bench import tracing
from bench.harness import Context, metric_reader
from conftest import ROOT

WHILE = "%while.1 = (s32[8]{0}) while((s32[8]{0}) %t), condition=%c, body=%b"
GATHER = "%fusion.2 = s32[8]{0} fusion(s32[8]{0} %a), kind=kCustom, calls=%f"
COPY = "%copy.3 = s32[8]{0} copy(s32[8]{0} %x)"
LOOP = "%fusion.9 = s32[8]{0} fusion(s32[8]{0} %y), kind=kLoop, calls=%g"

HAND = {
    "devices": {
        "0": {"ops": [[1000, 2000, WHILE], [1500, 500, GATHER],
                      [5000, 1000, COPY], [10500, 1000, COPY]],
              "modules": [[900, 2200, "jit__engine_jnp(1)"], [4900, 1200, "jit_b(2)"],
                          [10400, 2000, "jit__engine_jnp(1)"]]},
        "1": {"ops": [[2000, 4000, LOOP]],
              "modules": [[1900, 4200, "jit__engine_jnp(1)"]]},
    },
    "host": [[1000, 10000, "bench.window"], [1000, 4000, "bench.route_batch"],
             [3000, 1500, "np.asarray(jax.Array)"], [6000, 3000, "bench.fail_replica"],
             [20000, 10, "bench.route_batch"]],
}


def test_hand_trace_busy_idle_and_gaps():
    red = tracing.reduce(HAND)
    assert red["window_s"] == pytest.approx(10e-6)
    # device 0: [1000, 3000] + [5000, 6000] + [10500, 11000 = window end]
    assert red["busy_s"] == pytest.approx({"0": 3.5e-6, "1": 4e-6})
    # device 0's gaps: [3000, 5000] inside np.asarray, [6000, 10500] inside
    # fail_replica; device 1's: [1000, 2000] inside route_batch, [6000,
    # 11000] with its middle inside fail_replica
    assert red["idle_s"] == pytest.approx({"np.asarray(jax.Array)": 2e-6,
                                           "bench.fail_replica": 4.5e-6 + 5e-6,
                                           "bench.route_batch": 1e-6})
    assert sum(red["busy_s"].values()) + sum(red["idle_s"].values()) == \
        pytest.approx(2 * red["window_s"])


def test_hand_trace_self_times_and_programs():
    red = tracing.reduce(HAND)
    assert red["op_s"] == pytest.approx({
        "jit__engine_jnp/while.1 while": 1.5e-6,            # 2000 less its body
        "jit__engine_jnp/fusion.2 fusion kCustom": 0.5e-6,
        "jit_b/copy.3 copy": 1e-6,                          # the clipped one left out
        "jit__engine_jnp/fusion.9 fusion kLoop": 4e-6})
    assert red["program_s"] == pytest.approx({"jit__engine_jnp": 2.1e-6 + 0.6e-6 + 4.2e-6,
                                              "jit_b": 1.2e-6})
    assert red["program_runs"] == {"jit__engine_jnp": 3, "jit_b": 1}
    bd = tracing.breakdown(red, top=2)
    assert bd["device_ops"][0] == ["jit__engine_jnp/fusion.9 fusion kLoop", pytest.approx(4e-6)]
    assert bd["idle_gaps"][0] == ["bench.fail_replica", pytest.approx(9.5e-6)]


def _ctx(red, batch_keys=1 << 16, devices=1):
    cell = SimpleNamespace(traffic={"batch_keys": batch_keys}, root=ROOT)
    return Context(cell, "TPU v5 lite", devices, {}, red)


def test_hand_trace_readers():
    red = tracing.reduce(HAND)
    ctx = _ctx(red, batch_keys=2 * 819, devices=2)
    assert metric_reader(ctx.cell, "device.idle_pct")(ctx) == pytest.approx(62.5)
    assert metric_reader(ctx.cell, "engine.device_ms")(ctx) == pytest.approx(6.9e-6 / 3 * 1e3)
    # 819 keys per device move 6552 B: 8 ns at 819 GB/s, over 6.9/3 us a run
    assert metric_reader(ctx.cell, "engine.roofline_pct")(ctx) == pytest.approx(
        100 * 8e-9 / (6.9e-6 / 3))


def test_readers_find_nothing_to_read_untraced():
    ctx = _ctx(None)
    for name in ("device.idle_pct", "engine.device_ms", "engine.roofline_pct",
                 "router.host_ms", "plane.dispatch_ms", "store.sync_ms"):
        assert metric_reader(ctx.cell, name)(ctx) is None


def test_window_span_is_required():
    with pytest.raises(ValueError, match="bench.window"):
        tracing.reduce({"devices": {}, "host": []})


@pytest.mark.parametrize("hlo,want", [
    (WHILE, "p/while.1 while"), (GATHER, "p/fusion.2 fusion kCustom"),
    ("%copy-start.6 = (s32[4]{0}, u32[]{:S(2)}) copy-start(s32[4]{0} %g)",
     "p/copy-start.6 copy-start"),
    ("not an hlo line", "p/not an hlo line")])
def test_op_names(hlo, want):
    assert tracing.op_name("p", hlo) == want


RECORDED = ROOT / "tests" / "bench" / "trace_v5e_churn_small.json"


def test_recorded_chip_trace():
    """Six 65,536-key batches and one removal on a v5e: the engine program
    ran six times, its table gathers lead the device time, the scatter of
    the removal ran once, and busy plus idle fill the window."""
    red = tracing.reduce(json.loads(RECORDED.read_text()))
    assert red["program_runs"] == {"jit__engine_jnp": 6, "jit__scatter_jnp": 1,
                                   "jit_convert_element_type": 6}
    busy = red["busy_s"]["0"]
    assert red["window_s"] == pytest.approx(0.03683257)
    assert busy == pytest.approx(0.016774116)
    assert red["program_s"]["jit__engine_jnp"] == pytest.approx(0.016728706)
    assert red["idle_s"]["np.asarray(jax.Array)"] == pytest.approx(0.018670603)
    assert busy + sum(red["idle_s"].values()) == pytest.approx(red["window_s"])
    assert sum(red["op_s"].values()) == pytest.approx(busy, rel=1e-6)
    top = tracing.breakdown(red)["device_ops"][0]
    assert top == ["jit__engine_jnp/fusion.3 fusion kCustom", pytest.approx(0.007643633)]
    assert set(red["idle_s"]) <= {e[2] for e in json.loads(RECORDED.read_text())["host"]}
