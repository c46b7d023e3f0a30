"""Host ⇄ device data-plane equivalence for batched Memento lookups."""
from __future__ import annotations

import functools
import importlib.util
import re
from pathlib import Path

import numpy as np
import pytest

from repro.core import MementoHash, MementoTables, np_jump32, random_state
from repro.core import jax_lookup

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def keys():
    return np.random.default_rng(0).integers(0, 2**32, size=512, dtype=np.uint32)


def test_jnp_jump_matches_numpy(keys):
    import jax.numpy as jnp

    for n in (1, 3, 97, 4096, 100000, 1_000_000, 1 << 24):
        dev = np.asarray(jax_lookup.jump32(jnp.asarray(keys), n))
        host = np_jump32(keys, n)
        np.testing.assert_array_equal(dev, host)


@pytest.mark.parametrize("ulps", [-6, -2, -1, 0, 1, 2, 6])
def test_floor_rn_quotient_corrects_an_inexact_divide(ulps):
    """The jump step equals the host's correctly rounded float32 step even
    when the device's divide is off by a few ulps (a TPU's is)."""
    import jax.numpy as jnp

    from repro.kernels.primitives import floor_rn_quotient

    rng = np.random.default_rng(ulps + 100)
    # quotients around 2²³..2²⁴ (ulp ≥ 1), some beyond the clamp n = 2²⁴
    den = rng.integers(1, (1 << 24) + 1, size=1 << 13)
    q = rng.integers(1 << 22, (1 << 24) + (1 << 22), size=den.size)
    x = np.clip(q * den >> 24, 1, 1 << 24)
    # and quotients whose fraction sits at the round-up boundary of float32:
    # the gap to the next integer within one unit of den/2^(24−e)
    bden = rng.integers(2, 1 << 12, size=1 << 21)
    bx = rng.integers(1, bden)
    bq, brem = np.divmod(bx << 24, bden)
    sh = np.clip(24 - np.floor(np.log2(bq)).astype(np.int64), 1, 24)
    near = np.abs(bden - brem - ((bden - 1) >> sh)) <= 1
    x = np.concatenate([x, bx[near]]).astype(np.int32)
    den = np.concatenate([den, bden[near]]).astype(np.int32)
    n = np.int32(1 << 24)
    exact = np.float32(x) / (np.float32(den) * np.float32(2.0 ** -24))
    want = np.minimum(np.floor(exact), np.float32(n)).astype(np.int32)
    approx = exact.copy()
    for _ in range(abs(ulps)):
        approx = np.nextafter(approx, np.float32(np.inf if ulps > 0 else 0))
    got = floor_rn_quotient(jnp.asarray(x), jnp.asarray(den),
                            jnp.asarray(approx), jnp.asarray(n))
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("n0,removals",
                         [(16, 0), (16, 7), (128, 50), (1000, 400), (2000, 1800)])
def test_jnp_memento_matches_host(keys, n0, removals):
    import jax.numpy as jnp

    m = random_state(np.random.default_rng(1), n0, removals, variant="32")
    tabs = MementoTables(m)
    out = np.asarray(jax_lookup.memento_lookup(jnp.asarray(keys), jnp.asarray(tabs.repl), m.n))
    ws = m.working_set()
    host = np.asarray([m.lookup(int(k)) for k in keys])
    np.testing.assert_array_equal(out, host)
    assert set(out.tolist()) <= ws


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "t_memento_reference", ROOT / "bench" / "references" / "memento.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


#: the benchmark's plain numpy Memento (imports nothing of the program)
REF = _load_reference()


FLEET_KEYS = np.random.default_rng(8).integers(0, 2**32, size=512,
                                               dtype=np.uint32)


@functools.lru_cache(maxsize=None)
def _fleet(removed_share, n):
    """A ``variant="32"`` Memento of ``n`` buckets with ``removed_share``
    of them removed at random, and the host's buckets for
    :data:`FLEET_KEYS`, which the plain reference's equal."""
    m = MementoHash(n, variant="32")
    ref = REF.Reference(n)
    order = np.random.default_rng(int(n * removed_share) + 7).permutation(n)
    for b in order[:int(removed_share * n)].tolist():
        m.remove(b)
        ref.remove(b)
    host = np.asarray([m.lookup(int(k)) for k in FLEET_KEYS])
    np.testing.assert_array_equal(ref.lookup(FLEET_KEYS), host)
    return m, host


@pytest.mark.parametrize("plane,packed", [
    pytest.param("jnp", False, id="jnp-dense"),
    pytest.param("jnp", True, id="jnp-packed"),
    pytest.param("pallas", False, id="pallas-interpret")])
@pytest.mark.parametrize("n", [2000, 20_000])
@pytest.mark.parametrize("removed_share", [0.0, 0.3, 0.7, 0.9, 0.99])
def test_memento_planes_match_host_and_reference(removed_share, n, plane,
                                                 packed):
    """The one Memento loop gives the host's and the plain reference's
    bucket for every key, below and past the ~70% knee, on each plane."""
    from repro.core.packing import pack_image
    from repro.kernels.engine import engine_lookup

    m, host = _fleet(removed_share, n)
    image = pack_image(m.device_image()) if packed else m.device_image()
    out = engine_lookup(FLEET_KEYS, image, plane=plane, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), host)


@pytest.mark.parametrize("removed_share", [0.3, 0.9, 0.99])
def test_memento_counted_sweeps_equal_the_longest_lane(removed_share):
    """Each lane keeps its own phase, so a batch runs exactly as many
    sweeps as its slowest key takes steps, on every batch; the other
    counts are consistent with it."""
    import jax
    import jax.numpy as jnp

    m, _ = _fleet(removed_share, 20_000)
    repl = jnp.asarray(MementoTables(m).repl)
    counted = jax.jit(jax_lookup.memento_lookup_counted)
    for seed in range(4):
        keys = np.random.default_rng(100 + seed).integers(
            0, 2**32, size=1024 << seed % 2, dtype=np.uint32)
        b, sweeps, lanes, outer, longest = (
            np.asarray(x) for x in counted(jnp.asarray(keys), repl, m.n))
        assert int(sweeps) == int(longest) >= int(outer) >= 1
        assert int(sweeps) < int(lanes) < int(sweeps) * keys.size
        np.testing.assert_array_equal(
            b, jax_lookup.memento_lookup(jnp.asarray(keys), repl, m.n))


_HLO_CALLEE = re.compile(r"(calls|to_apply|condition|body)=%?([\w.\-]+)")


@pytest.mark.parametrize("variant", ["plain", "counted", "packed"])
def test_memento_sweep_is_one_repl_gather(variant):
    """Each ``repl`` word is read once: no while condition gathers or
    loops (the word it tests rides the carry, so a table reader is called
    only in the loop body).  Over the dense table the program holds two
    gather sites — before the loop and in its body; over the packed
    image (the engine's jnp program) each read is a bitmap gather and a
    slot probe loop."""
    import jax
    import jax.numpy as jnp

    from repro.launch.hlo_analysis import analyze_jit

    m = random_state(np.random.default_rng(5), 2000, 1800, variant="32")
    repl = jnp.asarray(MementoTables(m).repl)
    keys = jnp.asarray(np.random.default_rng(6).integers(
        0, 2**32, size=4096, dtype=np.uint32))
    if variant == "packed":
        from repro.core.packing import pack_image
        from repro.kernels.engine import EngineOp, _engine_jnp, _jnp_operands

        arrays, scalars = _jnp_operands([pack_image(m.device_image())])
        comps = analyze_jit(_engine_jnp, (keys,), arrays, scalars, None, None,
                            static={"op": EngineOp("memento", table="packed")}
                            ).comps
    else:
        fn = (jax_lookup.memento_lookup_counted if variant == "counted"
              else jax_lookup.memento_lookup)
        comps = analyze_jit(jax.jit(fn), keys, repl, m.n).comps

    def reached(name, opcode, seen=frozenset()):
        instrs = comps[name].instrs
        callees = {c for ins in instrs
                   for _, c in _HLO_CALLEE.findall(ins.rest)} - seen
        return (sum(ins.opcode == opcode for ins in instrs)
                + sum(reached(c, opcode, seen | {name}) for c in callees))

    conds = {c for comp in comps.values() for ins in comp.instrs
             for kind, c in _HLO_CALLEE.findall(ins.rest)
             if ins.opcode == "while" and kind == "condition"}
    assert len(conds) >= 2  # Memento's loop and Jump's
    for opcode in ("gather", "while"):
        assert {c: reached(c, opcode) for c in conds} == dict.fromkeys(conds, 0)
    if variant != "packed":
        assert sum(ins.opcode == "gather" for comp in comps.values()
                   for ins in comp.instrs) == 2


def test_jnp_memento_balance(keys):
    import jax.numpy as jnp

    m = random_state(np.random.default_rng(2), 32, 12, variant="32")
    tabs = MementoTables(m)
    big = np.random.default_rng(3).integers(0, 2**32, size=50000, dtype=np.uint32)
    out = np.asarray(jax_lookup.memento_lookup(jnp.asarray(big), jnp.asarray(tabs.repl), m.n))
    counts = np.bincount(out, minlength=m.n)
    ws = sorted(m.working_set())
    expected = len(big) / len(ws)
    assert counts[[b for b in range(m.n) if b not in ws]].sum() == 0
    for b in ws:
        assert abs(counts[b] - expected) < 6 * np.sqrt(expected)


def test_tables_incremental_updates():
    m = MementoHash(64, variant="32")
    tabs = MementoTables(m)
    rng = np.random.default_rng(4)
    for step in range(60):
        if rng.random() < 0.6 and m.working > 1:
            ws = sorted(m.working_set())
            b = ws[int(rng.integers(len(ws)))]
            m.remove(b)
            tabs.on_remove(b)
        else:
            b = m.add()
            tabs.on_add(b)
        tabs.check()
