"""Host ⇄ device data-plane equivalence for batched Memento lookups."""
from __future__ import annotations

import re

import numpy as np
import pytest

from repro.core import MementoHash, MementoTables, np_jump32, random_state
from repro.core import jax_lookup


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


_HLO_CALLEE = re.compile(r"(calls|to_apply|condition|body)=%?([\w.\-]+)")


@pytest.mark.parametrize("counted", [False, True], ids=["plain", "counted"])
def test_memento_sweep_is_one_repl_gather(counted):
    """Each ``repl`` word is gathered once: no while condition gathers
    (the word it tests rides the carry), and the program holds three
    gather sites — before the loops, at each inner loop's entry, and in
    the inner body."""
    import jax
    import jax.numpy as jnp

    from repro.launch.hlo_analysis import analyze_jit

    m = random_state(np.random.default_rng(5), 2000, 1800, variant="32")
    repl = jnp.asarray(MementoTables(m).repl)
    keys = jnp.asarray(np.random.default_rng(6).integers(
        0, 2**32, size=4096, dtype=np.uint32))
    fn = (jax_lookup.memento_lookup_counted if counted
          else jax_lookup.memento_lookup)
    comps = analyze_jit(jax.jit(fn), keys, repl, m.n).comps

    def gathers(name, seen=frozenset()):
        instrs = comps[name].instrs
        callees = {c for ins in instrs
                   for _, c in _HLO_CALLEE.findall(ins.rest)} - seen
        return (sum(ins.opcode == "gather" for ins in instrs)
                + sum(gathers(c, seen | {name}) for c in callees))

    conds = {c for comp in comps.values() for ins in comp.instrs
             for kind, c in _HLO_CALLEE.findall(ins.rest)
             if ins.opcode == "while" and kind == "condition"}
    assert len(conds) >= 2  # Memento's two loops (and Jump's)
    assert {c: gathers(c) for c in conds} == dict.fromkeys(conds, 0)
    assert sum(ins.opcode == "gather" for comp in comps.values()
               for ins in comp.instrs) == 3


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
