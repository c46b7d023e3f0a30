"""Compile the chip path for a described TPU v5e, at real sizes.

Nothing runs: each test lowers and compiles one program the chip path
dispatches against a ``v5e:2x2`` topology description, so what the TPU
compiler refuses (a Mosaic lowering, VMEM overflow, a sharding) fails
here at no chip time.  Sizes are the chip smoke's: 10⁶ buckets, 2²⁰ keys.
The topology is described inside a module fixture (never at import), so
every test worker collects the same tests and only the worker that runs
this file loads the TPU compiler.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from conformance import ALGORITHMS
from repro.core import make_hash
from repro.core.protocol import ALGORITHM_REGISTRY, IMAGE_LAYOUT, round_up
from repro.kernels.autotune import op_tag
from repro.kernels.engine import (DEFAULT_BLOCK_ROWS, MOSAIC_ALGOS, EngineOp,
                                  _engine_jnp, _engine_pallas, mosaic_compiles)

N_BUCKETS = 1_000_000
N_KEYS = 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _table_lengths(algo: str, n: int) -> dict[str, int]:
    """Image array lengths of a ``make_hash(algo, n)`` fleet, from the
    registry's sizing rule (fixed-capacity algorithms hold a = 10·n)."""
    a = 10 * n if ALGORITHM_REGISTRY[algo].fixed_capacity else n
    return {name: round_up(ln)
            for name, ln in ALGORITHM_REGISTRY[algo].required(a).items()}


def _image_shapes(algo: str, sharding, epochs: int = 1):
    dtype = {"words": jnp.uint32}
    lengths = _table_lengths(algo, N_BUCKETS)
    arrays = tuple(jax.ShapeDtypeStruct((lengths[name],),
                                        dtype.get(name, jnp.int32),
                                        sharding=sharding)
                   for _ in range(epochs) for name in IMAGE_LAYOUT[algo][1])
    scalars = tuple(jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding)
                    for _ in range(epochs)
                    for _ in IMAGE_LAYOUT[algo][0])
    return arrays, scalars


def test_described_device_is_a_v5e(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"


def test_table_lengths_match_the_host_images():
    for algo in ALGORITHMS:
        img = make_hash(algo, 1000, variant="32").device_image()
        assert {k: v.shape[0] for k, v in img.arrays.items()} == \
            _table_lengths(algo, 1000), algo


@pytest.mark.parametrize("algo,diff", [(a, False) for a in ALGORITHMS]
                         + [("memento", True)],
                         ids=list(ALGORITHMS) + ["memento-diff"])
def test_jnp_engine_compiles(one_chip, algo, diff):
    """Every algorithm's jitted jnp lookup, and the store's migration diff.
    (The TPU compiler takes tens of seconds on Anchor's and Dx's programs
    at 2²⁰ keys, against about one at 2¹⁶.)"""
    op = EngineOp(algo, diff=diff)
    keys = jax.ShapeDtypeStruct((N_KEYS,), jnp.uint32, sharding=one_chip)
    arrays, scalars = _image_shapes(algo, one_chip, epochs=2 if diff else 1)
    compiled = _engine_jnp.lower((keys,), arrays, scalars, None, None,
                                 op=op).compile()
    assert compiled.as_text()


@pytest.mark.parametrize("table", ["dense", "packed"])
@pytest.mark.parametrize("diff", [False, True], ids=["lookup", "diff"])
@pytest.mark.parametrize("algo", MOSAIC_ALGOS)
def test_pallas_engine_compiles(one_chip, algo, diff, table):
    op = EngineOp(algo, diff=diff, table=table)
    assert mosaic_compiles(op)
    n_scalars = op.num_scalars * (2 if diff else 1)
    scalars = jax.ShapeDtypeStruct((n_scalars,), jnp.int32, sharding=one_chip)
    keys2d = jax.ShapeDtypeStruct((N_KEYS // 128, 128), jnp.uint32,
                                  sharding=one_chip)
    compiled = _engine_pallas.lower(scalars, (keys2d,), (), op=op,
                                    block_rows=DEFAULT_BLOCK_ROWS,
                                    interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


REFUSED = ([EngineOp(a) for a in ALGORITHMS if a not in MOSAIC_ALGOS]
           + [EngineOp(MOSAIC_ALGOS[0], k=2),
              EngineOp(MOSAIC_ALGOS[1], bounded=True)])


@pytest.mark.parametrize("op", REFUSED, ids=[op_tag(op) for op in REFUSED])
def test_pallas_refuses_what_mosaic_cannot_compile(one_chip, op):
    """A gather-backed (or k>1) configuration raises at dispatch, naming
    the op, before Mosaic ever sees it."""
    assert not mosaic_compiles(op)
    keys2d = jax.ShapeDtypeStruct((64, 128), jnp.uint32, sharding=one_chip)
    with pytest.raises(ValueError, match=f"cannot compile {op.algo}"):
        _engine_pallas.lower(jnp.zeros((2,), jnp.int32), (keys2d,), (),
                             op=op, block_rows=8, interpret=False)


@pytest.mark.parametrize("words", [round_up(N_BUCKETS),
                                   round_up(2 * N_BUCKETS)],
                         ids=["1000064", "2000000"])
def test_delta_apply_compiles(one_chip, words):
    """The tiled apply-delta kernel at the dense 10⁶-bucket Memento image
    (and the store's 2× headroom capacity) stays inside VMEM."""
    from repro.kernels.delta_apply import _apply_scatter_i32

    meta = jax.ShapeDtypeStruct((1 + 2 * 8,), jnp.int32, sharding=one_chip)
    table = jax.ShapeDtypeStruct((words // 128, 128), jnp.int32,
                                 sharding=one_chip)
    compiled = _apply_scatter_i32.lower(meta, table, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("algo", ["memento", "jump"])
def test_sharded_plane_compiles_on_four_chips(topo, algo):
    """The serving plane's shard_map program over a 4-chip mesh, with the
    plane the chip dispatches (jnp for Memento, compiled Pallas for Jump):
    each chip gets a quarter of the keys and a replicated image."""
    from jax.sharding import Mesh

    from repro.serve.plane import sharded_lookup_program

    mesh = Mesh(topo.devices[:4], ("data",))
    op = EngineOp(algo)
    plane = "pallas" if mosaic_compiles(op) else "jnp"
    fn = sharded_lookup_program(op, mesh, ("data",), plane=plane,
                                block_rows=DEFAULT_BLOCK_ROWS,
                                interpret=False)
    rep = NamedSharding(mesh, P())
    arrays, scalars = _image_shapes(algo, rep)
    names = IMAGE_LAYOUT[algo][1]
    keys = jax.ShapeDtypeStruct((N_KEYS,), jnp.int32,
                                sharding=NamedSharding(mesh, P("data")))
    compiled = fn.lower(keys, dict(zip(names, arrays)), scalars).compile()
    out = compiled.output_shardings
    assert out.spec == P("data") and len(out.device_set) == 4
