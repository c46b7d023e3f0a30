"""The lookup engine — ONE tiled Pallas dispatch for the whole data plane.

Every device-side lookup-shaped operation in this repo is a configuration
of a single kernel family (DESIGN.md §6): the grid tiles the key batch
into ``(BLOCK_ROWS, 128)`` uint32 blocks streamed through VMEM while the
algorithm's image tables stay resident, and the **op mode** and
**algorithm** are selected statically, so each configuration compiles to
exactly ONE ``pallas_call`` launch (and, on the jnp plane, one jitted XLA
program).  The configuration space is :class:`EngineOp`:

  =========== =====================================================
  op            outputs (per key)
  =========== =====================================================
  lookup        1 bucket                       (k=1, the classic op)
  lookup_k      k distinct buckets             (k>1, salted walk)
  + bounded     the salted walk also skips buckets at/above a load
                cap — the fused "k replicas under bounded load"
                that previously needed multiple launches
  + diff        everything above under TWO epoch images at once,
                plus the moved mask — k=1 is the migration diff,
                k>1 the fused replica-set diff
  walk          one bounded-load chain-walk step (b, chain, probe)
                — the round primitive of :func:`bounded_assign`
  =========== =====================================================

Algorithms: ``memento`` (dense Θ(n) ``repl`` table, or the packed
bitmap + Θ(r) open-addressing slots), ``anchor`` (A/K arrays, narrowed
when packed), ``dx`` (packed bitmap), ``jump`` and ``power`` (stateless).
The per-algorithm lookup bodies live in :mod:`repro.core.jax_lookup`,
once each; :func:`algo_body` is the one place that picks a body and, for
packed Memento, its table reader, and :func:`_mode_outputs` the one op
runner every plane calls.  This module is the one import surface for
device lookups.

Planes: ``plane='pallas'`` (Mosaic on TPU for the configurations
:func:`mosaic_compiles` admits — any other refuses at dispatch —,
interpret elsewhere),
``plane='jnp'`` (pure-jnp, any backend; also the per-shard body the
mesh-sharded :class:`~repro.serve.plane.ShardedLookupPlane` runs under
``shard_map``) and ``plane='auto'`` (the autotuner's pick,
:func:`repro.kernels.autotune.resolve_plane`).  Both planes run the same
bodies and are bit-identical to the host control plane on
``variant="32"`` states.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bounded import accept_in_index_order, walk_probe_bound
from repro.core.hashing import GOLDEN32
from repro.core.jax_lookup import (_memento_loop, anchor_lookup,
                                   lookup_dispatch, memento_lookup_counted)
from repro.core.packing import PACKED_LAYOUT, image_table_names
from repro.core.protocol import (ALGORITHMS, IMAGE_LAYOUT, REPLICA_SALT_CAP,
                                 image_scalar_vec)
from repro.obs.metrics import default_registry as _obs_registry
from .primitives import fmix32, gather1d, hash2, table_shape2d

_U = jnp.uint32

DEFAULT_BLOCK_ROWS = 8  # (8, 128) keys per program = 1024 lookups


def default_interpret() -> bool:
    """THE interpret-mode rule for every Pallas kernel in the repo: Mosaic
    on a TPU backend, the Pallas interpreter everywhere else."""
    return jax.default_backend() != "tpu"


#: algorithms whose lookup body Mosaic compiles: the stateless ones.  The
#: table-backed bodies (Memento ``repl``/slots, Anchor A/K, Dx bitmap) and
#: every load-word mode need a 1-D gather from a VMEM table, which Mosaic
#: does not lower ("Only 2D gather is supported"), and k > 1 replica walks
#: start splat-constant lane carries Mosaic cannot lay out (DESIGN.md §6).
MOSAIC_ALGOS = ("jump", "power")


def mosaic_compiles(op: "EngineOp") -> bool:
    """Can the Pallas plane run ``op`` compiled (not interpreted) on a TPU?"""
    return (op.algo in MOSAIC_ALGOS and op.mode == "lookup" and op.k == 1
            and not op.bounded)


def _resolve_block_rows(op, n_keys: int, table_n: int,
                        block_rows: int | None) -> int:
    """Tile-height dispatch rule: an explicit ``block_rows=`` always wins;
    otherwise consult the autotuner's persisted cache (a pure dict lookup
    on the bucketed grid key — cache hits can never retrace), falling back
    to :data:`DEFAULT_BLOCK_ROWS`."""
    if block_rows is not None:
        return block_rows
    from . import autotune  # lazy: autotune ↔ engine would cycle at import
    return autotune.resolve_block_rows(op, n_keys, table_n)


def _dispatch_span(reg, op: "EngineOp"):
    """The ``engine.dispatch`` span of one engine dispatch: operand
    marshalling, the key upload and the launch.  It also observes the
    per-:class:`EngineOp` latency histogram ``engine.dispatch.us``, keyed
    by the autotuner's op tag."""
    if not reg.active:
        return reg.timed("engine.dispatch")
    from .autotune import op_tag
    return reg.timed("engine.dispatch", labels={"op": op_tag(op)})


def _obs_dispatch(reg, n_keys: int) -> None:
    """Fold one engine dispatch into the live telemetry registry
    (DESIGN.md §11): dispatches served, keys, and the batch-size
    distribution.  Counters are integers of replayed control flow, so a
    replay's counter snapshot is bit-identical."""
    reg.counter("engine.dispatches").inc()
    reg.counter("engine.keys").inc(n_keys)
    reg.histogram("engine.batch_keys").observe(n_keys)


#: the histograms the counted Memento program's four device counts feed
#: (:func:`repro.core.jax_lookup.memento_lookup_counted`), one value a batch
MEMENTO_SWEEP_HISTOGRAMS = ("engine.memento.sweeps", "engine.memento.lane_sweeps",
                            "engine.memento.outer_sweeps", "engine.memento.longest_lane")


# ---------------------------------------------------------------------------
# Static op configuration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class EngineOp:
    """Static engine configuration — one value of this dataclass, one
    compiled program (jnp) / one Pallas launch (pallas).

    * ``algo``    — a name in :data:`repro.core.protocol.ALGORITHMS`,
    * ``mode``    — "lookup" (k replica slots, optionally bounded and/or
      diffed across two epochs) or "walk" (one bounded chain-walk step),
    * ``k``       — replica slots per key (1 = plain lookup),
    * ``bounded`` — lookup mode: the salted walk also rejects buckets at or
      above the prefetched load cap (fused k-replica × bounded-load),
    * ``diff``    — lookup mode: run under two epoch images in the same
      launch and emit the moved mask (k>1 diffs whole replica sets),
    * ``table``   — "dense" (full-width layout) or "packed" (the compact
      :mod:`repro.core.packing` layout of a ``packed=True`` image; any
      algorithm, any mode).
    """

    algo: str
    mode: str = "lookup"
    k: int = 1
    bounded: bool = False
    diff: bool = False
    table: str = "dense"

    def __post_init__(self):
        if self.algo not in ALGORITHMS:
            raise ValueError(f"unknown algo {self.algo!r}")
        if self.mode not in ("lookup", "walk"):
            raise ValueError(f"unknown mode {self.mode!r}")
        if self.k < 1:
            raise ValueError("k must be ≥ 1")
        if self.mode == "walk" and (self.k != 1 or self.diff or self.bounded):
            raise ValueError("walk mode is k=1, no diff, cap-implicit")
        if self.table not in ("dense", "packed"):
            raise ValueError(f"unknown table kind {self.table!r}")

    # -- derived operand layout ---------------------------------------------
    @property
    def table_names(self) -> tuple[str, ...]:
        if self.table == "packed":
            return PACKED_LAYOUT[self.algo][1]
        return IMAGE_LAYOUT[self.algo][1]

    @property
    def num_tables(self) -> int:
        return len(self.table_names)

    @property
    def num_scalars(self) -> int:
        return len(IMAGE_LAYOUT[self.algo][0])

    @property
    def has_load(self) -> bool:
        return self.bounded or self.mode == "walk"

    @property
    def num_outputs(self) -> int:
        if self.mode == "walk":
            return 3                      # b, chain, probe
        return 2 * self.k + 1 if self.diff else self.k


# ---------------------------------------------------------------------------
# Body and table-reader selection (the bodies live in core/jax_lookup.py)
# ---------------------------------------------------------------------------

def packed_reader(state, slot_b, slot_c):
    """``read(idx)`` over the packed Memento image (DESIGN.md §8.2), for
    :func:`repro.core.jax_lookup._memento_loop`: the uint32 ``state``
    bitmap short-circuits working buckets (bit = 1 → −1, no probe at all —
    the overwhelmingly common case), removed buckets probe the
    open-addressing slots by linear probing from
    ``fmix32(idx·GOLDEN32 + 5) & mask`` and stop only on EMPTY (−1):
    TOMBSTONE (−2) slots left by epoch-delta restores keep the chain
    alive.  Slot words may be dtype-narrowed; values widen to int32 at
    the gather."""
    nslots = slot_b.shape[0]  # power of two
    mask = _U(nslots - 1)

    def read(idx):
        w = gather1d(state, idx >> 5).astype(_U)
        working = ((w >> (idx & 31).astype(_U)) & _U(1)) == _U(1)
        h0 = (fmix32(idx.astype(_U) * _U(GOLDEN32) + _U(5)) & mask).astype(jnp.int32)

        def cond(state_):
            _, done, _ = state_
            return jnp.any(~done)

        def body(state_):
            pos, done, val = state_
            sb = gather1d(slot_b, pos).astype(jnp.int32)
            hit = sb == idx
            empty = sb == -1  # tombstones (−2) keep probing
            val = jnp.where(~done & hit,
                            gather1d(slot_c, pos).astype(jnp.int32), val)
            done = done | hit | empty
            pos = jnp.where(done, pos, (pos + 1) % nslots)
            return pos, done, val

        val0 = jnp.full_like(idx, -1, jnp.int32)
        _, _, val = jax.lax.while_loop(cond, body, (h0, working, val0))
        return val

    return read


def algo_body(op: EngineOp, keys, tables, scalars):
    """One-epoch lookup of ``op`` over its table layout — the one place a
    body and a table reader are picked, shared by every op mode and plane
    so plain lookups, replicas, bounded assignment and epoch diffs can
    never disagree about placement.  Dense tables (and every layout a
    packed image shares with the dense one) go through
    :func:`~repro.core.jax_lookup.lookup_dispatch`."""
    if op.table == "packed" and op.algo == "memento":
        return _memento_loop(keys, packed_reader(*tables), scalars[0], (),
                             lambda acc, work, rehash: ())[0]
    if op.table == "packed" and op.algo == "anchor":
        # packed A/K are dtype-narrowed; widen at the boundary
        return anchor_lookup(keys, tables[0].astype(jnp.int32),
                             tables[1].astype(jnp.int32), scalars[0])
    return lookup_dispatch(op.algo, keys, dict(zip(op.table_names, tables)),
                           scalars)


# ---------------------------------------------------------------------------
# Mode bodies (lane-synchronous, plane-agnostic)
# ---------------------------------------------------------------------------

def replica_body(keys, k, single_lookup, load=None, cap=None):
    """k distinct buckets per lane via the salted-re-lookup walk
    (DESIGN.md §4.1); with ``load``/``cap`` the walk ALSO rejects buckets
    at or above the cap — the fused bounded-replica op (§6).

    The candidate at salt 0 is the plain lookup, salt s ≥ 1 re-looks-up
    ``hash2(key, s)``; the per-lane salt counter advances on every try and
    carries across slots, so the walk is bit-identical to the host
    ``ReplicatedLookup.lookup_k_filtered`` (with the load-cap reject rule
    when bounded).  Unbounded slot 0 always accepts at salt 0, which is
    exactly the legacy ``replica_body``.  Lanes that exhaust
    ``REPLICA_SALT_CAP`` keep the plain-lookup bucket (probability
    ≤ ((k−1)/w)^CAP — see protocol.py; the host raises instead).
    Returns a list of k int32 arrays.
    """
    keys = jnp.asarray(keys).astype(_U)
    first = single_lookup(keys)
    if load is None:
        # unbounded slot 0 is the plain lookup, accepted outside the loop
        # (no wasted salted pass); k=1 is exactly the one-body legacy program
        if k == 1:
            return [first]
        outs: list = [first]
        salt = jnp.ones_like(keys, jnp.int32)
    else:
        outs = []  # bounded: slot 0 walks too (cap check on the primary)
        salt = jnp.zeros_like(keys, jnp.int32)
    for _ in range(k - len(outs)):
        prev = tuple(outs)

        def cond(state):
            salt, _slot, done = state
            return jnp.any(~done & (salt <= REPLICA_SALT_CAP))

        def body(state, prev=prev):
            salt, slot, done = state
            active = ~done & (salt <= REPLICA_SALT_CAP)
            cand = single_lookup(hash2(keys, salt))
            if load is not None:  # only bounded lanes can sit at salt 0
                cand = jnp.where(salt == 0, first, cand)
            bad = jnp.zeros_like(keys, jnp.bool_)
            for o in prev:
                bad = bad | (cand == o)
            if load is not None:
                bad = bad | (gather1d(load, cand) >= cap)
            ok = active & ~bad
            slot = jnp.where(ok, cand, slot)
            salt = jnp.where(active, salt + 1, salt)
            return salt, slot, done | ok

        salt, slot, _ = jax.lax.while_loop(
            cond, body, (salt, first, jnp.zeros_like(keys, jnp.bool_)))
        outs.append(slot)
    return outs


def chain_walk_body(chain, probe, pending, load, cap, single_lookup):
    """Walk each pending lane's deterministic rehash chain
    (``chain ← hash2(chain, probe)``) to the first bucket with
    ``load[b] < cap``; non-pending lanes are left untouched (DESIGN.md
    §4.2).  One step is exactly the host's ``probe += 1; chain =
    hash2(chain, probe); b = lookup(chain)``; lanes stop after the shared
    ``walk_probe_bound`` so an infeasible cap surfaces as an error in the
    batch driver instead of spinning.  Returns ``(b, chain, probe)``.
    """
    chain = jnp.asarray(chain).astype(_U)
    probe = jnp.asarray(probe).astype(jnp.int32)
    max_probe = walk_probe_bound(load.shape[0])
    b = single_lookup(chain)

    def cond(state):
        _chain, probe, b, active = state
        return jnp.any(active & (gather1d(load, b) >= cap)
                       & (probe < max_probe))

    def body(state):
        chain, probe, b, active = state
        step = active & (gather1d(load, b) >= cap) & (probe < max_probe)
        probe = jnp.where(step, probe + 1, probe)
        chain = jnp.where(step, hash2(chain, probe), chain)
        b = jnp.where(step, single_lookup(chain), b)
        return chain, probe, b, active

    chain, probe, b, _ = jax.lax.while_loop(
        cond, body, (chain, probe, b, jnp.asarray(pending)))
    return b, chain, probe


def _mode_outputs(op: EngineOp, blocks, tables, scalars, load, cap):
    """Run the configured op over one key block — the one op runner of
    the Pallas kernel, the jnp program and the sharded plane's shards.

    ``blocks`` is (keys,) in lookup mode, (chain, probe, pending) in walk
    mode; ``tables``/``scalars`` hold one epoch's operands, or two epochs
    concatenated when ``op.diff``.  Returns the output list: k buckets per
    epoch plus the bool moved mask (diff), or (b, chain uint32, probe)
    (walk).
    """
    nt, ns = op.num_tables, op.num_scalars
    if op.mode == "walk":
        chain, probe, pending = blocks
        b, chain, probe = chain_walk_body(
            chain, probe, pending != 0, load, cap,
            lambda kk: algo_body(op, kk, tables, scalars))
        return [b, chain, probe]
    keys = blocks[0]

    def epoch_outs(tabs, scals):
        return replica_body(keys, op.k,
                            lambda kk: algo_body(op, kk, tabs, scals),
                            load=load if op.bounded else None, cap=cap)

    outs = epoch_outs(tables[:nt], scalars[:ns])
    if op.diff:
        new = epoch_outs(tables[nt:2 * nt], scalars[ns:2 * ns])
        moved = jnp.zeros(keys.shape, jnp.bool_)
        for o, n_ in zip(outs, new):
            moved = moved | (o != n_)
        outs = outs + new + [moved]
    return outs


# ---------------------------------------------------------------------------
# Pallas plane: one launch per configuration
# ---------------------------------------------------------------------------

def _pad_rows(x, cols=128):
    k = x.shape[0]
    rows = max(1, -(-k // cols))
    padded = jnp.zeros((rows * cols,), x.dtype).at[:k].set(x)
    return padded.reshape(rows, cols), k


def _engine_kernel_factory(op: EngineOp):
    nb = 1 if op.mode == "lookup" else 3   # key-shaped input blocks
    nt = op.num_tables * (2 if op.diff else 1)

    def kernel(s_ref, *refs):
        blocks = [r[...].astype(_U) if i == 0 and op.mode == "lookup"
                  else r[...] for i, r in enumerate(refs[:nb])]
        pos = nb
        tables = [r[...].reshape(-1) for r in refs[pos:pos + nt]]
        pos += nt
        load = refs[pos][...].reshape(-1) if op.has_load else None
        pos += int(op.has_load)
        out_refs = refs[pos:]
        ns_total = op.num_scalars * (2 if op.diff else 1)
        scalars = [s_ref[i] for i in range(ns_total)]
        cap = s_ref[ns_total] if op.has_load else None
        if op.mode == "walk":
            blocks[0] = blocks[0].astype(_U)
        outs = _mode_outputs(op, blocks, tables, scalars, load, cap)
        # output blocks are int32: the bool moved mask and the uint32 walk
        # chain convert at the store
        for ref, o in zip(out_refs, outs):
            ref[...] = o.astype(jnp.int32)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("op", "block_rows", "interpret"))
def _engine_pallas(scalars, blocks2d, tables2d, *, op: EngineOp,
                   block_rows: int, interpret: bool):
    if not interpret and not mosaic_compiles(op):
        from .autotune import op_tag
        raise ValueError(
            f"the Pallas engine cannot compile {op_tag(op)} with Mosaic "
            f"(compiled Pallas serves {'/'.join(MOSAIC_ALGOS)} k=1 lookups "
            "and diffs); use plane='jnp' or plane='auto'")
    rows = blocks2d[0].shape[0]
    block_rows = min(block_rows, rows)
    grid = (-(-rows // block_rows),)
    blk = pl.BlockSpec((block_rows, 128), lambda i, s: (i, 0))
    tab_specs = [pl.BlockSpec(t.shape, lambda i, s: (0, 0)) for t in tables2d]

    return pl.pallas_call(
        _engine_kernel_factory(op),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[blk] * len(blocks2d) + tab_specs,
            out_specs=[blk] * op.num_outputs,
        ),
        out_shape=[jax.ShapeDtypeStruct(blocks2d[0].shape, jnp.int32)]
        * op.num_outputs,
        interpret=interpret,
    )(scalars, *blocks2d, *tables2d)


# ---------------------------------------------------------------------------
# jnp plane: one jitted program per configuration (traced operands, so one
# compile serves every epoch of a given shape).  It returns ``(outs,
# counts)``: ``outs`` as :func:`_mode_outputs` lays them out, and the plain
# dense Memento lookup counts its loops' work on the device, in the same
# program whether or not anyone reads it.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("op",))
def _engine_jnp(blocks, arrays, scalars, load, cap, *, op: EngineOp):
    if _counts_sweeps(op):
        b, sweeps, lanes, outer, longest = memento_lookup_counted(
            blocks[0], arrays[0], scalars[0])
        return (b,), jnp.stack([sweeps.astype(_U), lanes, outer.astype(_U), longest])
    return tuple(_mode_outputs(op, blocks, arrays, scalars, load, cap)), None


def _counts_sweeps(op: EngineOp) -> bool:
    """Does the jnp program of ``op`` return the Memento loop counts
    (:data:`MEMENTO_SWEEP_HISTOGRAMS`) beside its buckets?  The plain dense
    Memento lookup does; every other configuration returns ``None``."""
    return (op.algo == "memento" and op.table == "dense" and op.k == 1
            and op.mode == "lookup" and not op.bounded and not op.diff)


# ---------------------------------------------------------------------------
# Operand marshalling
# ---------------------------------------------------------------------------

def _op_table(image, table: str = "dense") -> str:
    """The table kind an image serves: a ``packed=True`` image always runs
    the packed configuration (callers never have to spell it)."""
    if getattr(image, "packed", False):
        if table not in ("dense", "packed"):
            raise ValueError(f"packed image cannot serve table={table!r}")
        return "packed"
    return table


def _image_tables(op: EngineOp, image):
    if (op.table == "packed") != bool(getattr(image, "packed", False)):
        raise ValueError(f"table={op.table!r} op cannot read a "
                         f"{'packed' if image.packed else 'dense'} image")
    return [jnp.asarray(image.arrays[name]) for name in op.table_names]


def _tables2d(tables):
    return [t.reshape(table_shape2d(t.shape[0])) for t in tables]


def _scalar_vec(op: EngineOp, images, cap):
    vec: list[int] = []
    for img in images:
        vec += image_scalar_vec(img)
    if op.has_load:
        vec.append(int(cap))
    return jnp.asarray(vec, jnp.int32)


def _jnp_operands(images):
    arrays, scalars = [], []
    for img in images:
        arrays += [jnp.asarray(img.arrays[n]) for n in image_table_names(img)]
        scalars += [jnp.asarray(s, jnp.int32) for s in image_scalar_vec(img)]
    return tuple(arrays), tuple(scalars)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def engine_lookup(keys, image, *, k: int = 1, load=None, cap: int | None = None,
                  plane: str = "pallas", table: str = "dense",
                  interpret: bool | None = None,
                  block_rows: int | None = None, registry=None):
    """The one batched lookup: keys [K] → int32 [K] (k=1) or [K, k].

    ``k>1`` returns salted k-replica sets (column 0 = the plain lookup);
    passing ``load``/``cap`` fuses the bounded-load rejection into the same
    single launch (every returned bucket has ``load < cap``, slot 0
    included).  Bit-identical to the host plane on ``variant="32"`` states.
    ``plane`` is "pallas", "jnp" or "auto" (the autotuner's pick for this
    op, batch and table size).  Telemetry lands on ``registry``, else on the process default; the
    Memento loop counts are queued on it (``observe_device``) for the
    caller to flush once it has fetched the result.
    """
    bounded = load is not None
    if bounded and cap is None:
        raise ValueError("bounded lookup needs a cap")
    table = _op_table(image, table)
    op = EngineOp(algo=image.algo, k=k, bounded=bounded, table=table)
    reg = registry if registry is not None else _obs_registry()
    with _dispatch_span(reg, op):
        keys = jnp.asarray(keys, dtype=_U)
        if plane == "auto":
            from . import autotune  # lazy: autotune ↔ engine would cycle
            plane = autotune.resolve_plane(op, int(keys.shape[0]),
                                           int(image.n))
        if plane == "jnp":
            arrays, scalars = _jnp_operands([image])
            outs, counts = _engine_jnp(
                (keys,), arrays, scalars,
                None if load is None else jnp.asarray(load, jnp.int32),
                None if cap is None else jnp.asarray(cap, jnp.int32), op=op)
            out = outs[0] if k == 1 else jnp.stack(outs).T
            if counts is not None and reg.active:
                reg.observe_device(MEMENTO_SWEEP_HISTOGRAMS, counts)
        elif plane != "pallas":
            raise ValueError(f"unknown plane {plane!r}")
        else:
            if interpret is None:
                interpret = default_interpret()
            tables = _image_tables(op, image)
            if bounded:
                tables.append(jnp.asarray(load, jnp.int32))
            keys2d, nk = _pad_rows(keys)
            outs = _engine_pallas(_scalar_vec(op, [image], cap), (keys2d,),
                                  tuple(_tables2d(tables)), op=op,
                                  block_rows=_resolve_block_rows(
                                      op, nk, int(image.n), block_rows),
                                  interpret=interpret)
            flat = [o.reshape(-1)[:nk] for o in outs]
            out = flat[0] if k == 1 else jnp.stack(flat).T
    if reg.active:
        reg.counter("engine.lookups").inc()
        _obs_dispatch(reg, int(keys.shape[0]))
    if bounded:
        # Slots are only accepted when distinct AND below the cap, so an
        # over-cap bucket OR a duplicate row means that lane exhausted the
        # salt budget (fewer than k distinct buckets below the cap) —
        # surface it like the host oracle instead of silently violating
        # either invariant.  The host sync this costs is deliberate: the
        # event is vanishingly rare on feasible caps (≤ ((k−1)/w)^CAP) but
        # a silent miss loses redundancy, and bounded callers consume the
        # result on host anyway.
        out_np = np.asarray(out)
        exhausted = bool((np.asarray(load)[out_np] >= cap).any())
        if not exhausted:
            for i in range(1, k):  # k(k−1)/2 vector compares, no sort
                for j in range(i):
                    if bool((out_np[:, i] == out_np[:, j]).any()):
                        exhausted = True
                        break
                if exhausted:
                    break
        if exhausted:
            raise RuntimeError(
                "replica salt budget exhausted (infeasible cap: fewer than "
                f"k={k} distinct working buckets below cap={cap})")
    return out


def replica_lookup(keys, image, k: int, *, plane: str = "jnp", **kw):
    """k-replica sets with a STABLE 2-D shape: keys [K] → int32 [K, k] even
    for k=1 (where :func:`engine_lookup` returns the flat classic op) —
    the convenience replica-set consumers and tests share instead of each
    hand-rolling the k=1 reshape."""
    out = engine_lookup(keys, image, k=k, plane=plane, **kw)
    return jnp.reshape(out, (-1, 1)) if k == 1 else out


@dataclass
class EngineDiff:
    """Per-key placement under two epochs plus the moved mask.

    ``old``/``new`` are int32 ``[K]`` for k=1 (the classic migration diff)
    or ``[K, k]`` replica sets for k>1; ``moved[key]`` is True when ANY
    slot differs between the epochs.
    """

    old: np.ndarray
    new: np.ndarray
    moved: np.ndarray

    @property
    def num_moved(self) -> int:
        return int(np.asarray(self.moved).sum())


def engine_diff(keys, old_image, new_image, *, k: int = 1,
                plane: str = "jnp", interpret: bool | None = None,
                block_rows: int | None = None) -> EngineDiff:
    """Fused epoch diff: lookup a key batch under two images in ONE program
    (jnp) / ONE launch (pallas, both epoch tables in VMEM).  ``k>1`` diffs
    whole replica sets — the movement planners' view of replica churn."""
    reg = _obs_registry()
    if not reg.active:
        return _engine_diff(keys, old_image, new_image, k=k, plane=plane,
                            interpret=interpret, block_rows=block_rows)
    op = EngineOp(algo=new_image.algo, k=k, diff=True,
                  table=_op_table(new_image))
    with _dispatch_span(reg, op):
        out = _engine_diff(keys, old_image, new_image, k=k, plane=plane,
                           interpret=interpret, block_rows=block_rows)
    reg.counter("engine.diffs").inc()
    reg.counter("engine.moved_keys").inc(out.num_moved)
    _obs_dispatch(reg, int(np.shape(keys)[0]))
    return out


def _engine_diff(keys, old_image, new_image, *, k: int = 1,
                 plane: str = "jnp", interpret: bool | None = None,
                 block_rows: int | None = None) -> EngineDiff:
    keys = jnp.asarray(keys, dtype=_U)
    if plane not in ("jnp", "pallas"):
        raise ValueError(f"unknown plane {plane!r}")
    if old_image.algo != new_image.algo:
        if plane == "pallas":
            raise ValueError("pallas epoch diff requires one algorithm "
                             f"({old_image.algo!r} != {new_image.algo!r})")
        # cross-algorithm migration: two dispatches, still one program
        op_old = EngineOp(algo=old_image.algo, k=k,
                          table=_op_table(old_image))
        op_new = EngineOp(algo=new_image.algo, k=k,
                          table=_op_table(new_image))
        ao, so = _jnp_operands([old_image])
        an, sn = _jnp_operands([new_image])
        old, _ = _engine_jnp((keys,), ao, so, None, None, op=op_old)
        new, _ = _engine_jnp((keys,), an, sn, None, None, op=op_new)
        old_np = _stack_np(old, k)
        new_np = _stack_np(new, k)
        moved = (old_np != new_np) if k == 1 else \
            (old_np != new_np).any(axis=1)
        return EngineDiff(old_np, new_np, np.asarray(moved))
    if bool(getattr(old_image, "packed", False)) != \
            bool(getattr(new_image, "packed", False)):
        raise ValueError("epoch diff needs both images in one layout")
    op = EngineOp(algo=old_image.algo, k=k, diff=True,
                  table=_op_table(old_image))
    if plane == "jnp":
        arrays, scalars = _jnp_operands([old_image, new_image])
        outs, _ = _engine_jnp((keys,), arrays, scalars, None, None, op=op)
        flat = [np.asarray(o) for o in outs]
    else:
        if interpret is None:
            interpret = default_interpret()
        tables = _image_tables(op, old_image) + _image_tables(op, new_image)
        keys2d, nk = _pad_rows(keys)
        outs = _engine_pallas(_scalar_vec(op, [old_image, new_image], None),
                              (keys2d,), tuple(_tables2d(tables)), op=op,
                              block_rows=_resolve_block_rows(
                                  op, nk, int(new_image.n), block_rows),
                              interpret=interpret)
        flat = [np.asarray(o.reshape(-1)[:nk]) for o in outs]
    return EngineDiff(_stack_np(flat[:k], k), _stack_np(flat[k:2 * k], k),
                      flat[2 * k].astype(bool))


def _stack_np(outs, k):
    return (np.asarray(outs[0]) if k == 1 else
            np.stack([np.asarray(o) for o in outs]).T)


def engine_chain_walk(chain, probe, pending, image, load, cap, *,
                      plane: str = "jnp", interpret: bool | None = None,
                      block_rows: int | None = None):
    """One bounded-load chain-walk step (the round primitive of
    :func:`bounded_assign`): advance every pending lane to the first bucket
    of its rehash chain with ``load[b] < cap``.  Returns numpy
    ``(b, chain, probe)``; non-pending lanes come back unchanged."""
    op = EngineOp(algo=image.algo, mode="walk", table=_op_table(image))
    reg = _obs_registry()
    with _dispatch_span(reg, op):
        chain = jnp.asarray(chain, dtype=_U)
        probe = jnp.asarray(probe, dtype=jnp.int32)
        pending = jnp.asarray(pending, dtype=jnp.bool_)
        load = jnp.asarray(load, dtype=jnp.int32)
        nk = chain.shape[0]
        if plane == "jnp":
            arrays, scalars = _jnp_operands([image])
            outs, _ = _engine_jnp((chain, probe, pending), arrays, scalars,
                                  load, jnp.asarray(cap, jnp.int32), op=op)
            b, ch, pr = (np.asarray(o) for o in outs)
        elif plane != "pallas":
            raise ValueError(f"unknown plane {plane!r}")
        else:
            if interpret is None:
                interpret = default_interpret()
            chain2d, _ = _pad_rows(chain)
            probe2d, _ = _pad_rows(probe)
            pending2d, _ = _pad_rows(pending.astype(jnp.int32))
            tables = _image_tables(op, image) + [load]
            outs = _engine_pallas(
                _scalar_vec(op, [image], cap), (chain2d, probe2d, pending2d),
                tuple(_tables2d(tables)), op=op,
                block_rows=_resolve_block_rows(op, nk, int(image.n),
                                               block_rows),
                interpret=interpret)
            b, ch, pr = (np.asarray(o.reshape(-1)[:nk]) for o in outs)
    if reg.active:
        reg.counter("engine.walk_steps").inc()
        _obs_dispatch(reg, nk)
    return b, ch.astype(np.uint32), pr


def bounded_assign(keys, image, load, cap: int, *, plane: str = "jnp",
                   interpret: bool | None = None):
    """Assign a key batch under the load cap on the device plane.

    Per round: (1) the walk configuration advances every pending key to the
    first non-full bucket of its deterministic rehash chain (one launch);
    (2) intra-batch races are resolved in key-index order
    (:func:`repro.core.bounded.accept_in_index_order`) — identical, round
    for round, to the numpy reference ``bounded_assign_ref``.  Returns
    ``(assignments int32 [m], new_load int32)``.
    """
    keys = np.asarray(keys, dtype=np.uint32)
    m = len(keys)
    chain = keys.copy()
    probe = np.zeros(m, np.int32)
    out = np.full(m, -1, np.int32)
    pending = np.ones(m, bool)
    load = np.asarray(load, dtype=np.int32).copy()
    rounds = 0
    while pending.any():
        b, chain, probe = engine_chain_walk(chain, probe, pending, image,
                                            load, cap, plane=plane,
                                            interpret=interpret)
        if (load[b[pending]] >= cap).any():  # probe bound exhausted
            raise RuntimeError("no bucket below capacity (infeasible cap: "
                               f"cap={cap} cannot hold the pending keys)")
        accept_idx = accept_in_index_order(b, pending, load, cap)
        out[accept_idx] = b[accept_idx]
        np.add.at(load, b[accept_idx], 1)
        pending[accept_idx] = False
        rounds += 1
    reg = _obs_registry()
    if reg.active:
        reg.counter("engine.bounded_assigns").inc()
        reg.counter("engine.bounded_rounds").inc(rounds)
    return out, load


def bounded_load_len(image) -> int:
    """Length of a load-word array covering ``image``'s bucket-id space —
    THE sizing rule for bounded ops (walk gathers + the fused bounded
    lookup index ``load`` by bucket id).  Anchor/Memento loads align with
    their bucket-indexed tables; Dx packs bits and Jump has no table, so
    their loads are sized to the (128-padded) id space directly."""
    from repro.core.protocol import round_up

    if image.algo == "anchor":
        return int(image.arrays["A"].shape[0])
    if image.algo == "memento":
        if getattr(image, "packed", False):  # bitmap covers 32 ids per word
            return 32 * int(image.arrays["state"].shape[0])
        return int(image.arrays["repl"].shape[0])
    return round_up(image.n)


def bounded_replica_sets(h, keys, k: int, load, cap: int) -> np.ndarray:
    """Numpy oracle for the fused bounded-replica op: the host salted walk
    (``lookup_k_filtered``) with the load-cap reject rule applied to EVERY
    slot (slot 0 included), so all k replicas land below the cap.  Ground
    truth for ``engine_lookup(..., k, load=, cap=)`` on both planes."""
    load = np.asarray(load)

    def reject(cand, chosen):
        return cand in chosen or load[cand] >= cap

    keys = np.asarray(keys)
    out = np.empty((len(keys), k), dtype=np.int32)
    for i, key in enumerate(keys):
        out[i] = h.lookup_k_filtered(int(key), k, reject, check_first=True)
    return out
