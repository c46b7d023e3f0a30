"""Chip smoke test: the router → store → engine → replication path on a TPU.

    python chip_smoke.py              # one chip, every phase below
    python chip_smoke.py --chips 4    # the sharded plane over four chips, alone

One process drives every chip it uses and starts no other.  It runs the
system's main path through its user-facing entry points at the paper's
largest fleet, 10⁶ buckets (Coluzzi et al. §VIII), with 2²⁰-key batches:

1. stable fleet — ``SessionRouter.route_batch`` over a 10⁶-bucket Memento,
   every key compared with the vectorised host reference;
2. one-shot removal — 90% of the buckets removed at random, store synced,
   routed again, checked against the scalar host lookup on a seeded sample,
   minimal disruption checked with ``migration_diff``, then single removals
   through ``fail_replica`` (the O(changed-words) delta path), after which
   only the keys of the removed buckets may have moved;
3. every registry algorithm through ``engine_lookup``/``engine_diff`` on the
   plane the chip dispatches for it, and on the compiled Pallas plane too
   where Mosaic compiles the op (the two must agree);
4. one in-process follower (``ReplicationGroup``, Pallas delta apply) fed
   the frames of phase 2, whose image fingerprint must equal the leader's
   and whose lookups must equal the leader's routes.

Scalar host references run on one worker thread while the device works.

``--chips 4`` runs only the mesh-sharded serving plane over every chip,
compared with the single-device engine, and ``route_stream`` across one
membership event.  Timings printed on the way are smoke timings (host
clock, first call includes compilation), not metrics.  The last line is
the JSON result; it is printed only when every check passed.  Without a
TPU the script exits non-zero before doing anything.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

N_BUCKETS = 1_000_000          # the paper's largest fleet
N_KEYS = 1 << 20               # keys per batch
N_BATCHES = 3                  # stable-fleet batches
SAMPLE = 65_536                # scalar host-reference sample
ONESHOT_FRACTION = 0.9         # the paper's one-shot removal
SINGLE_REMOVALS = 4            # delta-path removals after the one-shot
CHURN_FRACTION = 0.1           # per-algorithm phase: removed before the diff
#: AnchorHash's host constructor grows faster than linearly (about 30 s at
#: 2¹⁸ buckets, minutes at 10⁶), so its phase runs at the largest
#: power of two whose setup stays under about a minute.
ANCHOR_BUCKETS = 1 << 18


class Smoke:
    """Collects check results; a failed check fails the run, not the phase."""

    def __init__(self):
        self.failures: list[str] = []

    def check(self, what: str, ok: bool, detail: str = "") -> None:
        print(f"  [{'ok' if ok else 'FAIL'}] {what}"
              + (f" ({detail})" if detail else ""), flush=True)
        if not ok:
            self.failures.append(what)

    def mismatches(self, what: str, got, want) -> None:
        import numpy as np

        got, want = np.asarray(got), np.asarray(want)
        bad = (int((got != want).sum()) if got.shape == want.shape
               else max(got.size, want.size))
        self.check(f"{what}: mismatches={bad} of {want.size}", bad == 0)

    def phase(self, name: str, fn, *args):
        """Run one phase; an exception fails the run and is printed."""
        print(f"== {name}", flush=True)
        t0 = time.perf_counter()
        try:
            return fn(self, *args)
        except Exception:  # noqa: BLE001 - report every phase, then fail
            traceback.print_exc()
            self.failures.append(f"{name}: raised")
            return None
        finally:
            print(f"  smoke timing: phase {name} {time.perf_counter() - t0:.3f} s",
                  flush=True)


def _timed(label: str, fn):
    t0 = time.perf_counter()
    out = fn()
    print(f"  smoke timing: {label} {time.perf_counter() - t0:.3f} s", flush=True)
    return out


def host_lookup(h, keys):
    """The host reference: vectorised where one exists (Jump, and Memento
    with nothing removed — exactly JumpHash), else the scalar lookup."""
    import numpy as np
    from repro.core.jump import np_jump32

    if h.name == "jump" or (h.name == "memento" and not h.R):
        return np_jump32(keys, h.size)
    return np.asarray([h.lookup(int(k)) for k in keys], np.int32)


def session_ids(rng, n: int):
    import numpy as np
    return rng.integers(0, np.iinfo(np.uint64).max, size=n, dtype=np.uint64)


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------

def phase_stable(s: Smoke, rng, n: int, n_keys: int, batches: int):
    from repro.core.hashing import np_key_to_u32
    from repro.core.jump import np_jump32
    from repro.serve.router import SessionRouter

    router = _timed("router + 10⁶-bucket store build",
                    lambda: SessionRouter(n, algo="memento"))
    store = router.image_store()
    print(f"  fleet: {n} buckets, device image "
          f"{sum(a.nbytes for a in store.image().arrays.values())} bytes "
          f"(capacity {store.capacity}), {n_keys} keys per batch")
    ids = out = None
    for i in range(batches):
        ids = session_ids(rng, n_keys)
        out = _timed(f"route_batch {i} ({n_keys} keys"
                     + (", includes compile)" if i == 0 else ")"),
                     lambda: router.route_batch(ids))
        s.mismatches(f"stable batch {i} vs host np_jump32",
                     out, np_jump32(np_key_to_u32(ids), n))
    return router, ids, out


def phase_oneshot(s: Smoke, pool, rng, router, ids, before, sample: int,
                  fraction: float, singles: int, group):
    """The scalar host references run on ``pool`` while the device routes;
    the host fleet is not touched until each has been collected."""
    import numpy as np
    from repro.core.hashing import np_key_to_u32

    ch, store = router.ch, router.image_store()
    keys = np_key_to_u32(ids)
    victims = rng.permutation(ch.size)[: int(fraction * ch.size)]
    _timed(f"host removal of {len(victims)} buckets",
           lambda: [ch.remove(int(b)) for b in victims])
    ref = pool.submit(host_lookup, ch, keys[:sample])
    st = _timed("store sync", store.sync)
    print(f"  sync: mode={st.mode} events={st.events} words={st.words} "
          f"epoch={st.epoch}; working={ch.working}")
    after = _timed("route_batch after the one-shot removal",
                   lambda: router.route_batch(ids))
    d = _timed("migration_diff", lambda: store.migration_diff(keys))
    removed = np.isin(d.old, victims)
    print(f"  moved keys: {d.num_moved} of {len(keys)}")
    s.mismatches("migration_diff old epoch vs pre-removal route_batch",
                 d.old, before)
    s.mismatches("migration_diff new epoch vs route_batch", d.new, after)
    s.check("minimal disruption: a key moved iff its bucket was removed",
            bool((np.asarray(d.moved) == removed).all()),
            f"moved={d.num_moved}, on removed buckets={int(removed.sum())}")
    s.mismatches(f"one-shot route_batch vs scalar host lookup "
                 f"({sample}-key seeded sample)",
                 after[:sample], _timed("wait for the host reference",
                                        ref.result))
    group.publish()
    # single removals of buckets that hold keys of the batch, each synced
    # by an O(changed-words) delta and published to the follower
    gone = []
    for i in range(singles):
        victim = int(after[np.isin(after, gone, invert=True)][0])
        _timed(f"fail_replica({victim}) + sync",
               lambda: router.fail_replica(victim))
        st = store.last_sync
        s.check(f"single removal {i}: synced by delta", st.mode == "delta",
                f"mode={st.mode} words={st.words} epoch={st.epoch}")
        gone.append(victim)
        group.publish()
    ref = pool.submit(host_lookup, ch, keys[:sample])
    final = _timed("route_batch after the single removals",
                   lambda: router.route_batch(ids))
    moved = final != after
    s.check(f"single removals: a key moved iff its bucket was one of {gone}",
            bool((moved == np.isin(after, gone)).all()),
            f"moved={int(moved.sum())}")
    s.mismatches(f"after single removals: route_batch vs scalar host lookup "
                 f"({sample}-key sample)",
                 final[:sample], _timed("wait for the host reference",
                                        ref.result))
    return final


def _remove_fraction(h, rng, fraction: float):
    """Remove ``fraction`` of the working buckets of ``h``: the tail for
    LIFO-only algorithms, a seeded random choice otherwise.  Returns the
    victims."""
    import numpy as np
    from repro.core import ALGORITHM_REGISTRY

    k_rm = int(fraction * h.working)
    if ALGORITHM_REGISTRY[h.name].lifo_only:
        victims = np.arange(h.size - k_rm, h.size)
        order = victims[::-1]
    else:
        victims = order = rng.permutation(
            np.fromiter(h.working_set(), np.int64))[:k_rm]
    for b in order:
        h.remove(int(b))
    return victims


def phase_algorithms(s: Smoke, pool, rng, n: int, anchor_n: int, n_keys: int,
                     sample: int, fraction: float):
    """Each algorithm's scalar host references run on ``pool`` while the
    device compiles and runs the same epoch."""
    import numpy as np
    from repro.core import ALGORITHMS, make_hash
    from repro.kernels.autotune import resolve_plane
    from repro.kernels.engine import (EngineOp, engine_diff, engine_lookup,
                                      mosaic_compiles)

    keys = rng.integers(0, 2**32, size=n_keys, dtype=np.uint32)
    for algo in ALGORITHMS:
        size = anchor_n if algo == "anchor" else n
        h = _timed(f"{algo}: host build at {size} buckets",
                   lambda: make_hash(algo, size, variant="32"))
        old = h.device_image()
        ref_old = pool.submit(host_lookup, h, keys[:sample])
        op = EngineOp(algo)
        plane = resolve_plane(op, n_keys, size)
        planes = [plane]
        if mosaic_compiles(op):
            planes.append("jnp" if plane == "pallas" else "pallas")
        out_old = {p: np.asarray(_timed(
            f"{algo}: engine_lookup plane={p} (includes compile)",
            lambda: engine_lookup(keys, old, plane=p))) for p in planes}
        host_old = ref_old.result()  # h is left alone until here
        victims = _remove_fraction(h, rng, fraction)
        new = h.device_image()
        ref_new = pool.submit(host_lookup, h, keys[:sample])
        print(f"  {algo}: {size} buckets, {len(victims)} removed, "
              f"dispatched plane={plane}, compared planes={planes}")
        diffs = {}
        for p in planes:
            d = diffs[p] = _timed(
                f"{algo}: engine_diff plane={p} (includes compile)",
                lambda: engine_diff(keys, old, new, plane=p))
            s.mismatches(f"{algo} {p}: old epoch vs host ({sample} keys)",
                         out_old[p][:sample], host_old)
            s.mismatches(f"{algo} {p}: engine_diff old vs engine_lookup",
                         d.old, out_old[p])
            removed = np.isin(d.old, victims)
            s.check(f"{algo} {p}: a key moved iff its bucket was removed",
                    bool((np.asarray(d.moved) == removed).all()),
                    f"moved={d.num_moved}")
        out_new = {p: np.asarray(engine_lookup(keys, new, plane=p))
                   for p in planes}
        host_new = _timed(f"{algo}: wait for the host reference",
                          ref_new.result)
        for p in planes:
            s.mismatches(f"{algo} {p}: new epoch vs host ({sample} keys)",
                         out_new[p][:sample], host_new)
            s.mismatches(f"{algo} {p}: engine_diff new vs engine_lookup",
                         diffs[p].new, out_new[p])
        if len(planes) == 2:
            a, b = planes
            s.mismatches(f"{algo}: {a} == {b} (lookup old)",
                         out_old[a], out_old[b])
            s.mismatches(f"{algo}: {a} == {b} (lookup new)",
                         out_new[a], out_new[b])
            s.mismatches(f"{algo}: {a} == {b} (diff moved)",
                         diffs[a].moved, diffs[b].moved)


def phase_replication(s: Smoke, group, store, keys, routed):
    import numpy as np
    from repro.core.protocol import image_fingerprint
    from repro.kernels.engine import engine_lookup

    group.publish()
    fol = group.followers[0]
    lead = store.image()
    print(f"  follower: epoch {fol.epoch} (leader {lead.epoch}), frames "
          f"applied={fol.frames_applied} snapshots={fol.snapshots} "
          f"deltas={fol.deltas}; wire frames={group.stats.frames} "
          f"bytes={group.stats.total_bytes}")
    s.check("follower fingerprint == leader fingerprint",
            group.converged(lead),
            f"{fol.fingerprint()} vs {image_fingerprint(lead)}")
    s.mismatches(f"follower engine_lookup vs leader route_batch "
                 f"({len(keys)} keys)",
                 np.asarray(engine_lookup(keys, fol.image(), plane="jnp")),
                 routed)


def run_one_chip(s: Smoke, seed: int, *, n: int = N_BUCKETS,
                 n_keys: int = N_KEYS, batches: int = N_BATCHES,
                 sample: int = SAMPLE, anchor_n: int = ANCHOR_BUCKETS) -> None:
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np
    from repro.core.hashing import np_key_to_u32
    from repro.launch.replicate import ReplicationGroup

    rng = np.random.default_rng(seed)
    got = s.phase("stable fleet", phase_stable, rng, n, n_keys, batches)
    if got is None:
        return
    router, ids, routed = got
    # the follower subscribes before the churn: the one-shot removal and
    # each single removal reach it as published frames, applied with the
    # Pallas delta kernel
    group = ReplicationGroup(router.ch, 1, plane="pallas")
    group.publish()
    # one worker thread computes scalar host references while the device
    # works (the device wait releases the GIL)
    with ThreadPoolExecutor(max_workers=1) as pool:
        final = s.phase("one-shot 90% removal", phase_oneshot, pool, rng,
                        router, ids, routed, sample, ONESHOT_FRACTION,
                        SINGLE_REMOVALS, group)
        s.phase("every registry algorithm", phase_algorithms, pool, rng, n,
                anchor_n, n_keys, sample, CHURN_FRACTION)
    if final is not None:
        s.phase("replication", phase_replication, group,
                router.image_store(), np_key_to_u32(ids[:sample]),
                final[:sample])


# ---------------------------------------------------------------------------
# Four chips: the sharded serving plane
# ---------------------------------------------------------------------------

def phase_sharded(s: Smoke, seed: int, n: int, n_keys: int, chips: int):
    import jax
    import numpy as np
    from repro.core.hashing import np_key_to_u32
    from repro.kernels.engine import engine_lookup
    from repro.serve.router import SessionRouter

    rng = np.random.default_rng(seed)
    router = SessionRouter(n, algo="memento")
    store = router.image_store()
    plane = router.sharded_plane()
    s.check(f"lookup mesh spans {chips} devices",
            plane.num_shards == chips == len(jax.devices()),
            f"mesh {dict(zip(plane.mesh.axis_names, plane.mesh.devices.shape))}")
    ids = session_ids(rng, n_keys)
    keys = np_key_to_u32(ids)
    single = np.asarray(_timed("single-device engine_lookup (includes compile)",
                               lambda: engine_lookup(keys, store.image(),
                                                     plane="jnp")))
    out, nk = _timed("sharded lookup dispatch (includes compile)",
                     lambda: plane.lookup_async(keys))
    full = np.empty(out.shape, np.int32)
    per_device = {}
    for shard in out.addressable_shards:
        data = np.asarray(shard.data)
        full[shard.index] = data
        per_device[str(shard.device)] = int(data.size)
    print(f"  keys per device (padded): {per_device}")
    s.check(f"keys land on all {chips} devices",
            len(per_device) == chips and min(per_device.values()) > 0)
    s.mismatches("sharded plane vs single-device engine", full[:nk], single)
    _timed("sharded lookup (steady)", lambda: plane.lookup(keys))
    _timed("single-device engine_lookup (steady)",
           lambda: np.asarray(engine_lookup(keys, store.image(), plane="jnp")))

    victim = int(single[0])  # a bucket that holds keys of the batch

    def batches():
        yield ids
        router.fail_replica(victim)  # membership event between batches
        yield ids

    out0, out1 = _timed("route_stream, 2 batches around one removal",
                        lambda: list(router.route_stream(batches())))
    s.mismatches("route_stream batch 0 vs single-device engine", out0, single)
    after = np.asarray(engine_lookup(keys, store.image(), plane="jnp"))
    s.mismatches("route_stream batch 1 vs single-device engine (new epoch)",
                 out1, after)
    moved = out0 != out1
    s.check(f"route_stream: a key moved iff it was on bucket {victim}",
            bool((moved == (out0 == victim)).all()),
            f"moved={int(moved.sum())}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded plane over four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every key batch and removal")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}")

    s = Smoke()
    t0 = time.perf_counter()
    if args.chips == 4:
        s.phase("sharded plane over 4 chips", phase_sharded, args.seed,
                N_BUCKETS, N_KEYS, 4)
    else:
        run_one_chip(s, args.seed)
    print(f"smoke timing: total {time.perf_counter() - t0:.3f} s")
    if s.failures:
        print(f"chip_smoke: {len(s.failures)} check(s) failed: {s.failures}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                              "kind": dev.device_kind,
                                              "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
