"""The benchmark's harness, driven by data.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; the
harness finds ``bench/configs/<config>.json`` (through the configuration's
``file``), ``bench/traffic/<traffic>.json``, the plain reference
``bench/references/<algo>.py`` and one reader ``bench/metrics/<metric>.py``
per per-layer metric, all by name.  A new cell, traffic mix or metric is
new files and a new ``BENCHMARK.json`` entry; nothing here changes.

One run: build the fleet through the program's public API
(``SessionRouter``, ``ch.remove``, one ``sync``), draw a pool of id
batches, warm every shape the loop uses (an event included, where the
traffic has events), then run the traffic's closed loop for the window,
with the profiler on for the first ``trace_seconds`` of a traced run.
Every answer of the window is then compared with the reference (see
:func:`check`).  The seed draws the order of the pool's batches, the
events and the checked samples, and draws the fleet and the pool too,
and the victims of the membership events (uniform among the working
buckets), unless the files fix those (``fleet_seed``, ``ids_seed``,
``events_seed``, ``order_seed``) so that every seed does the same work.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import shutil
import tempfile
import time
import traceback
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]

#: seed streams: each part of a run draws from its own
FLEET, IDS, EVENTS, CHECK, ORDER = 1, 2, 3, 4, 5


def rng(seed: int, stream: int) -> np.random.Generator:
    """The generator of one stream of one seed; any whole number is a seed."""
    return np.random.default_rng(np.random.SeedSequence([seed % 2**64, stream]))


def session_ids(gen: np.random.Generator, shape) -> np.ndarray:
    """Uniform 64-bit session ids."""
    return gen.integers(0, np.iinfo(np.uint64).max, size=shape, dtype=np.uint64)


def population_seed(spec: dict, key: str, seed: int) -> int:
    """The seed a population (the fleet's removals, the pool of ids) is
    drawn from: fixed where the file names one, so that every run does the
    same work and only its order changes; else the run's own seed."""
    return seed if spec.get(key) is None else spec[key]


class Schedule:
    """The order the pool's batches are sent in: each pass over the pool
    is a permutation of it drawn from the run's seed."""

    def __init__(self, size: int, gen: np.random.Generator):
        self.size, self.gen, self.slots = size, gen, []

    def __getitem__(self, i: int) -> int:
        while i >= len(self.slots):
            self.slots += self.gen.permutation(self.size).tolist()
        return self.slots[i]


# ---------------------------------------------------------------------------
# Finding a cell's files
# ---------------------------------------------------------------------------

@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list
    per_layer: list
    root: Path


def _load_module(path: Path):
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: Path = ROOT, overrides: dict | None = None) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files.
    ``overrides`` (``{"config": {...}, "traffic": {...}}``) replaces
    numbers, for runs at a small size."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    wl = next((w for w in spec["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}")
    cfg_entry = next(c for c in spec["configs"] if c["name"] == wl["config"])
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{wl['traffic']}.json").read_text())
    overrides = overrides or {}
    config.update(overrides.get("config", {}))
    traffic.update(overrides.get("traffic", {}))

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return Cell(name, config, traffic, wl["chips"], mine(spec["end_to_end"]),
                mine(spec["per_layer"]), root)


def reference_module(cell: Cell):
    return _load_module(cell.root / "bench" / "references" / f"{cell.config['algo']}.py")


def metric_reader(cell: Cell, name: str):
    return _load_module(cell.root / "bench" / "metrics" / f"{name}.py").read


# ---------------------------------------------------------------------------
# What a run records
# ---------------------------------------------------------------------------

@dataclass
class Event:
    victim: int
    epoch: int           # the epoch this event starts
    t_call: float
    t_served: float | None = None


class History:
    """Every batch and membership event of a run, with the epoch each
    batch was sent on: the number of events before it."""

    def __init__(self, victims):
        self.victims = victims
        self.epoch = 0
        self.events: list[Event] = []
        self.batches: list = []   # (slot, epoch, t0, t1, out)
        self.recording = False
        self._pending: Event | None = None

    def batch(self, slot, epoch, t0, t1, out) -> None:
        ev = self._pending
        if ev is not None and epoch >= ev.epoch:
            ev.t_served, self._pending = t1, None
        if self.recording:
            self.batches.append((slot, epoch, t0, t1, out))

    def event(self, router, span) -> None:
        """Remove the next victim through ``fail_replica``."""
        victim = next(self.victims)
        with span("bench.fail_replica"):
            t = time.perf_counter()
            router.fail_replica(victim)
        self.epoch += 1
        self._pending = Event(victim, self.epoch, t)
        self.events.append(self._pending)


def victims(n: int, removed, gen: np.random.Generator):
    """Membership events' victims: working buckets in a random order."""
    gone = set(removed)
    return (b for b in gen.permutation(n).tolist() if b not in gone)


def closed_loop(router, pool, order, every, hist, stop, tick, span) -> None:
    """One client: ``route_batch`` on the next pool batch, wait for the
    answer, and after every ``every``-th batch one ``fail_replica``."""
    i = 0
    while True:
        slot = order[i]
        with span("bench.route_batch"):
            t0 = time.perf_counter()
            out = router.route_batch(pool[slot])
            t1 = time.perf_counter()
        hist.batch(slot, hist.epoch, t0, t1, out)
        i += 1
        tick()
        if stop(i):
            return
        if every and i % every == 0:
            hist.event(router, span)


def stream_loop(router, pool, order, every, hist, stop, tick, span) -> None:
    """One stream through ``route_stream``: the router keeps one batch in
    flight; a batch is timed from when it is handed over until its answer
    comes back.  Events run between batches, from the feeding side."""
    fed = []

    def feed():
        i = 0
        while True:
            slot = order[i]
            fed.append((slot, hist.epoch, time.perf_counter()))
            yield pool[slot]
            i += 1
            if stop(i):
                return
            if every and i % every == 0:
                hist.event(router, span)

    for j, out in enumerate(router.route_stream(feed())):
        slot, epoch, t0 = fed[j]
        hist.batch(slot, epoch, t0, time.perf_counter(), out)
        tick()


LOOPS = {"route_batch": closed_loop, "route_stream": stream_loop}


def build(cell: Cell, seed: int, registry):
    """The fleet, through the program's API: the router, the seeded
    removals, one sync.  Returns the router and the removed buckets."""
    from repro.serve.router import SessionRouter

    cfg = cell.config
    n = cfg["n_buckets"]
    router = SessionRouter(n, algo=cfg["algo"], sync_mode=cfg["sync_mode"],
                           registry=registry)
    k = int(round(cfg["removed_fraction"] * n))
    fleet = rng(population_seed(cfg, "fleet_seed", seed), FLEET)
    removed = fleet.permutation(n)[:k].tolist()
    remove = router.ch.remove
    for b in removed:
        remove(b)
    router.image_store().sync()
    if cfg["plane"] == "sharded":
        plane = router.sharded_plane()
        if plane.num_shards != cell.chips:
            raise RuntimeError(f"the plane spans {plane.num_shards} devices, "
                               f"the cell asks for {cell.chips}")
    return router, removed


# ---------------------------------------------------------------------------
# Tracing a part of the window
# ---------------------------------------------------------------------------

class TraceWindow:
    """The profiler over the first ``seconds`` of the window; the window
    span ``bench.window`` marks the part that was traced."""

    def __init__(self, seconds: float | None):
        self.seconds = seconds
        self.dir = tempfile.mkdtemp(prefix="bench-trace-") if seconds else None
        self._ann = None

    def start(self) -> None:
        if self.dir:
            from . import tracing
            tracing.start(self.dir)

    def open(self) -> None:
        if self.dir:
            import jax
            from .tracing import WINDOW_SPAN
            self._ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            self._ann.__enter__()
            self._t0 = time.perf_counter()

    def tick(self) -> None:
        if self._ann is not None and time.perf_counter() - self._t0 >= self.seconds:
            self.close()

    def close(self) -> None:
        if self._ann is not None:
            from . import tracing
            self._ann.__exit__(None, None, None)
            self._ann = None
            tracing.stop()

    def read(self) -> dict | None:
        if not self.dir:
            return None
        from . import tracing
        try:
            return tracing.reduce(tracing.extract(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _span(on: bool):
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


# ---------------------------------------------------------------------------
# The comparison with the reference
# ---------------------------------------------------------------------------

#: the numbers compared, each with its limit: all exact
LIMITS = {"wrong_keys": 0, "disrupted_keys": 0, "unstable_keys": 0,
          "errors": 0, "unchecked": 0}


def check(cell: Cell, hist: History, pool, removed, seed: int, errors: int):
    """Compare the window's answers with the reference.

    * ``wrong_keys`` — keys whose bucket differs from the reference's, over
      every batch of the checked epochs: the first and last of the window
      and ``check_epochs`` more drawn from the seed; of each batch every
      key, or ``check_keys`` positions drawn from the seed;
    * ``disrupted_keys`` — across every event of the window, keys that
      moved though their bucket was not removed, or stayed though it was;
    * ``unstable_keys`` — keys whose bucket differs between two batches of
      the same ids in the same epoch;
    * ``errors`` — exceptions the timed path raised;
    * ``unchecked`` — 1 where no batch of the window could be compared.

    Returns ``(checks, failed_batches)``.
    """
    ref_mod = reference_module(cell)
    t = cell.traffic
    gen = rng(seed, CHECK)
    groups = defaultdict(list)
    for slot, epoch, _t0, _t1, out in hist.batches:
        groups[(epoch, slot)].append(out)
    epochs = sorted({e for e, _ in groups})
    unstable = sum(int((o != outs[0]).sum())
                   for outs in groups.values() for o in outs[1:])
    disrupted = 0
    for e in epochs:
        victim = hist.events[e].victim if e < len(hist.events) else None
        for s in range(len(pool)):
            before, after = groups.get((e, s)), groups.get((e + 1, s))
            if victim is not None and before and after:
                b, a = before[-1], after[0]
                disrupted += int(((a != b) != (b == victim)).sum())
    chosen = set(epochs[:1] + epochs[-1:])
    middle = epochs[1:-1]
    if middle and t["check_epochs"]:
        chosen |= set(gen.choice(middle, min(t["check_epochs"], len(middle)),
                                 replace=False).tolist())
    n_keys = t["batch_keys"]
    pos = (np.sort(gen.choice(n_keys, t["check_keys"], replace=False))
           if 0 < t["check_keys"] < n_keys else slice(None))
    ref = ref_mod.Reference(cell.config["n_buckets"])
    for b in removed:
        ref.remove(b)
    applied, wrong, failed, checked = 0, 0, 0, 0
    for e in sorted(chosen):
        while applied < e:
            ref.remove(hist.events[applied].victim)
            applied += 1
        for s in range(len(pool)):
            if (e, s) not in groups:
                continue
            want = ref.lookup(ref_mod.key_to_u32(pool[s][pos]))
            for out in groups[(e, s)]:
                bad = int((np.asarray(out)[pos] != want).sum())
                wrong += bad
                failed += bad > 0
                checked += 1
    checks = {"wrong_keys": wrong, "disrupted_keys": disrupted,
              "unstable_keys": unstable, "errors": errors,
              "unchecked": int(checked == 0)}
    return checks, failed + errors


# ---------------------------------------------------------------------------
# End-to-end metrics, from the window's own clock
# ---------------------------------------------------------------------------

def _p95_ms(xs) -> float | None:
    return float(np.percentile(np.asarray(xs) * 1e3, 95)) if len(xs) else None


def end_to_end(hist: History, t_open: float, setup_s: float, batch_keys: int) -> dict:
    t_close = max(b[3] for b in hist.batches)
    served = [e.t_served - e.t_call for e in hist.events
              if e.t_served is not None and e.t_call >= t_open]
    return {
        "keys_per_s": len(hist.batches) * batch_keys / (t_close - t_open),
        "batch_p95_ms": _p95_ms([b[3] - b[2] for b in hist.batches]),
        "event_to_serve_p95_ms": _p95_ms(served),
        "setup_s": setup_s,
    }


# ---------------------------------------------------------------------------
# Per-layer metrics: the readers' view of a traced run
# ---------------------------------------------------------------------------

@dataclass
class Context:
    """What a per-layer reader may read: the cell, the device, the
    window's telemetry sums and the reduced trace (``None`` untraced)."""

    cell: Cell
    device_kind: str
    devices: int
    obs: dict       # histogram name -> (count, sum) over the window
    trace: dict | None

    def hist(self, name: str) -> tuple[int, float]:
        return self.obs.get(name, (0, 0.0))


def _obs_sums(registry) -> dict:
    out = defaultdict(lambda: [0, 0.0])
    for key, h in registry.snapshot()["histograms"].items():
        acc = out[key.split("{")[0]]
        acc[0] += h["count"]
        acc[1] += h["sum"]
    return dict(out)


def _obs_window(before: dict, after: dict) -> dict:
    return {k: (c - before.get(k, (0, 0.0))[0], s - before.get(k, (0, 0.0))[1])
            for k, (c, s) in after.items()}


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float, plant=None) -> dict:
    """One run of ``cell`` on ``devices``; returns the result line's dict.
    ``plant`` is a context manager held around everything the program
    does (the control runs the program with it)."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    from repro.obs.metrics import MetricRegistry

    enable_compile_cache()
    t = cell.traffic
    loop = LOOPS[t["entry"]]
    registry = MetricRegistry() if trace else None
    span = _span(trace)
    pool = session_ids(rng(population_seed(t, "ids_seed", seed), IDS),
                       (t["pool_batches"], t["batch_keys"]))
    order = rng(population_seed(t, "order_seed", seed), ORDER)
    tw = TraceWindow(min(seconds, t["trace_seconds"]) if trace else None)
    errors = 0
    with plant or contextlib.nullcontext():
        router, removed = build(cell, seed, registry)
        hist = History(victims(cell.config["n_buckets"], removed,
                               rng(population_seed(t, "events_seed", seed), EVENTS)))
        # warm-up: every shape of the loop, a membership event included
        loop(router, pool, Schedule(len(pool), order),
             2 if t["event_every"] else 0, hist, lambda i: i >= 4, lambda: None, span)
        tw.start()
        obs0 = _obs_sums(registry) if trace else {}
        hist.recording = True
        t_open = time.perf_counter()
        setup_s = t_open - t_start
        deadline = t_open + seconds
        tw.open()
        try:
            loop(router, pool, Schedule(len(pool), order), t["event_every"],
                 hist, lambda i: time.perf_counter() >= deadline, tw.tick, span)
        except Exception:  # noqa: BLE001 - a failed window is a result
            traceback.print_exc()
            errors = 1
        tw.close()
        obs = _obs_window(obs0, _obs_sums(registry)) if trace else {}
        memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                          for d in devices)
        del router
    red = tw.read()
    checks, failed = check(cell, hist, pool, removed, seed, errors)
    correct = all(checks[k] <= LIMITS[k] for k in checks)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(hist.batches) + errors,
              "failed": failed, "metrics": {}, "device": device}
    if trace:
        busy = red["busy_s"] if red else {}
        device["busy_s"] = sum(busy.values()) / len(busy) if busy else 0.0
        device["window_s"] = red["window_s"] if red else 0.0
        ctx = Context(cell, dev.device_kind, cell.chips, obs, red)
        for m in cell.per_layer:
            v = metric_reader(cell, m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        if red:
            from .tracing import breakdown
            result["breakdown"] = breakdown(red)
    elif hist.batches:
        values = end_to_end(hist, t_open, setup_s, t["batch_keys"])
        for m in cell.end_to_end:
            if values.get(m["name"]) is not None:
                result["metrics"][m["name"]] = {"value": values[m["name"]],
                                                "unit": m["unit"]}
    result["checks"] = {k: {"value": v, "limit": LIMITS[k]}
                        for k, v in checks.items()}
    return result
