"""Engine autotuner — tuned tiles and plane choice per EngineOp (DESIGN.md §8.1).

The engine runs every configuration at a hard-coded ``(8, 128)`` tile and
leaves jnp-vs-Pallas to the caller.  This module searches, per
:class:`~repro.kernels.engine.EngineOp`, over ``block_rows`` (the tile
height) and the execution plane across a (batch size × table size) grid,
and persists the winners in a deterministic JSON cache
(``benchmarks/results/TUNE_engine.json``) that the engine consults at
dispatch time:

* **grid key** — ``backend/op-tag/keys<2^i>/n<2^j>``: batch and table
  sizes bucket to the next power of two, so one measurement covers its
  whole size band and dispatch-time resolution is a pure dict lookup —
  a cache hit can NEVER retrace (the resolved ``block_rows`` is the same
  static jit key every time).
* **override** — an explicit ``block_rows=`` at any entry point always
  wins; an absent cache entry falls back to
  :data:`~repro.kernels.engine.DEFAULT_BLOCK_ROWS` (and, for
  ``plane="auto"`` callers, the Pallas plane on TPU where Mosaic compiles
  the op, jnp otherwise).
* **correctness** — every candidate's output is asserted bit-identical to
  the jnp program before it may win; tuning can change *time*, never
  placement.

The cache path can be redirected with ``REPRO_TUNE_CACHE=/path.json``
(tests point it at a tmpdir; ``REPRO_TUNE_CACHE=`` disables loading).
"""
from __future__ import annotations

import json
import os
import time
from dataclasses import asdict, dataclass
from pathlib import Path

import numpy as np

from repro.obs.metrics import default_registry as _obs_registry

CACHE_ENV = "REPRO_TUNE_CACHE"
CACHE_VERSION = 1
DEFAULT_CACHE_PATH = (Path(__file__).resolve().parents[3]
                      / "benchmarks" / "results" / "TUNE_engine.json")

#: tile heights searched (rows of 128 lanes per Pallas program instance)
BLOCK_ROWS_GRID = (1, 2, 4, 8, 16, 32)
PLANES = ("jnp", "pallas")


@dataclass(frozen=True)
class TunedConfig:
    """One grid cell's winner: the tile height for the Pallas launch, the
    faster plane at that shape, and the measured µs/key at tuning time
    (advisory — retiming happens in bench_engine, not at dispatch)."""

    block_rows: int = 8
    plane: str = "pallas"
    us_per_key: float = 0.0


def _backend() -> str:
    import jax
    return jax.default_backend()


def op_tag(op) -> str:
    """Stable textual identity of an EngineOp (duck-typed: anything with
    the op's fields works, so this module never imports the engine)."""
    tag = f"{op.algo}.{op.mode}.k{op.k}"
    if op.bounded:
        tag += ".bounded"
    if op.diff:
        tag += ".diff"
    return f"{tag}.{op.table}"


def size_bucket(x: int) -> int:
    """Next power of two ≥ max(x, 1) — one tuning cell per size band."""
    b = 1
    while b < max(int(x), 1):
        b <<= 1
    return b


def grid_key(op, n_keys: int, table_n: int, backend: str | None = None) -> str:
    backend = backend or _backend()
    return (f"{backend}/{op_tag(op)}/keys{size_bucket(n_keys)}"
            f"/n{size_bucket(table_n)}")


# ---------------------------------------------------------------------------
# The persisted cache
# ---------------------------------------------------------------------------

def cache_path() -> Path | None:
    """The active cache file: ``$REPRO_TUNE_CACHE`` (empty = disabled) or
    the checked-in ``benchmarks/results/TUNE_engine.json``."""
    env = os.environ.get(CACHE_ENV)
    if env is not None:
        return Path(env) if env else None
    return DEFAULT_CACHE_PATH


class TuneCache:
    """Grid key → :class:`TunedConfig`, JSON-persisted deterministically
    (sorted keys, stable formatting: same entries ⇒ byte-identical file)."""

    def __init__(self, entries: dict[str, TunedConfig] | None = None,
                 path: Path | None = None):
        self.entries: dict[str, TunedConfig] = dict(entries or {})
        self.path = path

    @classmethod
    def load(cls, path: Path | str | None = None) -> "TuneCache":
        p = Path(path) if path is not None else cache_path()
        if p is None or not p.exists():
            return cls({}, p)
        raw = json.loads(p.read_text())
        entries = {k: TunedConfig(**v)
                   for k, v in raw.get("entries", {}).items()}
        return cls(entries, p)

    def save(self, path: Path | str | None = None) -> Path:
        p = Path(path) if path is not None else (self.path or DEFAULT_CACHE_PATH)
        payload = {"version": CACHE_VERSION,
                   "entries": {k: asdict(self.entries[k])
                               for k in sorted(self.entries)}}
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
        self.path = p
        return p

    def get(self, key: str) -> TunedConfig | None:
        return self.entries.get(key)

    def put(self, key: str, cfg: TunedConfig) -> None:
        self.entries[key] = cfg

    def __len__(self) -> int:
        return len(self.entries)


_ACTIVE: TuneCache | None = None


def active_cache() -> TuneCache:
    """The process-wide cache the engine consults, loaded lazily once."""
    global _ACTIVE
    if _ACTIVE is None:
        _ACTIVE = TuneCache.load()
    return _ACTIVE


def set_active_cache(cache: TuneCache | None) -> None:
    """Install (or, with ``None``, drop — forcing a lazy reload) the
    process-wide cache; tests and the tuner use this."""
    global _ACTIVE
    _ACTIVE = cache


# ---------------------------------------------------------------------------
# Dispatch-time resolution (pure dict lookups — never retraces)
# ---------------------------------------------------------------------------

def lookup_tuned(op, n_keys: int, table_n: int,
                 backend: str | None = None) -> TunedConfig | None:
    cfg = active_cache().get(grid_key(op, n_keys, table_n, backend))
    reg = _obs_registry()
    if reg.active:
        reg.counter("engine.autotune.hit" if cfg is not None
                    else "engine.autotune.miss").inc()
    return cfg


def resolve_block_rows(op, n_keys: int, table_n: int,
                       backend: str | None = None) -> int:
    cfg = lookup_tuned(op, n_keys, table_n, backend)
    if cfg is not None:
        return cfg.block_rows
    from .engine import DEFAULT_BLOCK_ROWS
    return DEFAULT_BLOCK_ROWS


def resolve_plane(op, n_keys: int, table_n: int,
                  backend: str | None = None) -> str:
    """Plane for ``plane="auto"`` callers: the tuned winner, else Pallas on
    TPU (the compiled kernel) and jnp elsewhere (interpret-mode Pallas is
    a correctness path, not a serving plane).  On TPU the answer is never
    a Pallas configuration Mosaic cannot compile
    (:func:`~repro.kernels.engine.mosaic_compiles`): such ops resolve to
    jnp whatever the cache says."""
    from .engine import mosaic_compiles

    backend = backend or _backend()
    cfg = lookup_tuned(op, n_keys, table_n, backend)
    plane = cfg.plane if cfg is not None else (
        "pallas" if backend == "tpu" else "jnp")
    if plane == "pallas" and backend == "tpu" and not mosaic_compiles(op):
        return "jnp"
    return plane


# ---------------------------------------------------------------------------
# Measurement
# ---------------------------------------------------------------------------

def _time_best(fn, repeats: int) -> float:
    import jax
    jax.block_until_ready(fn())  # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def autotune_lookup(image, n_keys: int, *, k: int = 1, seed: int = 0,
                    candidates=BLOCK_ROWS_GRID, planes=PLANES,
                    repeats: int = 3, cache: TuneCache | None = None,
                    backend: str | None = None) -> tuple[str, TunedConfig]:
    """Tune one grid cell: measure ``engine_lookup`` over every (plane,
    block_rows) candidate at this (image, batch) shape, assert every
    candidate bit-identical to the jnp program, record the
    fastest in ``cache`` (default: the active cache) and return
    ``(grid key, winner)``."""
    from .engine import (DEFAULT_BLOCK_ROWS, EngineOp, default_interpret,
                         engine_lookup, mosaic_compiles)

    op = EngineOp(algo=image.algo, k=k,
                  table="packed" if getattr(image, "packed", False)
                  else "dense")
    keys = np.random.default_rng(seed).integers(0, 2**32, size=n_keys,
                                                dtype=np.uint32)
    ref = np.asarray(engine_lookup(keys, image, k=k, plane="jnp"))
    measured: list[tuple[float, str, int]] = []
    if "jnp" in planes:
        t = _time_best(lambda: engine_lookup(keys, image, k=k, plane="jnp"),
                       repeats)
        measured.append((t, "jnp", DEFAULT_BLOCK_ROWS))
    # on a TPU, Pallas is a candidate only where Mosaic compiles the op
    if "pallas" in planes and (default_interpret() or mosaic_compiles(op)):
        for br in candidates:
            t = _time_best(lambda: engine_lookup(keys, image, k=k,
                                                 plane="pallas",
                                                 block_rows=br), repeats)
            out = np.asarray(engine_lookup(keys, image, k=k, plane="pallas",
                                           block_rows=br))
            if not np.array_equal(out, ref):
                raise AssertionError(
                    f"pallas block_rows={br} diverged from the jnp "
                    f"program for {op_tag(op)}")
            measured.append((t, "pallas", br))
    if not measured:
        raise ValueError("no candidate planes to tune over")
    best_t, best_plane, best_br = min(measured)
    cfg = TunedConfig(block_rows=int(best_br), plane=best_plane,
                      us_per_key=round(best_t / n_keys * 1e6, 4))
    key = grid_key(op, n_keys, int(image.n), backend)
    (cache if cache is not None else active_cache()).put(key, cfg)
    return key, cfg
