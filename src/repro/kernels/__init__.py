# Device data plane: ONE unified lookup engine (engine.py, DESIGN.md §6)
# — a single tiled Pallas dispatch (and matching jitted jnp program) whose
# static EngineOp configuration covers plain lookup, k-replication,
# bounded-load (incl. the fused k-replica-under-cap op), chain-walk
# assignment rounds, and one/two-epoch diffs for every registry algorithm.
# engine.engine_lookup is the public image-generic entry; the lookup
# bodies it runs live in core/jax_lookup.py (engine.algo_body picks one);
# primitives.py holds the shared 32-bit hash arithmetic; ref.py the
# oracles kernel tests compare against; delta_apply.py the epoch-delta
# scatter (§3.5).
