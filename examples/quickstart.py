"""Quickstart: the MementoHash API in 60 lines.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import AnchorHash, DxHash, JumpHash, MementoHash, PowerHash
from repro.kernels.engine import engine_lookup


def main():
    # 1. a 10-node cluster — Memento starts empty (Θ(1) state, like Jump)
    m = MementoHash(10)
    keys = [f"user:{i}" for i in range(6)]
    from repro.core.hashing import key_to_u64
    print("initial placement:", {k: m.lookup(key_to_u64(k)) for k in keys})
    print(f"state: n={m.n} |R|={len(m.R)} memory={m.memory_bytes()}B")

    # 2. node 4 fails (random removal — the case JumpHash cannot handle)
    m.remove(4)
    print("\nafter node 4 fails:", {k: m.lookup(key_to_u64(k)) for k in keys})
    print(f"state: n={m.n} |R|={len(m.R)} l={m.l} R={m.R}")

    # 3. scale out: the failed node is restored first (reverse order)
    print("restored node:", m.add())
    print("new tail node:", m.add())
    print(f"state: n={m.n} |R|={len(m.R)}")

    # 4. the device data plane: bulk lookups through the lookup engine
    m.remove(7)
    m.remove(2)
    batch = np.random.default_rng(0).integers(0, 2**32, size=8, dtype=np.uint32)
    out = engine_lookup(batch, m.device_image(), plane="auto")
    print("\nbatched device-plane lookups:", np.asarray(out).tolist())

    # 5. baselines for comparison (fixed capacity a = 10·w)
    for h in (JumpHash(10), AnchorHash(100, 10), DxHash(100, 10),
              PowerHash(10)):
        print(f"{h.name:8s} lookup({keys[0]!r}) → {h.lookup(key_to_u64(keys[0]))}"
              f"   memory={h.memory_bytes()}B")

    # 6. every algorithm speaks the same protocol: one device plane for all
    from repro.core import ALGORITHM_REGISTRY, ALGORITHMS, make_hash
    print("\nprotocol device plane (host == device, variant='32'):")
    for algo in ALGORITHMS:
        h = make_hash(algo, 10, variant="32")
        if ALGORITHM_REGISTRY[algo].lifo_only:
            h.remove(h.size - 1)
        else:
            h.remove(3)
        out = engine_lookup(batch, h.device_image(), plane="auto")
        assert [h.lookup(int(k)) for k in batch] == np.asarray(out).tolist()
        print(f"  {algo:8s} → {np.asarray(out).tolist()}")


if __name__ == "__main__":
    main()
