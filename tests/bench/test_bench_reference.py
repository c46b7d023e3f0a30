"""The benchmark's plain reference agrees with the program at a small fleet:
healthy, 90% failed, after churn, and with a LIFO removal."""
from __future__ import annotations

import numpy as np
import pytest

from bench.references import memento as ref_mod

N = 4000


def _ids(seed, n=3000):
    rng = np.random.default_rng(seed)
    return rng.integers(0, np.iinfo(np.uint64).max, size=n, dtype=np.uint64)


def test_key_to_u32_matches_program():
    from repro.core.hashing import np_key_to_u32

    ids = _ids(1, 50_000)
    np.testing.assert_array_equal(ref_mod.key_to_u32(ids), np_key_to_u32(ids))


@pytest.mark.parametrize("n", [1, 2, 1000, 10**6, 1 << 24])
def test_jump32_matches_program(n):
    from repro.core.jump import np_jump32

    keys = ref_mod.key_to_u32(_ids(n % 97, 20_000))
    np.testing.assert_array_equal(ref_mod.jump32(keys, n), np_jump32(keys, n))


def _router_and_reference(state: str, seed: int):
    from repro.serve.router import SessionRouter

    router = SessionRouter(N, algo="memento")
    ref = ref_mod.Reference(N)
    rng = np.random.default_rng(seed)
    victims = []
    if state == "failed90":
        victims = rng.permutation(N)[: int(0.9 * N)].tolist()
    elif state == "lifo":
        victims = [N - 1, N - 2, 17, N - 3]
    for b in victims:
        router.ch.remove(b)
        ref.remove(b)
    router.image_store().sync()
    return router, ref


@pytest.mark.parametrize("state", ["healthy", "failed90", "lifo"])
@pytest.mark.parametrize("seed", [0, 1])
def test_reference_equals_route_batch(state, seed):
    router, ref = _router_and_reference(state, seed)
    ids = _ids(seed + 10)
    np.testing.assert_array_equal(router.route_batch(ids),
                                  ref.lookup(ref_mod.key_to_u32(ids)))


@pytest.mark.parametrize("seed", [0, 1])
def test_reference_equals_route_batch_through_churn(seed):
    """Removals through ``fail_replica`` (the delta path), each of the
    bucket of a key of the batch, compared after every one."""
    router, ref = _router_and_reference("healthy", seed)
    ids = _ids(seed + 20)
    keys = ref_mod.key_to_u32(ids)
    rng = np.random.default_rng(seed)
    out = router.route_batch(ids)
    for _ in range(12):
        victim = int(out[rng.integers(out.size)])
        router.fail_replica(victim)
        ref.remove(victim)
        new = router.route_batch(ids)
        np.testing.assert_array_equal(new, ref.lookup(keys))
        np.testing.assert_array_equal(new != out, out == victim)
        out = new


def test_reference_refuses_what_the_paper_refuses():
    ref = ref_mod.Reference(3)
    ref.remove(1)
    with pytest.raises(ValueError):
        ref.remove(1)
    ref.remove(0)
    with pytest.raises(ValueError):
        ref.remove(2)
    assert ref.working == 1
    assert set(ref.lookup(ref_mod.key_to_u32(_ids(3, 100))).tolist()) == {2}
