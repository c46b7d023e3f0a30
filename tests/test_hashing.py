"""`np_key_to_u32`, the router's blocked in-place id hash, gives the bits of
the plain formula for every integer input, keeps the input's shape, and
never writes into the caller's array."""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import hashing
from repro.core.hashing import MASK32, key_to_u32, np_fmix32, np_key_to_u32

BLOCK = hashing._KEY_BLOCK
SIZES = [0, 1, BLOCK - 1, BLOCK, BLOCK + 1, 3 * BLOCK + 5]
DTYPES = ["uint64", "int64", "int32", "uint32", "uint8", ">u8"]
LAYOUTS = ["flat", "strided", "2d", "2d-fortran"]


def _plain(keys):
    """The formula as one expression over whole arrays."""
    k = keys.astype(np.uint64)
    return np_fmix32(((k & np.uint64(MASK32)) ^ (k >> np.uint64(32)))
                     .astype(np.uint32))


def _ids(dtype, n, seed=0):
    """``n`` ids of ``dtype`` over its whole range, negatives included."""
    dt = np.dtype(dtype)
    info = np.iinfo(dt)
    rng = np.random.default_rng(seed)
    ids = rng.integers(info.min, info.max, size=n, dtype=dt.newbyteorder("="),
                       endpoint=True)
    return ids.astype(dt)


def _layout(dtype, n, layout):
    if layout == "flat":
        return _ids(dtype, n)
    if layout == "strided":
        return _ids(dtype, 3 * n)[::3]
    ids = _ids(dtype, 2 * n).reshape(2, n)
    return ids if layout == "2d" else np.asfortranarray(ids)


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_np_key_to_u32_matches_plain_formula(dtype, n, layout):
    ids = _layout(dtype, n, layout)
    got = np_key_to_u32(ids)
    assert got.dtype == np.uint32 and got.shape == ids.shape
    np.testing.assert_array_equal(got, _plain(ids))


def test_np_key_to_u32_matches_scalar_key_to_u32():
    ids = np.concatenate([_ids("uint64", 300, seed=1),
                          np.array([0, 1, MASK32, MASK32 + 1,
                                    np.iinfo(np.uint64).max], np.uint64)])
    assert np_key_to_u32(ids).tolist() == [key_to_u32(int(i)) for i in ids]


@pytest.mark.parametrize("dtype", ["uint64", "int64", "int32"])
def test_np_key_to_u32_leaves_the_ids_unchanged(dtype):
    ids = _ids(dtype, 2 * BLOCK + 3, seed=2)
    before = ids.tobytes()
    np_key_to_u32(ids)
    assert ids.tobytes() == before
