"""``spmd._unique_rows``: the engine's exact row dedup equals
``np.unique(rows, axis=0)`` array for array -- same rows, same
lexicographic order, same dtype -- on the packed int64-key path (every
value a vertex id, columns fit 63 bits) and on the fallback."""
import numpy as np
import pytest

from repro.constants import MAX_VERTEX_ID
from repro.core.spmd import _unique_rows

_RNG = np.random.default_rng(14)


def _ids(n, v, hi=MAX_VERTEX_ID + 1):
    return _RNG.integers(0, hi, (n, v)).astype(np.int32)


def _dups(n, v):
    base = _ids(max(n // 50, 1), v, hi=40)
    return base[_RNG.integers(0, base.shape[0], n)]


def _extremes(v):
    rows = _RNG.choice(np.array([0, 1, MAX_VERTEX_ID - 1, MAX_VERTEX_ID]),
                       (300, v)).astype(np.int32)
    return rows


def _negative(v):
    rows = _ids(200, v, hi=50)
    rows[7, v - 1] = -1
    return np.concatenate([rows, rows[:20]])


CASES = [
    ("v1", _ids(2000, 1), True),
    ("v2", _ids(3000, 2), True),
    ("v3", _ids(3000, 3), True),
    ("v4-fallback", _ids(1000, 4, hi=30), False),
    ("v5-fallback", _ids(1000, 5, hi=30), False),
    ("v2-many-dups", _dups(5000, 2), True),
    ("v3-many-dups", _dups(5000, 3), True),
    ("v3-zero-and-max-ids", _extremes(3), True),
    ("v2-one-row", np.array([[MAX_VERTEX_ID, 0]], np.int32), True),
    ("v3-empty", np.zeros((0, 3), np.int32), True),
    ("v2-negative-fallback", _negative(2), False),
    ("v3-above-max-fallback",
     np.array([[MAX_VERTEX_ID + 1, 1, 2], [0, 1, 2], [0, 1, 2]], np.int32),
     False),
    ("v2-int64", _ids(1000, 2).astype(np.int64), True),
]


@pytest.mark.parametrize("rows,packed", [c[1:] for c in CASES],
                         ids=[c[0] for c in CASES])
def test_unique_rows_equals_numpy_unique_axis0(rows, packed):
    got, got_packed = _unique_rows(rows)
    want = np.unique(rows, axis=0)
    assert got.dtype == want.dtype
    assert got.shape == want.shape
    assert np.array_equal(got, want)
    assert got_packed is packed
