"""The expansion step's index maps, built by scatter and running max.

``core.spmd._expand_fixed`` maps each output slot to its binding row and
each binding row to its run in the edge table without a binary search.
These tests hold it slot for slot to the binary-search oracle
``kernels.ref.expand_fixed_ref`` (the same four outputs: rows, payload
column, validity, overflow count) and check that the index maps lower
with no loop.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.constants import INT32_SENTINEL, MAX_VERTEX_ID
from repro.core import spmd as S
from repro.kernels import ref as kref


def _table(runs, W, rng):
    """Ascending keys from ``runs`` ((key, multiplicity) pairs), padded
    with the sentinel to ``W`` rows, and a random payload (-1 on the
    padding)."""
    keys = np.concatenate([np.full(m, k, np.int32) for k, m in sorted(runs)]
                          + [np.zeros(0, np.int32)])
    n = keys.size
    assert n <= W
    payload = np.concatenate([rng.integers(0, 1 << 20, n).astype(np.int32),
                              np.full(W - n, -1, np.int32)])
    keys = np.concatenate([keys, np.full(W - n, INT32_SENTINEL, np.int32)])
    return keys, payload


def _rows(probes, V, rng, valid=None):
    """A (C, V) binding table whose first column is ``probes``."""
    probes = np.asarray(probes, np.int32)
    bind = rng.integers(0, 1 << 20, (probes.size, V)).astype(np.int32)
    bind[:, 0] = probes
    valid = np.ones(probes.size, bool) if valid is None else valid
    return bind, np.asarray(valid, bool), probes


def _case(name, rng):
    """(bind, valid, probes, keys, payload, capacity) of one named case."""
    runs = [(10 + 3 * i, 1 + i % 3) for i in range(10)]   # 19 edges
    keys, payload = _table(runs, 32, rng)
    present = [k for k, _ in runs]
    if name == "zero_count_head_and_tail":
        probes = [5, 6] + present + [7, 500, 501]
        return (*_rows(probes, 2, rng), keys, payload, 64)
    if name == "all_counts_zero":
        return (*_rows([1, 2, 3, 8, 9] * 4, 2, rng), keys, payload, 16)
    if name == "total_equals_capacity":
        return (*_rows(present, 2, rng), keys, payload,
                sum(m for _, m in runs))
    if name == "total_exceeds_capacity":
        return (*_rows(present * 3, 3, rng), keys, payload, 37)
    if name == "gathered_table_larger_than_capacity":
        probes = rng.choice(present + [4, 5, 6], 256)
        return (*_rows(probes, 2, rng, rng.random(256) < 0.1),
                keys, payload, 64)
    if name == "table_smaller_than_capacity":
        return (*_rows(present[:6], 1, rng), keys, payload, 256)
    if name == "duplicate_heavy":
        hk, hp = _table([(7, 50), (8, 1), (9, 40)], 96, rng)
        probes = rng.choice([7, 7, 7, 9, 8, 3], 40)
        return (*_rows(probes, 2, rng), hk, hp, 1024)
    if name == "sentinel_padded_tail":
        sk, sp = _table(runs[:3], 512, rng)
        probes = [present[0], present[1], present[2], present[5]] * 3
        return (*_rows(probes, 2, rng), sk, sp, 64)
    if name == "keys_at_id_bounds":
        bk, bp = _table([(0, 3), (1, 1), (MAX_VERTEX_ID - 1, 1),
                         (MAX_VERTEX_ID, 4)], 16, rng)
        probes = [MAX_VERTEX_ID, 0, 2, MAX_VERTEX_ID - 1, 0, MAX_VERTEX_ID]
        return (*_rows(probes, 2, rng), bk, bp, 32)
    if name == "invalid_probe_rows":
        valid = np.arange(30) % 3 != 1
        probes = rng.choice(present, 30)
        return (*_rows(probes, 2, rng, valid), keys, payload, 64)
    if name == "wrap_risk":
        # one run longer than (2^31 - 1) // C: the int32 offset sum could
        # wrap, so the overflow count is forced past capacity
        C = 1 << 16
        wk, wp = _table([(5, (2 ** 31 - 1) // C + 1), (6, 2)], 32776, rng)
        probes = np.full(C, 6, np.int32)
        probes[[3, 70]] = 5
        return (*_rows(probes, 1, rng, np.arange(C) < 100), wk, wp, 128)
    seed = int(name.split("_")[-1])
    r2 = np.random.default_rng(seed)
    rk = int(r2.choice([8, 64, MAX_VERTEX_ID + 1]))
    W = int(r2.integers(1, 300))
    n = int(r2.integers(0, W + 1))
    ks = np.sort(r2.integers(0, rk, n)).astype(np.int32)
    keys = np.concatenate([ks, np.full(W - n, INT32_SENTINEL, np.int32)])
    payload = r2.integers(0, 1 << 20, W).astype(np.int32)
    C = int(r2.integers(1, 400))
    pool = np.concatenate([ks, r2.integers(0, rk, 8).astype(np.int32)])
    return (*_rows(r2.choice(pool, C), 3, r2, r2.random(C) < 0.7),
            keys, payload, int(r2.integers(1, 700)))


CASES = ["zero_count_head_and_tail", "all_counts_zero",
         "total_equals_capacity", "total_exceeds_capacity",
         "gathered_table_larger_than_capacity",
         "table_smaller_than_capacity", "duplicate_heavy",
         "sentinel_padded_tail", "keys_at_id_bounds", "invalid_probe_rows",
         "wrap_risk", "random_1", "random_2", "random_3"]


@pytest.mark.parametrize("name", CASES)
def test_expand_fixed_matches_binary_search_oracle(name, monkeypatch):
    """New bindings, payload column, validity and overflow count equal
    the oracle's slot for slot."""
    monkeypatch.setenv("REPRO_SPMD_PALLAS", "0")
    bind, valid, probes, keys, payload, capacity = _case(
        name, np.random.default_rng(sum(map(ord, name))))
    args = [jnp.asarray(a) for a in (bind, valid, probes, keys, payload)]
    want = kref.expand_fixed_ref(*args, capacity)
    paths = {}
    got = S._expand_fixed(*args, capacity, paths)
    for w, g, what in zip(want, got, ("new_bind", "new_col", "ok", "over")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=what)
    assert paths["expand_direct"] == 1
    total = int(np.asarray(kref.join_count_ref(
        jnp.where(args[1], args[2], INT32_SENTINEL), args[3]))[valid].sum())
    over = int(np.asarray(got[3]))
    if name == "wrap_risk":
        assert over == capacity + 1
    else:
        assert over == max(total - capacity, 0)
        assert int(np.asarray(got[2]).sum()) == min(total, capacity)
    if name == "total_exceeds_capacity":
        assert over > 0
    if name == "total_equals_capacity":
        assert total == capacity


@pytest.mark.parametrize("seed", range(4))
def test_index_maps_equal_searchsorted(seed):
    """Each map alone equals its binary search where the expansion reads
    it: the slot -> row map on every slot below the total, the row ->
    run map on every probe the table holds."""
    rng = np.random.default_rng(seed)
    C, capacity = int(rng.integers(1, 300)), int(rng.integers(1, 400))
    cnt = np.where(rng.random(C) < 0.4, 0, rng.integers(0, 5, C))
    start = (np.cumsum(cnt) - cnt).astype(np.int32)
    total = min(int(cnt.sum()), capacity)
    r = np.asarray(S._slot_rows(jnp.asarray(start),
                                jnp.asarray(cnt, jnp.int32), capacity))
    t = np.arange(total)
    np.testing.assert_array_equal(
        r[:total], np.searchsorted(start, t, side="right") - 1)
    keys = np.sort(rng.choice([0, 1, 7, 300, MAX_VERTEX_ID], 50))
    keys = np.concatenate([keys, np.full(9, INT32_SENTINEL)]).astype(np.int32)
    probe = rng.choice(keys[:50], 80).astype(np.int32)
    lo = np.asarray(S._run_starts(jnp.asarray(keys), jnp.asarray(probe)))
    np.testing.assert_array_equal(lo, np.searchsorted(keys, probe, "left"))


@pytest.mark.parametrize("helper", ["slot_rows", "run_starts"])
def test_index_maps_lower_without_a_loop(helper):
    """The slot -> row and row -> run maps lower to HLO with no
    ``while``: the binary search's loop must not come back."""
    n = 1 << 12
    ints = jax.ShapeDtypeStruct((n,), jnp.int32)
    if helper == "slot_rows":
        lowered = jax.jit(S._slot_rows, static_argnums=2).lower(
            ints, ints, n)
    else:
        lowered = jax.jit(S._run_starts).lower(ints, ints)
    text = lowered.as_text()
    assert "scatter" in text
    assert "while" not in text


def test_expand_fixed_flags_a_wrapped_offset_sum(monkeypatch):
    """Offsets that really wrap int32 (2^16 rows of 2^15 matches each)
    still report the overflow count past capacity, so the retry ladder
    discards the slots."""
    monkeypatch.setenv("REPRO_SPMD_PALLAS", "0")
    C, capacity = 1 << 16, 64
    rng = np.random.default_rng(3)
    keys, payload = _table([(5, 1 << 15)], 1 << 15, rng)
    bind, valid, probes = _rows(np.full(C, 5, np.int32), 1, rng)
    args = [jnp.asarray(a) for a in (bind, valid, probes, keys, payload)]
    assert int(np.asarray(S._expand_fixed(*args, capacity)[3])) \
        == capacity + 1
