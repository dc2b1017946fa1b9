"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import attention, join_count, pair_semijoin, ref, semijoin

RNG = np.random.default_rng(42)
INT32_MAX = np.iinfo(np.int32).max


@pytest.mark.parametrize("m,n", [(1, 1), (7, 3), (100, 1000), (1000, 100),
                                 (513, 1025), (5000, 5000), (20000, 3000)])
@pytest.mark.parametrize("key_range", [50, 5000])
def test_semijoin_sweep(m, n, key_range):
    table = np.sort(RNG.integers(0, key_range, size=n).astype(np.int32))
    queries = RNG.integers(0, int(key_range * 1.3), size=m).astype(np.int32)
    got = np.asarray(semijoin(jnp.asarray(queries), jnp.asarray(table)))
    want = np.asarray(ref.semijoin_mask_ref(jnp.asarray(queries),
                                            jnp.asarray(table)))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("m,n", [(1, 1), (100, 1000), (5000, 5000),
                                 (513, 1025)])
def test_join_count_sweep(m, n):
    table = np.sort(RNG.integers(0, 400, size=n).astype(np.int32))
    queries = RNG.integers(0, 500, size=m).astype(np.int32)
    got = np.asarray(join_count(jnp.asarray(queries), jnp.asarray(table)))
    want = np.asarray(ref.join_count_ref(jnp.asarray(queries),
                                         jnp.asarray(table)))
    np.testing.assert_array_equal(got, want)
    # counts are exact expansion sizes
    assert got.sum() == sum(int((table == q).sum()) for q in queries)


def test_semijoin_empty():
    assert semijoin(jnp.zeros(0, jnp.int32), jnp.zeros(5, jnp.int32)).shape \
        == (0,)
    assert not bool(semijoin(jnp.zeros(5, jnp.int32),
                             jnp.zeros(0, jnp.int32)).any())


# ----------------------------------------------------------------------
# Padded (sentinel) inputs: the SPMD match loop feeds tables padded with
# -1 (SiteStore) / INT32_MAX (sorted-key sentinel); kernel and oracle
# must agree bit-for-bit on them.
# ----------------------------------------------------------------------

@pytest.mark.parametrize("fill", [-1, INT32_MAX])
def test_semijoin_and_count_padded_sentinel_parity(fill):
    """-1 (SiteStore padding) is an ordinary key on both sides;
    INT32_MAX is the ops' reserved block-padding sentinel -- legal as
    table padding but never a real probe (vertex ids < 2^21), so the
    query side only carries it in the -1 case."""
    real = RNG.integers(0, 300, size=700).astype(np.int32)
    table = np.sort(np.concatenate([real, np.full(345, fill, np.int32)]))
    queries = RNG.integers(0, 400, size=500).astype(np.int32)
    if fill == -1:
        queries = np.concatenate([queries, np.full(77, fill, np.int32)])
    for op, oracle in ((semijoin, ref.semijoin_mask_ref),
                       (join_count, ref.join_count_ref)):
        got = np.asarray(op(jnp.asarray(queries), jnp.asarray(table)))
        want = np.asarray(oracle(jnp.asarray(queries), jnp.asarray(table)))
        np.testing.assert_array_equal(got, want)


def test_semijoin_all_padding_table():
    """Sorted-key sentinel rows (INT32_MAX) never match a real id; an
    all-(-1) padded table matches exactly the -1 probes."""
    queries = RNG.integers(0, 100, size=600).astype(np.int32)
    sent = np.full(1000, INT32_MAX, np.int32)
    assert not bool(np.asarray(semijoin(jnp.asarray(queries),
                                        jnp.asarray(sent))).any())
    neg = np.full(1000, -1, np.int32)
    got = np.asarray(semijoin(jnp.asarray(queries), jnp.asarray(neg)))
    np.testing.assert_array_equal(got, queries == -1)
    cnt = np.asarray(join_count(jnp.full(3, -1, jnp.int32),
                                jnp.asarray(neg)))
    np.testing.assert_array_equal(cnt, np.full(3, 1000, np.int32))


@pytest.mark.parametrize("m,n", [(1, 1), (100, 1000), (513, 1025),
                                 (3000, 2000)])
def test_pair_semijoin_sweep(m, n):
    t_s = RNG.integers(0, 60, size=n).astype(np.int32)
    t_o = RNG.integers(0, 60, size=n).astype(np.int32)
    q_s = RNG.integers(0, 70, size=m).astype(np.int32)
    q_o = RNG.integers(0, 70, size=m).astype(np.int32)
    got = np.asarray(pair_semijoin(jnp.asarray(q_s), jnp.asarray(q_o),
                                   jnp.asarray(t_s), jnp.asarray(t_o)))
    want = np.asarray(ref.pair_semijoin_ref(jnp.asarray(q_s),
                                            jnp.asarray(q_o),
                                            jnp.asarray(t_s),
                                            jnp.asarray(t_o)))
    np.testing.assert_array_equal(got, want)
    # spot-check the oracle itself against brute force
    pairs = {(int(a), int(b)) for a, b in zip(t_s, t_o)}
    brute = np.array([(int(a), int(b)) in pairs for a, b in zip(q_s, q_o)])
    np.testing.assert_array_equal(want, brute)


def test_pair_semijoin_padded_and_empty():
    t_s = np.concatenate([RNG.integers(0, 50, 400).astype(np.int32),
                          np.full(112, INT32_MAX, np.int32)])
    t_o = np.concatenate([RNG.integers(0, 50, 400).astype(np.int32),
                          np.full(112, INT32_MAX, np.int32)])
    q_s = RNG.integers(0, 50, 300).astype(np.int32)
    q_o = RNG.integers(0, 50, 300).astype(np.int32)
    got = np.asarray(pair_semijoin(jnp.asarray(q_s), jnp.asarray(q_o),
                                   jnp.asarray(t_s), jnp.asarray(t_o)))
    want = np.asarray(ref.pair_semijoin_ref(jnp.asarray(q_s),
                                            jnp.asarray(q_o),
                                            jnp.asarray(t_s),
                                            jnp.asarray(t_o)))
    np.testing.assert_array_equal(got, want)
    # empty table / empty queries
    assert not bool(pair_semijoin(jnp.asarray(q_s), jnp.asarray(q_o),
                                  jnp.zeros(0, jnp.int32),
                                  jnp.zeros(0, jnp.int32)).any())
    assert pair_semijoin(jnp.zeros(0, jnp.int32), jnp.zeros(0, jnp.int32),
                         jnp.asarray(t_s), jnp.asarray(t_o)).shape == (0,)


def test_probe_kernels_jit_safe_inside_jit():
    """The SPMD match loop calls the probe ops inside jit/shard_map:
    jit_safe=True must trace (static block plan, no host sync) and still
    agree with the oracles."""
    table = np.sort(RNG.integers(0, 500, size=1200).astype(np.int32))
    queries = RNG.integers(0, 600, size=800).astype(np.int32)
    t_s = RNG.integers(0, 40, size=900).astype(np.int32)
    t_o = RNG.integers(0, 40, size=900).astype(np.int32)

    @jax.jit
    def probes(q, t, ps, po):
        return (semijoin(q, t, jit_safe=True),
                join_count(q, t, jit_safe=True),
                pair_semijoin(q, q, ps, po, jit_safe=True))

    mask, cnt, pair = probes(jnp.asarray(queries), jnp.asarray(table),
                             jnp.asarray(t_s), jnp.asarray(t_o))
    np.testing.assert_array_equal(
        np.asarray(mask),
        np.asarray(ref.semijoin_mask_ref(jnp.asarray(queries),
                                         jnp.asarray(table))))
    np.testing.assert_array_equal(
        np.asarray(cnt),
        np.asarray(ref.join_count_ref(jnp.asarray(queries),
                                      jnp.asarray(table))))
    np.testing.assert_array_equal(
        np.asarray(pair),
        np.asarray(ref.pair_semijoin_ref(
            jnp.asarray(queries), jnp.asarray(queries),
            jnp.asarray(t_s), jnp.asarray(t_o))))


ATTN_CASES = [
    # B, Hq, Hkv, Sq, Skv, D, causal, window
    (1, 4, 2, 256, 256, 64, True, None),
    (2, 8, 8, 128, 128, 32, True, None),
    (1, 4, 1, 256, 256, 64, True, 128),     # sliding window + GQA 4:1
    (1, 2, 2, 200, 200, 64, True, None),    # padded path
    (1, 4, 4, 128, 384, 64, True, None),    # cross (q at end of timeline)
    (1, 8, 2, 512, 512, 128, True, None),   # MXU-width head dim
    (1, 4, 4, 256, 256, 64, True, 64),      # window < block
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
def test_attention_sweep(case, dtype):
    B, Hq, Hkv, Sq, Skv, D, causal, window = case
    if dtype == "bfloat16":
        import ml_dtypes
        dtype = ml_dtypes.bfloat16
        atol = 4e-2
    else:
        atol = 2e-5
    q = RNG.standard_normal((B, Hq, Sq, D)).astype(dtype)
    k = RNG.standard_normal((B, Hkv, Skv, D)).astype(dtype)
    v = RNG.standard_normal((B, Hkv, Skv, D)).astype(dtype)
    got = np.asarray(attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                               causal=causal, window=window),
                     dtype=np.float32)
    want = np.asarray(ref.attention_ref(jnp.asarray(q), jnp.asarray(k),
                                        jnp.asarray(v), causal=causal,
                                        window=window), dtype=np.float32)
    np.testing.assert_allclose(got, want, atol=atol, rtol=atol)


def test_attention_kernel_matches_inside_jit():
    q = jnp.asarray(RNG.standard_normal((1, 4, 128, 64)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 2, 128, 64)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 2, 128, 64)), jnp.float32)
    f = jax.jit(lambda a, b, c: attention(a, b, c, causal=True))
    np.testing.assert_allclose(
        np.asarray(f(q, k, v)),
        np.asarray(ref.attention_ref(q, k, v, causal=True)),
        atol=2e-5, rtol=2e-5)


# ----------------------------------------------------------------------
# Hash-dedup kernel vs the lexsort oracle (semantics of record), and
# the fused dedup->expand->filter join kernel vs the oracle
# composition used by core.spmd off-TPU.  Adversarial inputs: padded
# all-sentinel blocks, duplicate-heavy tables, capacity overflow, and
# empty (all-sentinel) property tables.
# ----------------------------------------------------------------------

from repro.kernels import dedup_rows, fused_join  # noqa: E402


def _bind_case(C, V, style, seed):
    rng = np.random.default_rng(seed)
    if style == "dup_heavy":
        bind = rng.integers(0, 3, (C, V)).astype(np.int32)
        valid = rng.random(C) < 0.9
    elif style == "all_sentinel":
        bind = np.full((C, V), -1, np.int32)
        valid = np.zeros(C, bool)
    elif style == "all_valid_distinct":
        bind = np.arange(C * V, dtype=np.int32).reshape(C, V)
        valid = np.ones(C, bool)
    else:                                   # random with padding holes
        bind = rng.integers(0, 40, (C, V)).astype(np.int32)
        valid = rng.random(C) < 0.7
        bind[~valid] = -1
    return bind, valid


def _first_occurrence_keep(bind, valid):
    """Brute-force first-occurrence-by-original-index keep mask."""
    seen, keep = set(), np.zeros(len(valid), bool)
    for i in range(len(valid)):
        key = tuple(bind[i].tolist())
        if valid[i] and key not in seen:
            seen.add(key)
            keep[i] = True
    return keep


@pytest.mark.parametrize("C,V", [(8, 1), (64, 3), (256, 2), (128, 5),
                                 (512, 4)])
@pytest.mark.parametrize("style", ["random", "dup_heavy", "all_sentinel",
                                   "all_valid_distinct"])
def test_dedup_rows_matches_oracle(C, V, style):
    bind, valid = _bind_case(C, V, style, seed=C * 31 + V)
    got = np.asarray(dedup_rows(jnp.asarray(bind), jnp.asarray(valid)))
    # the lexsort oracle keeps one row per distinct value set ...
    want_ref = np.asarray(ref.dedup_rows_ref(jnp.asarray(bind),
                                             jnp.asarray(valid)))
    # ... and the kernel's contract pins *which* one: the earliest index
    want_brute = _first_occurrence_keep(bind, valid)
    np.testing.assert_array_equal(got, want_brute)
    assert got.sum() == want_ref.sum()
    np.testing.assert_array_equal(
        np.sort(bind[got], axis=0), np.sort(bind[want_ref], axis=0))


def _edge_table(T, n_real, key_range, seed):
    """Sorted keys padded with the INT32_MAX sentinel + payload."""
    rng = np.random.default_rng(seed)
    keys = np.sort(rng.integers(0, key_range, n_real).astype(np.int32))
    keys = np.concatenate([keys, np.full(T - n_real, INT32_MAX, np.int32)])
    payload = np.concatenate([rng.integers(0, 99, n_real).astype(np.int32),
                              np.full(T - n_real, -1, np.int32)])
    return keys, payload


def _oracle_join(bind, valid, probe, keys, payload, capacity, monkeypatch):
    """The off-TPU composition of record: lexsort dedup + _expand_fixed
    (REPRO_SPMD_PALLAS pinned to 0 so CI kernel runs still diff against
    the jnp oracle)."""
    from repro.core import spmd as S
    monkeypatch.setenv("REPRO_SPMD_PALLAS", "0")
    db, dv = S._dedup_padded(jnp.asarray(bind), jnp.asarray(valid))
    # rebuild per-row probes exactly like exp_via_gather: column lookup
    # on the (possibly reordered) deduped table
    dprobe = np.asarray(db)[:, _PROBE_COL]
    return S._expand_fixed(db, dv, jnp.asarray(dprobe),
                           jnp.asarray(keys), jnp.asarray(payload), capacity)


_PROBE_COL = 0        # probe on the first binding column throughout


def _row_multiset(nb, nc, nv):
    nb, nc, nv = np.asarray(nb), np.asarray(nc), np.asarray(nv)
    rows = [tuple(nb[i].tolist()) + (int(nc[i]),)
            for i in range(len(nv)) if nv[i]]
    out = {}
    for r in rows:
        out[r] = out.get(r, 0) + 1
    return out


@pytest.mark.parametrize("C,V,T,capacity", [
    (64, 2, 64, 256),        # comfortable fit
    (128, 3, 32, 512),       # duplicate-heavy probes
    (64, 2, 8, 256),         # tiny table
    (256, 4, 128, 1024),
])
@pytest.mark.parametrize("style", ["random", "dup_heavy", "all_sentinel"])
def test_fused_join_matches_oracle_composition(C, V, T, capacity, style,
                                               monkeypatch):
    bind, valid = _bind_case(C, V, style, seed=C + T)
    keys, payload = _edge_table(T, max(T // 2, 1), 40, seed=C * T)
    probe = bind[:, _PROBE_COL]
    got = fused_join(jnp.asarray(bind), jnp.asarray(valid),
                     jnp.asarray(probe), jnp.asarray(keys),
                     jnp.asarray(payload), capacity)
    want = _oracle_join(bind, valid, probe, keys, payload, capacity,
                        monkeypatch)
    assert int(got[3]) == int(want[3]), "overflow counts diverged"
    assert int(got[3]) == 0
    assert _row_multiset(*got[:3]) == _row_multiset(*want[:3])


def test_fused_join_empty_property_table(monkeypatch):
    """An empty property on this shard: every key is the sentinel, so
    the join yields zero rows and zero overflow."""
    bind, valid = _bind_case(64, 2, "random", seed=9)
    keys = np.full(16, INT32_MAX, np.int32)
    payload = np.full(16, -1, np.int32)
    got = fused_join(jnp.asarray(bind), jnp.asarray(valid),
                     jnp.asarray(bind[:, 0]), jnp.asarray(keys),
                     jnp.asarray(payload), 128)
    assert int(got[3]) == 0 and not bool(np.asarray(got[2]).any())
    want = _oracle_join(bind, valid, bind[:, 0], keys, payload, 128,
                        monkeypatch)
    assert int(want[3]) == 0 and not bool(np.asarray(want[2]).any())


@pytest.mark.parametrize("capacity", [1, 4, 16])
def test_fused_join_overflow_counts_match_composition(capacity,
                                                      monkeypatch):
    """Under capacity overflow the retry ladder only consumes the
    overflow *count*; fused kernel and oracle composition must agree on
    it exactly (truncated content is discarded either way)."""
    bind, valid = _bind_case(128, 2, "dup_heavy", seed=3)
    keys, payload = _edge_table(64, 64, 3, seed=4)   # dense key collisions
    probe = bind[:, _PROBE_COL]
    got = fused_join(jnp.asarray(bind), jnp.asarray(valid),
                     jnp.asarray(probe), jnp.asarray(keys),
                     jnp.asarray(payload), capacity)
    want = _oracle_join(bind, valid, probe, keys, payload, capacity,
                        monkeypatch)
    assert int(got[3]) == int(want[3]) > 0
