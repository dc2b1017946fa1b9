"""Pure-jnp oracles for every Pallas kernel in this package, and for
the SPMD match loop's gathered-row dedup and expansion step.

These are the semantics of record: kernel tests sweep shapes/dtypes and
``assert_allclose`` against these functions.  They are also the fallback
implementation on backends without Pallas support.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp


# ----------------------------------------------------------------------
# Semi-join membership: queries ∈ sorted table?
# ----------------------------------------------------------------------

def semijoin_mask_ref(queries: jax.Array, table_sorted: jax.Array) -> jax.Array:
    """mask[i] = any(table == queries[i]);  table_sorted ascending.
    Sentinel entries (INT32_MIN padding) never match real keys."""
    pos = jnp.searchsorted(table_sorted, queries)
    pos = jnp.clip(pos, 0, table_sorted.shape[0] - 1)
    return table_sorted[pos] == queries


# ----------------------------------------------------------------------
# Join count: #table entries equal to each left key (expansion sizes)
# ----------------------------------------------------------------------

def join_count_ref(left_keys: jax.Array, table_sorted: jax.Array) -> jax.Array:
    lo = jnp.searchsorted(table_sorted, left_keys, side="left")
    hi = jnp.searchsorted(table_sorted, left_keys, side="right")
    return (hi - lo).astype(jnp.int32)


# ----------------------------------------------------------------------
# Fixed-capacity join expansion by binary search (the oracle of
# ``core.spmd._expand_fixed``, which builds the same index maps directly)
# ----------------------------------------------------------------------

def expand_fixed_ref(bind: jax.Array, valid: jax.Array, col_vals: jax.Array,
                     keys_sorted: jax.Array, payload: jax.Array,
                     capacity: int):
    """Join-expand the (C, V) binding table against a sorted (keys ->
    payload) edge table into ``capacity`` output slots.  Returns
    (new_bind, new_payload_col, new_valid, overflow rows).  Both index
    maps -- binding row -> its key run, output slot -> binding row --
    are ``jnp.searchsorted`` binary searches."""
    C, V = bind.shape
    probe = jnp.where(valid, col_vals, jnp.iinfo(jnp.int32).max)
    lo = jnp.searchsorted(keys_sorted, probe, side="left")
    cnt = jnp.where(valid, join_count_ref(probe, keys_sorted), 0)
    cnt = cnt.astype(jnp.int32)
    wrap_risk = (jnp.max(cnt, initial=0) > (2 ** 31 - 1) // max(C, 1)
                 if C else jnp.bool_(False))
    start = jnp.cumsum(cnt) - cnt                     # output offsets
    total = start[-1] + cnt[-1] if C else jnp.int32(0)
    # inverse map: output slot t -> source row r
    t = jnp.arange(capacity)
    r = jnp.searchsorted(start, t, side="right") - 1
    r = jnp.clip(r, 0, C - 1)
    k = t - start[r]
    ok = (t < total) & (k < cnt[r])
    src = jnp.clip(lo[r] + k, 0, keys_sorted.shape[0] - 1)
    new_col = jnp.where(ok, payload[src], -1)
    new_bind = jnp.where(ok[:, None], bind[r], -1)
    over = jnp.maximum(total - capacity, 0).astype(jnp.int32)
    over = jnp.where(wrap_risk, jnp.int32(capacity + 1), over)
    return new_bind, new_col, ok, over


# ----------------------------------------------------------------------
# Pair semi-join membership: (q_s, q_o) ∈ table pairs?  (the cycle-close
# probe of the SPMD match loop; int32-safe -- no 42-bit key composition)
# ----------------------------------------------------------------------

def pair_semijoin_ref(q_s: jax.Array, q_o: jax.Array,
                      t_s: jax.Array, t_o: jax.Array) -> jax.Array:
    """mask[i] = any table row r with (t_s[r], t_o[r]) == (q_s[i], q_o[i]).

    Neither side needs to be sorted.  Exact O((T+Q) log(T+Q)) merge:
    lexsort the concatenation with table rows ordered before equal query
    rows, then each query row hits iff the nearest preceding table row
    carries the same pair."""
    T, Q = t_s.shape[0], q_s.shape[0]
    if T == 0 or Q == 0:
        return jnp.zeros(q_s.shape, bool)
    cs = jnp.concatenate([t_s, q_s]).astype(jnp.int32)
    co = jnp.concatenate([t_o, q_o]).astype(jnp.int32)
    flag = jnp.concatenate([jnp.zeros(T, jnp.int32), jnp.ones(Q, jnp.int32)])
    order = jnp.lexsort((flag, co, cs))
    fs, fo, ff = cs[order], co[order], flag[order]
    idx = jnp.arange(T + Q)
    last_tab = jax.lax.cummax(jnp.where(ff == 0, idx, -1))
    lt = jnp.clip(last_tab, 0, T + Q - 1)
    hit_sorted = (ff == 1) & (last_tab >= 0) & (fs[lt] == fs) & (fo[lt] == fo)
    out = jnp.zeros(T + Q, bool).at[order].set(hit_sorted)
    return out[T:]


# ----------------------------------------------------------------------
# Binding-row dedup (first-occurrence keep mask over a padded table)
# ----------------------------------------------------------------------

def dedup_rows_ref(bind: jax.Array, valid: jax.Array) -> jax.Array:
    """keep[i] = valid[i] and no earlier valid row j < i has
    bind[j] == bind[i] (all columns).  The oracle of the match loop's
    gathered-row dedup (``core.spmd._dedup_padded``): exact, first
    occurrence by original index, keep mask returned in original row
    positions.

    Implemented as a stable column-wise lexsort (valid rows first,
    ties preserve original order, so the first of each duplicate run is
    the earliest index) + adjacent compare + scatter back."""
    C, V = bind.shape
    if V == 0:
        return jnp.zeros((C,), bool).at[0].set(valid.any())
    keys = tuple(bind[:, v] for v in range(V - 1, -1, -1)) \
        + ((~valid).astype(jnp.int32),)
    order = jnp.lexsort(keys)                # stable; invalid rows last
    bs, vs = bind[order], valid[order]
    dup = jnp.zeros((C,), bool).at[1:].set(
        jnp.all(bs[1:] == bs[:-1], axis=1) & vs[1:] & vs[:-1])
    keep_sorted = vs & ~dup
    return jnp.zeros((C,), bool).at[order].set(keep_sorted)


# ----------------------------------------------------------------------
# Flash attention (causal, optional sliding window, GQA)
# ----------------------------------------------------------------------

def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool = True,
                  window: Optional[int] = None,
                  scale: Optional[float] = None) -> jax.Array:
    """Reference attention.

    q: [B, Hq, Sq, D]; k, v: [B, Hkv, Skv, D]; Hq % Hkv == 0 (GQA).
    window: sliding-window size (key j visible to query i iff
            i - window < j <= i), mixtral-style.
    Returns [B, Hq, Sq, D] in q.dtype; accumulation in fp32.
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    g = Hq // Hkv
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    kk = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kk.astype(jnp.float32)) * scale
    # positions: queries occupy the last Sq slots of the Skv timeline
    qpos = jnp.arange(Sq) + (Skv - Sq)
    kpos = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vv.astype(jnp.float32))
    return out.astype(q.dtype)


# ----------------------------------------------------------------------
# MoE token dispatch (dense formulation oracle)
# ----------------------------------------------------------------------

def moe_dispatch_ref(x: jax.Array, gates: jax.Array, topk: int):
    """Return (combine_weights [T, E], dispatch_mask [T, E]) for top-k
    routing with softmax-over-selected renormalization."""
    T, E = gates.shape
    vals, idx = jax.lax.top_k(gates, topk)
    w = jax.nn.softmax(vals, axis=-1)
    combine = jnp.zeros((T, E), gates.dtype)
    combine = combine.at[jnp.arange(T)[:, None], idx].set(w)
    return combine, combine > 0
