"""jit'd public wrappers around the Pallas kernels.

Each op pads/blocks its inputs, dispatches to the kernel (interpret mode
on non-TPU backends so the kernel *body* is what gets validated), and
un-pads the result.  ``ref.py`` holds the oracles.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from ..constants import INT32_SENTINEL, MAX_VERTEX_ID  # noqa: F401
from .flash_attention import flash_attention
from .semijoin import (BM, BN, dedup_blocks, fused_join_blocks,
                       pair_semijoin_blocks, semijoin_blocks)

#: the shared pad/fill sentinel (see ``repro.constants``): sorts last,
#: never equals a real vertex id (ids are bounded by ``MAX_VERTEX_ID``,
#: enforced at ``RDFGraph`` construction).
INT32_MAX = INT32_SENTINEL

def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret_default(interpret: Optional[bool]) -> bool:
    return (not _on_tpu()) if interpret is None else interpret


# ----------------------------------------------------------------------
# Semi-join membership / join count
# ----------------------------------------------------------------------

def _pad_tail(x: jax.Array, mult: int) -> jax.Array:
    """Pad to a multiple of ``mult`` with the INT32_MAX sentinel (sorts
    last; never equals a real vertex id, which are < 2^21)."""
    pad = (-x.shape[0]) % mult
    if pad:
        x = jnp.concatenate([x, jnp.full((pad,), INT32_MAX, x.dtype)])
    return x


def compact_rows(sel: jax.Array, cols: Tuple[jax.Array, ...], size: int,
                 fill: int = INT32_MAX
                 ) -> Tuple[Tuple[jax.Array, ...], jax.Array]:
    """Pack the rows where ``sel`` holds into fixed-``size`` buffers,
    padded with the ``fill`` sentinel -- the shape-static gather that
    lets a data-dependent selection travel through jit / collectives
    (e.g. the SPMD edge-shipping step gathers each device's rows of one
    property this way before an ``all_gather``; the match seed step and
    the binding-table compaction pack rows with it too).

    Each entry of ``cols`` is indexed on its leading axis, so 1-D key
    columns and 2-D row tables both work.  Returns ``(packed columns,
    valid mask)``.  Selected rows beyond ``size`` are dropped (not an
    error): callers either guarantee ``sel.sum() <= size`` statically
    (the SPMD planner sizes the buffer from the ``SiteStore`` residency
    metadata) or count the surplus as overflow themselves (the
    capacity-retry ladder).
    """
    idx = jnp.nonzero(sel, size=size, fill_value=-1)[0]
    ok = idx >= 0
    idxc = jnp.clip(idx, 0, sel.shape[0] - 1)
    return tuple(jnp.where(ok.reshape((size,) + (1,) * (c.ndim - 1)),
                           c[idxc].astype(jnp.int32), fill)
                 for c in cols), ok


def lexsort_passes(keys: Sequence[jax.Array]) -> jax.Array:
    """The permutation of ``jnp.lexsort(keys)`` (last key primary, ties
    in input order), built as one stable single-key sort per key, least
    significant first -- a stable LSD sort is lexicographic.  The TPU
    compiler's time for one sort grows steeply with its operand count:
    for a described v5e at 64k rows, about 20 s for a one-key sort
    against 130-160 s for a three-key one, so k passes of two operands
    each compile several times faster than one sort of k + 1."""
    perm = jnp.arange(keys[0].shape[0], dtype=jnp.int32)
    for key in keys:
        _, perm = jax.lax.sort((key[perm], perm), num_keys=1,
                               is_stable=True)
    return perm


def _block_plan_1d(qs_p: jax.Array, ts_p: jax.Array, bm: int, bn: int,
                   jit_safe: bool):
    """Block plan on one sorted+padded key column: first overlapping
    table block per query block, per-block overlap widths, and the
    static inner-grid extent.

    The plan is data-dependent metadata computed on host -- the paper's
    control-site role; the heavy compare runs in the kernel.
    ``jit_safe=True`` skips the host sync on the max overlap width so
    the op traces inside jit/shard_map (the SPMD match loop): the inner
    grid then statically spans every table block, with non-overlapping
    steps skipped by the kernel's width guard.
    """
    nqb = qs_p.shape[0] // bm
    ntb = ts_p.shape[0] // bn
    qmin = qs_p[::bm]
    qmax = qs_p[bm - 1::bm]
    lo = (jnp.searchsorted(ts_p, qmin, side="left") // bn).astype(jnp.int32)
    hi = (jnp.clip(jnp.searchsorted(ts_p, qmax, side="right") - 1, 0, None)
          // bn).astype(jnp.int32)
    lo = jnp.minimum(lo, ntb - 1)
    widths = jnp.maximum(hi - lo + 1, 1).astype(jnp.int32)
    if jit_safe:
        width = ntb                   # static worst case, no host sync
    else:
        width = int(jax.device_get(jnp.max(widths))) if nqb else 1
    return lo, widths, max(width, 1)


def _prep_blocks(queries: jax.Array, table_sorted: jax.Array,
                 bm: int, bn: int, jit_safe: bool = False):
    """Sort+pad the query side, pad the table, compute the block plan
    (see ``_block_plan_1d``)."""
    order = jnp.argsort(queries)
    qs = queries[order]
    nq = qs.shape[0]
    qs_p = _pad_tail(qs, bm)
    ts_p = _pad_tail(table_sorted, bn)
    nqb = qs_p.shape[0] // bm
    ntb = ts_p.shape[0] // bn
    lo, widths, width = _block_plan_1d(qs_p, ts_p, bm, bn, jit_safe)
    return (order, qs_p.reshape(nqb, 1, bm), ts_p.reshape(ntb, 1, bn), lo,
            widths, width, nq)


def semijoin(queries: jax.Array, table_sorted: jax.Array,
             interpret: Optional[bool] = None,
             bm: int = BM, bn: int = BN,
             jit_safe: bool = False) -> jax.Array:
    """Boolean mask: queries[i] present in sorted table.  Kernel-backed.
    ``jit_safe=True`` makes the op traceable inside jit (static block
    plan, see ``_prep_blocks``)."""
    queries = queries.astype(jnp.int32)
    table_sorted = table_sorted.astype(jnp.int32)
    if queries.shape[0] == 0 or table_sorted.shape[0] == 0:
        return jnp.zeros(queries.shape, dtype=bool)
    order, q3d, t3d, lo, widths, width, nq = _prep_blocks(
        queries, table_sorted, bm, bn, jit_safe=jit_safe)
    got = semijoin_blocks(q3d, t3d, lo, widths, width, count=False,
                          interpret=_interpret_default(interpret))
    mask_sorted = got.reshape(-1)[:nq] > 0
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(nq))
    return mask_sorted[inv]


def join_count(queries: jax.Array, table_sorted: jax.Array,
               interpret: Optional[bool] = None,
               bm: int = BM, bn: int = BN,
               jit_safe: bool = False) -> jax.Array:
    """counts[i] = multiplicity of queries[i] in the sorted table.
    ``jit_safe=True`` makes the op traceable inside jit (static block
    plan, see ``_prep_blocks``)."""
    queries = queries.astype(jnp.int32)
    table_sorted = table_sorted.astype(jnp.int32)
    if queries.shape[0] == 0 or table_sorted.shape[0] == 0:
        return jnp.zeros(queries.shape, dtype=jnp.int32)
    order, q3d, t3d, lo, widths, width, nq = _prep_blocks(
        queries, table_sorted, bm, bn, jit_safe=jit_safe)
    got = semijoin_blocks(q3d, t3d, lo, widths, width, count=True,
                          interpret=_interpret_default(interpret))
    cnt_sorted = got.reshape(-1)[:nq]
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(nq))
    return cnt_sorted[inv]


def pair_semijoin(q_s: jax.Array, q_o: jax.Array,
                  t_s: jax.Array, t_o: jax.Array,
                  interpret: Optional[bool] = None,
                  bm: int = BM, bn: int = BN,
                  jit_safe: bool = False) -> jax.Array:
    """mask[i] = any table row r with (t_s[r], t_o[r]) == (q_s[i], q_o[i]).

    Neither side needs to be pre-sorted (both are lexsorted internally;
    the block plan overlaps on the subject column).  This is the
    cycle-close probe of the SPMD match loop: an exact int32 pair
    membership with no 42-bit key composition, so it runs with jax's
    default x64-disabled config.  ``jit_safe=True`` as in ``semijoin``.
    """
    q_s, q_o = q_s.astype(jnp.int32), q_o.astype(jnp.int32)
    t_s, t_o = t_s.astype(jnp.int32), t_o.astype(jnp.int32)
    if q_s.shape[0] == 0 or t_s.shape[0] == 0:
        return jnp.zeros(q_s.shape, dtype=bool)
    torder = lexsort_passes((t_o, t_s))
    ts, to = _pad_tail(t_s[torder], bn), _pad_tail(t_o[torder], bn)
    qorder = lexsort_passes((q_o, q_s))
    qs, qo = _pad_tail(q_s[qorder], bm), _pad_tail(q_o[qorder], bm)
    nq = q_s.shape[0]
    nqb, ntb = qs.shape[0] // bm, ts.shape[0] // bn
    # plan on the subject column alone: both sides lexsorted by (s, o),
    # so a query block's candidate table rows lie in its subject span
    lo, widths, width = _block_plan_1d(qs, ts, bm, bn, jit_safe)
    got = pair_semijoin_blocks(qs.reshape(nqb, 1, bm), qo.reshape(nqb, 1, bm),
                               ts.reshape(ntb, 1, bn), to.reshape(ntb, 1, bn),
                               lo, widths, width,
                               interpret=_interpret_default(interpret))
    mask_sorted = got.reshape(-1)[:nq] > 0
    inv = jnp.zeros_like(qorder).at[qorder].set(jnp.arange(nq))
    return mask_sorted[inv]


# ----------------------------------------------------------------------
# Hash dedup / fused dedup->expand->filter join
# ----------------------------------------------------------------------

def _hash_size(C: int) -> int:
    """Power-of-two open-addressing table size >= 2C (load factor
    <= 0.5, so probing terminates fast and an empty slot always
    exists)."""
    H = 8
    while H < 2 * C:
        H *= 2
    return H


def _column_major(bind: jax.Array) -> jax.Array:
    return bind.astype(jnp.int32).T.reshape(-1)


def dedup_rows(bind: jax.Array, valid: jax.Array,
               interpret: Optional[bool] = None) -> jax.Array:
    """First-occurrence keep mask over the valid rows of a padded
    binding table: ``keep[i]`` is True iff ``valid[i]`` and no earlier
    valid row equals row ``i``.  Exact (open-addressed int32 hash with
    full-row compare on collision) and in place -- unlike the lexsort
    oracle it never reorders rows, which no caller depends on anyway.
    Every operand lives in SMEM (1 MiB on a v5e core), so on a chip the
    table has to fit there."""
    C, V = bind.shape
    keep = dedup_blocks(_column_major(bind), valid.astype(jnp.int32),
                        C, V, _hash_size(C),
                        interpret=_interpret_default(interpret))
    return keep > 0


def fused_join(bind: jax.Array, valid: jax.Array, probe: jax.Array,
               keys_sorted: jax.Array, payload: jax.Array, capacity: int,
               interpret: Optional[bool] = None
               ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused ``dedup_rows`` + join-expand against a sorted (keys ->
    payload) edge table, one kernel pass (the SPMD gather step without
    materializing the deduped table).  Same contract as
    ``core.spmd._expand_fixed`` composed after a dedup: returns
    (new_bind (capacity, V), new_col, new_valid, overflow) where
    overflow counts result rows that did not fit (identical to the
    composition's count, including the int32 cumsum wrap-risk guard);
    output row *placement* differs (original gathered order, not
    lexsorted), which no caller observes.  Every operand and output
    lives in SMEM, as for ``dedup_rows``."""
    C, V = bind.shape
    nb, nc, nv, over = fused_join_blocks(
        _column_major(bind), valid.astype(jnp.int32),
        probe.astype(jnp.int32), keys_sorted.astype(jnp.int32),
        payload.astype(jnp.int32), C, V, capacity, _hash_size(C),
        interpret=_interpret_default(interpret))
    return nb.reshape(V, capacity).T, nc, nv > 0, over[0]


# ----------------------------------------------------------------------
# Attention
# ----------------------------------------------------------------------

def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              causal: bool = True, window: Optional[int] = None,
              scale: Optional[float] = None,
              block_q: int = 128, block_k: int = 128,
              interpret: Optional[bool] = None,
              use_kernel: bool = True) -> jax.Array:
    """Kernel-backed attention with padding to block multiples.

    Falls back to the jnp oracle when ``use_kernel=False`` (used by the
    dry-run path, where XLA's fused attention is what we cost-model) or
    for tiny shapes.
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    if not use_kernel or Sq * Skv <= 128 * 128:
        return ref.attention_ref(q, k, v, causal, window, scale)
    bq = min(block_q, max(8, Sq))
    bk = min(block_k, max(8, Skv))
    if Sq % bq == 0 and Skv % bk == 0:
        return flash_attention(q, k, v, causal=causal, window=window,
                               scale=scale, block_q=bq, block_k=bk,
                               interpret=_interpret_default(interpret))
    if causal and Sq == Skv:
        # pad q and kv equally at the END of the timeline: real queries
        # keep positions 0..Sq-1 and never attend padded keys (causal
        # mask: padded key positions >= Sq > any real query position).
        step = int(np.lcm(bq, bk))
        pad = (-Sq) % step
        qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        out = flash_attention(qp, kp, vp, causal=causal, window=window,
                              scale=scale, block_q=bq, block_k=bk,
                              interpret=_interpret_default(interpret))
        return out[:, :, :Sq]
    # irregular cross-attention shapes: oracle fallback
    return ref.attention_ref(q, k, v, causal, window, scale)
