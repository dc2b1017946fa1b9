"""Blocked sort-merge semi-join membership + join-count Pallas kernels.

The hot loop of distributed subgraph matching (executor §7.3) is: given a
binding-table column (candidate vertex ids) and a sorted edge-table key
column, decide for every candidate whether/how often it appears.  gStore
answers this with a VS-tree; on TPU the natural shape is a *blocked
compare*: both sides sorted, each query block overlaps a short contiguous
run of table blocks, and each (query-block, table-block) pair is a dense
(BM, BN) equality compare on the VPU.

Grid: (num_query_blocks, max_overlap).  A scalar-prefetch array holds the
first overlapping table-block index per query block; the table BlockSpec
index_map adds the inner grid coordinate (held at the last overlapping
block once the overlap is exhausted, so skipped steps re-use the
resident block instead of fetching a new one), so each step streams
exactly the table blocks that can contain matches.

Blocks travel as ``(n, 1, B)`` arrays with a ``(None, 1, B)`` block
spec: the kernel sees a ``(1, B)`` lane row, and the block's last two
dims (1 == the array's, B a multiple of 128) satisfy the TPU tiling
rule.  VMEM per step: BM*4 + BN*4 + BM*BN*4 bytes; defaults (BM=512,
BN=512) use ~1 MB -- well inside the ~16 MB v5e VMEM budget, leaving
room for double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BM = 512   # query block (lane-aligned: 4 * 128)
BN = 512   # table block


def _semijoin_kernel(first_blk_ref,   # scalar prefetch: (num_qblocks,)
                     width_ref,       # scalar prefetch: per-block overlap
                     q_ref,           # (1, BM) query block
                     t_ref,           # (1, BN) table block
                     o_ref):          # (1, BM) int32 mask out
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # steps beyond this query block's true overlap re-see the last
    # overlapping table block -- skip them.
    @pl.when(j < width_ref[i])
    def _compute():
        q = q_ref[0, :]                       # (BM,)
        t = t_ref[0, :]                       # (BN,)
        eq = q[:, None] == t[None, :]         # (BM, BN) dense compare (VPU)
        hit = eq.any(axis=1).astype(jnp.int32)
        o_ref[0, :] = jnp.maximum(o_ref[0, :], hit)


def _count_kernel(first_blk_ref, width_ref, q_ref, t_ref, o_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(j < width_ref[i])
    def _compute():
        q = q_ref[0, :]
        t = t_ref[0, :]
        eq = (q[:, None] == t[None, :]).astype(jnp.int32)
        o_ref[0, :] += eq.sum(axis=1)


def _pair_kernel(first_blk_ref, width_ref, qs_ref, qo_ref, ts_ref, to_ref,
                 o_ref):
    """Pair membership: query (s, o) pairs vs table (s, o) pairs, both
    lexsorted by (s, o); the block plan overlaps on the subject column.
    Two dense equality compares ANDed on the VPU per (BM, BN) step."""
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(j < width_ref[i])
    def _compute():
        qs = qs_ref[0, :]
        qo = qo_ref[0, :]
        ts = ts_ref[0, :]
        to = to_ref[0, :]
        eq = (qs[:, None] == ts[None, :]) & (qo[:, None] == to[None, :])
        hit = eq.any(axis=1).astype(jnp.int32)
        o_ref[0, :] = jnp.maximum(o_ref[0, :], hit)


def _query_spec(b: int) -> pl.BlockSpec:
    return pl.BlockSpec((None, 1, b), lambda i, j, fb, wd: (i, 0, 0))


def _table_spec(b: int) -> pl.BlockSpec:
    # clamp the inner coordinate to the block's own overlap: a skipped
    # step maps to the block already resident, so nothing is re-fetched
    return pl.BlockSpec(
        (None, 1, b),
        lambda i, j, fb, wd: (fb[i] + jnp.minimum(j, wd[i] - 1), 0, 0))


def _blocked_call(kernel, queries, tables, first_blk, widths, nsteps,
                  interpret):
    """Shared pallas_call of the blocked kernels: ``queries`` are the
    (nq_blocks, 1, BM) query-side arrays, ``tables`` the (nt_blocks, 1,
    BN) table-side arrays; out is (nq_blocks, 1, BM) int32."""
    nqb, _, bm = queries[0].shape
    bn = tables[0].shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nqb, nsteps),
        in_specs=([_query_spec(bm)] * len(queries)
                  + [_table_spec(bn)] * len(tables)),
        out_specs=_query_spec(bm),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nqb, 1, bm), jnp.int32),
        interpret=interpret,
    )(first_blk, widths, *queries, *tables)


def semijoin_blocks(queries_3d: jax.Array, table_3d: jax.Array,
                    first_blk: jax.Array, widths: jax.Array, nsteps: int,
                    count: bool = False, interpret: bool = True) -> jax.Array:
    """Run the blocked kernel.

    queries_3d: (nq_blocks, 1, BM) sorted, padded with INT32_MAX.
    table_3d:   (nt_blocks, 1, BN) sorted, padded with INT32_MAX.
    first_blk:  (nq_blocks,) first overlapping table block per query block.
    widths:     (nq_blocks,) true overlap width per query block (>= 1,
                first_blk + width <= nt_blocks).
    nsteps:     inner grid extent (max overlap width).
    """
    kern = _count_kernel if count else _semijoin_kernel
    return _blocked_call(kern, (queries_3d,), (table_3d,), first_blk,
                         widths, nsteps, interpret)


def pair_semijoin_blocks(qs_3d: jax.Array, qo_3d: jax.Array,
                         ts_3d: jax.Array, to_3d: jax.Array,
                         first_blk: jax.Array, widths: jax.Array,
                         nsteps: int, interpret: bool = True) -> jax.Array:
    """Run the blocked pair-membership kernel.

    qs/qo: (nq_blocks, 1, BM) query pairs lexsorted by (s, o), INT32_MAX
    padded; ts/to: (nt_blocks, 1, BN) table pairs likewise.  first_blk /
    widths: subject-column block plan (see ``ops._block_plan_1d``)."""
    return _blocked_call(_pair_kernel, (qs_3d, qo_3d), (ts_3d, to_3d),
                         first_blk, widths, nsteps, interpret)


# ----------------------------------------------------------------------
# Hash-based binding-row dedup + the fused dedup->expand->filter join
# ----------------------------------------------------------------------
#
# Both kernels are scalar programs over SMEM: every operand is a flat
# int32 array in scalar memory, rows are read one int32 at a time, and
# the hash probe, the binary searches and the expansion are scalar
# loops.  Nothing gathers or scatters a vector, which Mosaic cannot
# lower; the price is that the whole working set has to fit the 1 MiB
# SMEM of a v5e core.  A gathered step of the served capacity tiers is
# far bigger, so ``core.spmd`` calls them only when they are forced on
# (``REPRO_SPMD_PALLAS=1``); on a chip the jnp composition is the default.
#
# Binding tables arrive column-major (``bind[v * C + i]`` is column v of
# row i) so a row's columns are V scalar loads.

_HASH_SEED = 0x811C9DC5
_HASH_MUL = 0x9E3779B1 - (1 << 32)      # as int32: multiply wraps alike
_HASH_FIN = 0xC2B2AE35 - (1 << 32)


def _srl(x, n: int):
    return jax.lax.shift_right_logical(x, jnp.int32(n))


def _row_slot(bind_ref, i, C: int, V: int, H: int):
    """Open-addressing start slot of row ``i``: a multiplicative
    xor-mix over its int32 columns, avalanched, masked to the
    power-of-two table size ``H``.  Collisions are fine (resolved by
    full-row compare)."""
    h = jnp.int32(_HASH_SEED - (1 << 32))
    for v in range(V):                       # static unroll: V is tiny
        h = (h ^ bind_ref[v * C + i]) * jnp.int32(_HASH_MUL)
        h = h ^ _srl(h, 15)
    h = h ^ _srl(h, 13)
    h = h * jnp.int32(_HASH_FIN)
    h = h ^ _srl(h, 16)
    return h & (H - 1)


def _rows_equal(bind_ref, a, b, C: int, V: int):
    same = jnp.bool_(True)
    for v in range(V):
        same = same & (bind_ref[v * C + a] == bind_ref[v * C + b])
    return same


def _hash_dedup_rows(bind_ref, valid_ref, table_ref, keep_ref,
                     C: int, V: int, H: int):
    """Serial open-addressed insert of every valid row; writes the
    first-occurrence keep flag (int32 0/1, original row positions) of
    every row into ``keep_ref`` (C,).  ``table_ref`` (H,) holds
    row-index+1 (0 = empty).  Exact: equal start slots fall through to
    a full-row compare, so hash collisions can never merge distinct
    rows."""
    def clear(k, c):
        table_ref[k] = jnp.int32(0)
        return c

    jax.lax.fori_loop(0, H, clear, 0)

    def insert(i, c):
        # probe until an empty slot (-> first occurrence, insert) or an
        # occupied slot whose row equals ours (-> duplicate).  At most C
        # rows ever insert and H >= 2C, so an empty slot always exists.
        def probing(carry):
            return carry[1] == 0

        def probe(carry):
            slot, _ = carry
            occ = table_ref[slot]
            empty = occ == 0
            same = (~empty) & _rows_equal(
                bind_ref, jnp.maximum(occ - 1, 0), i, C, V)
            verdict = jnp.where(empty, 1, jnp.where(same, 2, 0))
            nxt = jnp.where(verdict == 0, (slot + 1) & (H - 1), slot)
            return nxt, verdict

        # invalid rows skip probing entirely (verdict pre-set to "dup")
        start = (_row_slot(bind_ref, i, C, V, H),
                 jnp.where(valid_ref[i] != 0, 0, 2).astype(jnp.int32))
        slot, verdict = jax.lax.while_loop(probing, probe, start)

        @pl.when(verdict == 1)
        def _first_occurrence():
            table_ref[slot] = i + 1

        keep_ref[i] = (verdict == 1).astype(jnp.int32)
        return c

    jax.lax.fori_loop(0, C, insert, 0)


def _dedup_kernel(bind_ref, valid_ref, keep_ref, table_ref, *,
                  C: int, V: int, H: int):
    _hash_dedup_rows(bind_ref, valid_ref, table_ref, keep_ref, C, V, H)


def _bsearch(keys_ref, T: int, x, right: bool):
    """Branchless binary search: insertion point of scalar ``x`` in the
    ascending ``keys_ref`` (searchsorted left/right), as a fixed-trip
    scalar loop over SMEM."""
    def step(_, carry):
        lo, sz = carry
        half = sz // 2
        mid = jnp.minimum(lo + half, T - 1)
        val = keys_ref[mid]
        go = ((val <= x) if right else (val < x)) & (sz > 0)
        lo = jnp.where(go, mid + 1, lo)
        sz = jnp.where(sz > 0, jnp.where(go, sz - half - 1, half), 0)
        return lo, sz

    lo, _ = jax.lax.fori_loop(0, max(T.bit_length() + 1, 1), step,
                              (jnp.int32(0), jnp.int32(T)))
    return lo


def _fused_join_kernel(bind_ref, valid_ref, probe_ref, keys_ref, pay_ref,
                       out_bind_ref, out_col_ref, out_valid_ref, over_ref,
                       table_ref, keep_ref, lo_ref, cnt_ref, *,
                       C: int, V: int, T: int, H: int, capacity: int):
    """dedup -> expand -> filter in one SMEM pass.

    Replaces the ``_dedup_padded`` + ``_expand_fixed`` composition of
    the SPMD gather step without materializing the deduped table:
    duplicate gathered rows are dropped in place (hash dedup, original
    row order -- order never matters downstream), the surviving rows
    binary-search the sorted edge-key column for their join ranges, and
    the expansion writes each kept row's matches into consecutive output
    slots, in row order -- the same slots the composition's cumsum'd
    inverse map fills.  Overflow semantics are exactly
    ``_expand_fixed``'s, including the conservative int32 cumsum
    wrap-risk guard -- the retry ladder must see identical overflow
    counts whichever path traced."""
    _hash_dedup_rows(bind_ref, valid_ref, table_ref, keep_ref, C, V, H)

    # join range of every kept row; total and the largest count feed
    # the overflow report
    def ranges(r, carry):
        total, big = carry
        x = probe_ref[r]
        lo = _bsearch(keys_ref, T, x, right=False)
        cnt = jnp.where(keep_ref[r] != 0,
                        _bsearch(keys_ref, T, x, right=True) - lo, 0)
        lo_ref[r] = lo
        cnt_ref[r] = cnt
        return total + cnt, jnp.maximum(big, cnt)

    total, big = jax.lax.fori_loop(0, C, ranges,
                                   (jnp.int32(0), jnp.int32(0)))

    def clear(t, c):
        for v in range(V):
            out_bind_ref[v * capacity + t] = jnp.int32(-1)
        out_col_ref[t] = jnp.int32(-1)
        out_valid_ref[t] = jnp.int32(0)
        return c

    jax.lax.fori_loop(0, capacity, clear, 0)

    def expand(r, pos):
        n = jnp.clip(capacity - pos, 0, cnt_ref[r])
        lo = lo_ref[r]

        def emit(k, c):
            t = pos + k
            for v in range(V):
                out_bind_ref[v * capacity + t] = bind_ref[v * C + r]
            out_col_ref[t] = pay_ref[jnp.minimum(lo + k, T - 1)]
            out_valid_ref[t] = jnp.int32(1)
            return c

        jax.lax.fori_loop(0, n, emit, 0)
        return pos + cnt_ref[r]

    jax.lax.fori_loop(0, C, expand, jnp.int32(0))

    # identical wrap-risk guard to _expand_fixed (int32 cumsum can wrap
    # past 2^31 total expansion rows; treat as conservative overflow)
    wrap_risk = big > (2 ** 31 - 1) // max(C, 1)
    over_ref[0] = jnp.where(wrap_risk, jnp.int32(capacity + 1),
                            jnp.maximum(total - capacity, 0))


def _smem_spec() -> pl.BlockSpec:
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def dedup_blocks(bind_cm: jax.Array, valid_i32: jax.Array, C: int, V: int,
                 H: int, interpret: bool = True) -> jax.Array:
    """Run the hash-dedup kernel.  bind_cm (V * C,) int32 column-major,
    valid (C,) int32; returns the (C,) int32 first-occurrence keep
    flags."""
    return pl.pallas_call(
        functools.partial(_dedup_kernel, C=C, V=V, H=H),
        out_shape=jax.ShapeDtypeStruct((C,), jnp.int32),
        in_specs=[_smem_spec(), _smem_spec()],
        out_specs=_smem_spec(),
        scratch_shapes=[pltpu.SMEM((H,), jnp.int32)],
        interpret=interpret,
    )(bind_cm, valid_i32)


def fused_join_blocks(bind_cm: jax.Array, valid_i32: jax.Array,
                      probe: jax.Array, keys: jax.Array, pay: jax.Array,
                      C: int, V: int, capacity: int, H: int,
                      interpret: bool = True):
    """Run the fused dedup->expand->filter kernel over flat int32
    operands (bind column-major (V * C,), valid/probe (C,), keys/payload
    (T,)).  Returns (new_bind (V * capacity,) column-major, new_col
    (capacity,), new_valid (capacity,), overflow (1,)), all int32."""
    T = keys.shape[0]
    return pl.pallas_call(
        functools.partial(_fused_join_kernel, C=C, V=V, T=T, H=H,
                          capacity=capacity),
        out_shape=(jax.ShapeDtypeStruct((V * capacity,), jnp.int32),
                   jax.ShapeDtypeStruct((capacity,), jnp.int32),
                   jax.ShapeDtypeStruct((capacity,), jnp.int32),
                   jax.ShapeDtypeStruct((1,), jnp.int32)),
        in_specs=[_smem_spec()] * 5,
        out_specs=(_smem_spec(),) * 4,
        scratch_shapes=[pltpu.SMEM((H,), jnp.int32),
                        pltpu.SMEM((C,), jnp.int32),
                        pltpu.SMEM((C,), jnp.int32),
                        pltpu.SMEM((C,), jnp.int32)],
        interpret=interpret,
    )(bind_cm, valid_i32, probe, keys, pay)
