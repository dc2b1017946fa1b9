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


def _blocked_call(kernel, name, queries, tables, first_blk, widths, nsteps,
                  interpret):
    """Shared pallas_call of the blocked kernels: ``name`` names the
    kernel in HLO and profiler traces, ``queries`` are the (nq_blocks,
    1, BM) query-side arrays, ``tables`` the (nt_blocks, 1, BN)
    table-side arrays; out is (nq_blocks, 1, BM) int32."""
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
        name=name,
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
    kern, name = ((_count_kernel, "join_count") if count
                  else (_semijoin_kernel, "semijoin"))
    return _blocked_call(kern, name, (queries_3d,), (table_3d,), first_blk,
                         widths, nsteps, interpret)


def pair_semijoin_blocks(qs_3d: jax.Array, qo_3d: jax.Array,
                         ts_3d: jax.Array, to_3d: jax.Array,
                         first_blk: jax.Array, widths: jax.Array,
                         nsteps: int, interpret: bool = True) -> jax.Array:
    """Run the blocked pair-membership kernel.

    qs/qo: (nq_blocks, 1, BM) query pairs lexsorted by (s, o), INT32_MAX
    padded; ts/to: (nt_blocks, 1, BN) table pairs likewise.  first_blk /
    widths: subject-column block plan (see ``ops._block_plan_1d``)."""
    return _blocked_call(_pair_kernel, "pair_semijoin", (qs_3d, qo_3d),
                         (ts_3d, to_3d), first_blk, widths, nsteps,
                         interpret)

