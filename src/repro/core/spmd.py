"""SPMD distributed subgraph matching: sites = devices on a mesh axis.

This is the TPU-native rendering of the paper's online phase (§7.3):
every site holds its allocated fragments as dense, predicate-sorted edge
tables; the query runs as the *same* program on every site over its
local shard (shard_map), producing fixed-capacity binding tables.

Multi-device exactness comes from the broadcast join: before every join
step the (small, fixed-capacity) binding tables are ``all_gather``-ed
across the mesh axis, deduplicated, and expanded against each device's
*local* edge table -- the paper's "ship intermediate results" step, so a
match whose edges straddle devices is assembled exactly (the same
shard-local-match-then-exchange discipline as AdPart's semi-join
evaluation and TriAD's inter-node joins).

Which side moves is decided per join step by a size-aware
**communication planner** (the paper's §7.3 communication-cost
objective, the ROADMAP's size-aware broadcast-join item):

* **skip** -- when the step's property is *shard-complete* (every
  device already holds every resident edge of that property, detected
  from per-property residency metadata at ``SiteStore`` build time),
  nothing is shipped: each device extends its local bindings against
  its local -- complete -- edge table.
* **ship bindings** vs. **ship edges** -- otherwise the global binding
  count (one scalar ``psum``, already tracked for overflow accounting)
  is compared in-trace against the property's total resident edge rows
  (static metadata): the smaller side is gathered.  Shipping edges
  keeps every binding where it is and expands it against the gathered
  global edge table -- exactly equivalent, cheaper when bindings
  outgrow the property.  A gathered table is cached across the steps
  of one query that share a property (reuse is free), and a query
  whose step-0 property is shard-complete stripes its seeds across
  the mesh (seed decimation), so storage replicated by the
  allocation-aware replication pass serves as balanced partitioned
  work.

All decisions are trace-time static in *shape* (a ``lax.cond`` between
equal-shape branches), so the shape-keyed jit cache and the capacity
retry tiers keep working; the per-step decisions and shipped-row counts
are returned to the host for the ``comm_bytes`` ledger and the
``gather_steps`` / ``edge_shipped_steps`` / ``skipped_gathers``
counters.  ``SpmdEngine(comm_plan=False)`` (or
``Session(spmd_comm_plan=False)``) restores the naive
gather-bindings-every-step behaviour.

On top of the planner sits per-query **replica-/load-aware routing**
(``repro.core.routing``, ``docs/routing.md``): a ``RoutePlan`` computed
from the same residency metadata masks devices that hold none of the
query's non-replicated properties out of the whole query -- step 0
zeroes them via the rank vector, route-complete steps skip their
collective, and every ledgered byte count uses ``route_width - 1``
peers instead of ``m - 1``.  Fully-replicated shapes are
rendezvous-pinned to one device, route-complete seed steps stripe
seeds across exactly the replica holders, and narrow decimated routes
start the capacity ladder ``ceil(log2(m/width))`` tiers lower.
``SpmdEngine(routing=False)`` (or ``Session(spmd_routing=False)``)
restores whole-mesh execution bit-identically.

Shapes are static everywhere (capacity + valid-count), so the whole
query plan jits and the production-mesh dry-run can lower/compile it.
Overflow of a binding table is *counted in-trace* and returned per
device; ``SpmdEngine`` transparently re-executes with doubled capacity
(geometric, compile cached per capacity tier) until the answer is exact
or ``max_capacity`` is hit, which raises instead of truncating.

The expansion probes (join multiplicities per binding row) and the
cycle-close pair probe run through the blocked Pallas kernels in
``repro.kernels`` on TPU (``TPU_KERNELS``) and through their
``kernels.ref`` jnp oracles elsewhere (``REPRO_SPMD_PALLAS=1/0`` forces
the two kernels on or off).  A gathered step dedups its rows with a
lexicographic sort (``_dedup_padded``) and expands them with
``_expand_fixed``.  Which path each join step traced is counted per
compiled matcher (``JOIN_PATHS``, ``stats().extra["traced_*"]``).
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..constants import INT32_SENTINEL, MAX_VERTEX_ID
from ..kernels import ref as kref
from ..obs.trace import NULL_SPAN
from .engine import EngineBase
from .executor import CostModel, ExecStats, QueryResult
from .fragmentation import Fragmentation
from .graph import RDFGraph
from .query import PROP_VAR, QueryGraph, _connected_edge_order
from .routing import RoutePlan, plan_route


# ----------------------------------------------------------------------
# Site-sharded storage
# ----------------------------------------------------------------------

@dataclasses.dataclass
class SiteStore:
    """Per-site edge storage, padded to uniform shape for SPMD.

    Each site's edges are sorted by (p, s, o) and packed into
    per-property tables (below); ``e_max`` is the most edges any site
    holds, rounded up to ``pad_multiple``.

    ``build`` also derives the static per-property residency metadata
    the communication planner reads (host-side numpy, trace-time
    constants):

    * ``prop_dev_rows[j, p]``      -- edge rows of property ``p`` stored
      on device ``j`` (what shipping that device's ``p``-table costs);
    * ``prop_dev_distinct[j, p]``  -- distinct edge ids behind those
      rows;
    * ``prop_union_rows[p]``       -- distinct edge ids of ``p``
      resident anywhere;
    * ``prop_dev_owned[j, p]``     -- rows of ``p`` device ``j`` *owns*
      for edge shipping: each resident edge id is owned by exactly its
      lowest-indexed holder (first row of the id on that device), so
      the union of the owned sets is each resident edge exactly once.
      ``owned`` carries the per-row flags in the same (p, s, o)-sorted
      order as the main/CSR tables -- the edge-shipping step compacts
      and gathers only these rows, never the padded window and never a
      replicated duplicate.

    A property is *shard-complete* when every device's distinct set
    equals the union -- e.g. a vertical fragment replicated by
    overlapping FAPs, WARP's replicated pattern matches, or several
    logical sites folded onto one device.  For such a step no
    inter-device shipping is needed at all.

    The device holds **CSR per-property edge tables** (the join
    hot-path layout): because rows are sorted by (p, s, o), each
    property's edges form one contiguous, subject-sorted run;
    ``csr_sub_s``/``csr_sub_o`` hold those runs (key = subject,
    payload = object), ``csr_obj_o``/``csr_obj_s`` hold the
    object-sorted counterpart from a second (p, o, s) sort, and
    ``csr_offs`` (m, P+1) holds the per-device run offsets.  The match
    loop slices one property's run per join step (a
    ``lax.dynamic_slice`` window sized by static residency metadata),
    so no traced step sorts or scans a whole site.  Arrays are padded
    ``csr_pad`` rows past the last run so a window never clamps into a
    neighbouring property.

    With ``sharding`` (the engine passes ``NamedSharding(mesh, P(axis,
    None))``) every per-device array is placed row-sharded over the mesh
    axis as it is built, so each device holds only its own sites' rows
    and the matcher's ``shard_map`` consumes them without a reshard;
    without it they land on the default device.
    """
    num_sites: int
    e_max: int
    prop_dev_rows: np.ndarray       # (m, P) int64
    prop_dev_distinct: np.ndarray   # (m, P) int64
    prop_union_rows: np.ndarray     # (P,) int64
    csr_sub_s: jax.Array            # (m, e_max + csr_pad) int32
    csr_sub_o: jax.Array
    csr_obj_o: jax.Array
    csr_obj_s: jax.Array
    csr_offs: jax.Array             # (m, P + 1) int32
    csr_pad: int
    prop_dev_owned: np.ndarray      # (m, P) int64
    owned: jax.Array                # (m, e_max + csr_pad) bool

    @staticmethod
    def build(graph: RDFGraph, site_edge_ids: Sequence[np.ndarray],
              pad_multiple: int = 512,
              sharding: Optional[jax.sharding.Sharding] = None
              ) -> "SiteStore":
        m = len(site_edge_ids)
        e_max = max((len(e) for e in site_edge_ids), default=1)
        e_max = int(np.ceil(max(e_max, 1) / pad_multiple) * pad_multiple)
        n_props = graph.num_properties
        dev_rows = np.zeros((m, n_props), np.int64)
        dev_distinct = np.zeros((m, n_props), np.int64)
        dev_owned = np.zeros((m, n_props), np.int64)
        # edge ownership for shipping: ascending device order, each
        # resident edge id claimed by its first holder (first row of
        # the id within that device), so every resident edge has
        # exactly one owning row across the mesh
        owner = np.full(graph.num_edges, -1, np.int64)
        per_site = []
        for j, eids in enumerate(site_edge_ids):
            eids = np.asarray(eids, np.int64)
            s, p, o = graph.s[eids], graph.p[eids], graph.o[eids]
            order = np.lexsort((o, s, p))
            n = len(eids)
            dev_rows[j] = np.bincount(p, minlength=n_props)[:n_props]
            dev_distinct[j] = np.bincount(
                graph.p[np.unique(eids)], minlength=n_props)[:n_props]
            first_here = np.zeros(n, bool)
            first_here[np.unique(eids, return_index=True)[1]] = True
            claim = first_here & (owner[eids] < 0)
            owner[eids[claim]] = j
            dev_owned[j] = np.bincount(
                p[claim], minlength=n_props)[:n_props]
            per_site.append((s, p, o, order, claim[order]))
        resident = np.unique(np.concatenate(
            [np.zeros(0, np.int64)]
            + [np.asarray(e, np.int64) for e in site_edge_ids]))
        union = np.bincount(graph.p[resident], minlength=n_props)[:n_props]
        # CSR per-property packing: the (p, s, o) sort above already
        # groups each property into one subject-sorted run; a second
        # (p, o, s) sort yields the object-sorted runs.  Pad past the
        # last run by the largest window any property can ask for
        # (max per-device run, rounded like prop_window) so a
        # dynamic_slice window starting at the final offset stays in
        # bounds without clamping.
        pad = int(np.ceil(max(int(dev_rows.max(initial=1)), 1) / 8) * 8)
        width = e_max + pad
        sub_s = np.full((m, width), INT32_SENTINEL, np.int32)
        sub_o = np.full((m, width), -1, np.int32)
        obj_o = np.full((m, width), INT32_SENTINEL, np.int32)
        obj_s = np.full((m, width), -1, np.int32)
        offs = np.zeros((m, n_props + 1), np.int32)
        owned = np.zeros((m, width), bool)
        for j, (s, p, o, order, claim_sorted) in enumerate(per_site):
            n = len(order)
            sub_s[j, :n], sub_o[j, :n] = s[order], o[order]
            owned[j, :n] = claim_sorted
            order_o = np.lexsort((s, o, p))
            obj_o[j, :n], obj_s[j, :n] = o[order_o], s[order_o]
            offs[j, 1:] = np.cumsum(dev_rows[j])
        def put(a: np.ndarray) -> jax.Array:
            return (jnp.asarray(a) if sharding is None
                    else jax.device_put(a, sharding))

        return SiteStore(m, e_max, dev_rows, dev_distinct, union,
                         put(sub_s), put(sub_o), put(obj_o), put(obj_s),
                         put(offs), pad, dev_owned, put(owned))

    def prop_shard_complete(self, prop: int) -> bool:
        """Every device holds every resident edge of ``prop`` (so a join
        step on it needs no inter-device shipping).  Properties outside
        the metadata range (or resident nowhere) are trivially
        complete."""
        if not (0 <= prop < self.prop_union_rows.shape[0]):
            return True
        return bool(np.all(self.prop_dev_distinct[:, prop]
                           == self.prop_union_rows[prop]))

    def prop_rows(self, prop: int) -> Tuple[int, int]:
        """(total stored rows across devices, max rows on any device)
        for ``prop`` -- the static size of the edge-shipping side."""
        if not 0 <= prop < self.prop_dev_rows.shape[1]:
            return 0, 0
        col = self.prop_dev_rows[:, prop]
        return int(col.sum()), int(col.max(initial=0))

    def prop_window(self, prop: int) -> int:
        """Static CSR window rows for ``prop``: the max per-device run,
        rounded up to 8 (min 8).  The ONE sizing formula shared by the
        per-step table slices and the step-0 seed window, so a local
        window always covers the property's full run."""
        _total, per_dev = self.prop_rows(prop)
        return int(np.ceil(max(per_dev, 1) / 8) * 8)

    def prop_resident_rows(self, prop: int) -> int:
        """Distinct edges of ``prop`` resident anywhere -- the rows an
        edge-shipping step puts on the wire (each resident edge ships
        from its one owning device)."""
        if not 0 <= prop < self.prop_union_rows.shape[0]:
            return 0
        return int(self.prop_union_rows[prop])

    def prop_ship_window(self, prop: int) -> int:
        """Static per-device buffer rows for *shipping* ``prop``: the
        max owned rows on any device, rounded up to 8 (min 8).  Sizes
        the planner's edge-gather buffers (``plan_step_comm``) --
        smaller than ``prop_window`` whenever replication stores the
        same edge on several devices, since only the owner ships it."""
        if not 0 <= prop < self.prop_dev_owned.shape[1]:
            return 8
        per_dev = int(self.prop_dev_owned[:, prop].max(initial=0))
        return int(np.ceil(max(per_dev, 1) / 8) * 8)

    def csr_arrays(self) -> Tuple[jax.Array, ...]:
        """The packed per-property tables as one tuple of device
        arrays (subject-sorted keys/payload, object-sorted
        keys/payload, offsets, owned-row flags): what a matcher of
        ``make_spmd_matcher`` takes."""
        return (self.csr_sub_s, self.csr_sub_o, self.csr_obj_o,
                self.csr_obj_s, self.csr_offs, self.owned)

    @staticmethod
    def from_fragmentation(graph: RDFGraph, frag: Fragmentation,
                           site_of: np.ndarray, num_sites: int,
                           include_cold: bool = True) -> "SiteStore":
        per_site: List[np.ndarray] = []
        for j in range(num_sites):
            ids = [f.edge_ids for fi, f in enumerate(frag.fragments)
                   if int(site_of[fi]) == j]
            if include_cold:
                ids += [f.edge_ids for k, f in enumerate(frag.cold_fragments)
                        if k % num_sites == j]
            per_site.append(np.unique(np.concatenate(ids))
                            if ids else np.zeros(0, np.int64))
        return SiteStore.build(graph, per_site)


# ----------------------------------------------------------------------
# Per-join-step communication planning
# ----------------------------------------------------------------------

# decision codes, as reported in the matcher's per-step decision vector
COMM_GATHER = 0       # shipped the binding tables (all_gather + dedup)
COMM_EDGE = 1         # shipped the step property's edge rows instead
COMM_SKIP = 2         # shipped nothing (shard-complete property / 1 device)
COMM_EDGE_CACHED = 3  # reused an earlier step's gathered edge table

#: decision code -> the name used in trace records and docs
COMM_DECISION_NAMES = {COMM_GATHER: "gather", COMM_EDGE: "edge_ship",
                       COMM_SKIP: "skip", COMM_EDGE_CACHED: "edge_cached"}


def bind_row_bytes(num_cols: int) -> int:
    """Wire bytes of one binding-table row: ``num_cols`` int32 columns
    plus the validity byte.  The ONE formula shared by the in-trace
    ship-smaller-side predicate and the host-side ``comm_bytes``
    ledger -- they must never diverge."""
    return num_cols * 4 + 1


EDGE_ROW_BYTES = 8   # one shipped edge row: two int32 columns (key, pay)


@dataclasses.dataclass(frozen=True)
class StepComm:
    """Static communication spec for one join step (trace-time
    constant; derived from ``SiteStore`` residency metadata).

    mode:
      ``"gather"``  -- always ship bindings (planner off);
      ``"skip"``    -- property is shard-complete (or complete on every
      route member, flagged ``route_complete``), ship nothing;
      ``"dynamic"`` -- compare the psum'd global binding count against
      ``edge_rows`` in-trace and ship the smaller side.
    """
    mode: str
    prop: int
    gather_cap: int     # per-device edge-gather buffer rows ("dynamic")
    edge_rows: int      # distinct resident rows of ``prop`` (wire rows)
    route_complete: bool = False   # skipped via route-local completeness

    @property
    def edge_bytes(self) -> int:
        """Wire bytes of shipping this property's resident edge rows
        (per receiving peer): compacted owned rows only, so the count
        is the distinct resident edges -- never the padded window, and
        never a replicated duplicate."""
        return self.edge_rows * EDGE_ROW_BYTES


def plan_step_comm(store: SiteStore, pattern: QueryGraph,
                   enabled: bool = True,
                   route=None) -> Tuple[StepComm, ...]:
    """One ``StepComm`` per join step (steps >= 1 of the connected edge
    order) for matching ``pattern`` over ``store``.  With
    ``enabled=False`` every step ships bindings -- the naive broadcast
    join.  ``route`` (a ``repro.core.routing.RoutePlan``) additionally
    skips steps whose property is complete on every route member: the
    devices outside the route never hold binding rows, so
    completeness on the members is all a skip needs."""
    from .routing import route_prop_complete
    order = _connected_edge_order(pattern)
    specs: List[StepComm] = []
    for ei in order[1:]:
        prop = pattern.edges[ei].prop
        union = store.prop_resident_rows(prop)
        if not enabled:
            specs.append(StepComm("gather", prop, 0, union))
        elif store.prop_shard_complete(prop):
            specs.append(StepComm("skip", prop, 0, union))
        elif route is not None and route_prop_complete(
                store, prop, route.members):
            specs.append(StepComm("skip", prop, 0, union,
                                  route_complete=True))
        else:
            specs.append(StepComm("dynamic", prop,
                                  store.prop_ship_window(prop), union))
    return tuple(specs)


def plan_seed_decimation(store: SiteStore, pattern: QueryGraph) -> bool:
    """Should the matcher decimate the seed rows of step 0 across
    devices?  True when step 0's property is shard-complete: every
    device holds the identical (identically sorted) seed table, so each
    keeping every ``m``-th row partitions the seeds exactly -- replicated
    storage becomes balanced partitioned work instead of ``m`` devices
    duplicating every seed (which would inflate every downstream
    binding count and the final gather ``m``-fold).

    Striping by rank is only exact when every device's stored rows of
    the property are duplicate-free (rows == distinct ids per device;
    ``SpmdEngine`` guarantees it by unique-ing every folded site list,
    but a directly-built ``SiteStore`` may not), so duplicated rows
    disable decimation rather than risk dropping a seed."""
    order = _connected_edge_order(pattern)
    if not order:
        return False
    prop = pattern.edges[order[0]].prop
    if not store.prop_shard_complete(prop):
        return False
    if 0 <= prop < store.prop_dev_rows.shape[1] \
            and not np.array_equal(store.prop_dev_rows[:, prop],
                                   store.prop_dev_distinct[:, prop]):
        return False
    return True


# ----------------------------------------------------------------------
# Local (per-site) fixed-capacity pattern matching
# ----------------------------------------------------------------------

#: The Pallas kernels the match loop calls: the blocked expansion-size
#: and cycle-close probes.  Both compile for TPU v5e
#: (``tests/test_tpu_compile.py``).
TPU_KERNELS = ("join_count", "pair_semijoin")


def _use_pallas_probes(kernel: str) -> bool:
    """Is the Pallas ``kernel`` (one of ``TPU_KERNELS``) on?  On TPU,
    yes; elsewhere its jnp oracle runs.  The env knob
    ``REPRO_SPMD_PALLAS`` forces both on or off (tests exercise the
    kernel path in interpret mode on CPU through it)."""
    env = os.environ.get("REPRO_SPMD_PALLAS")
    if env is not None:
        return env not in ("0", "false", "")
    from ..kernels.ops import _on_tpu
    return _on_tpu() and kernel in TPU_KERNELS


#: Trace-time join-path counters of one compiled matcher, in the order
#: ``stats().extra`` reports them (as ``traced_<name>``):
#:
#: * ``join_count_kernel`` / ``join_count_jnp`` -- expansion-size
#:   probes traced through the blocked kernel / the searchsorted oracle;
#: * ``pair_semijoin_kernel`` / ``pair_semijoin_jnp`` -- cycle-close
#:   probes, likewise;
#: * ``lexsort_dedup`` -- gathered-row dedups (``_dedup_padded``);
#: * ``expand_direct`` -- ``_expand_fixed`` calls, each mapping rows and
#:   output slots by scatter and running max (no binary search).
#:
#: Both branches of a planner ``lax.cond`` are traced, so a dynamic step
#: counts the paths of both.
JOIN_PATHS = ("join_count_kernel", "join_count_jnp",
              "pair_semijoin_kernel", "pair_semijoin_jnp",
              "lexsort_dedup", "expand_direct")


def _note(paths: Optional[Dict[str, int]], name: str) -> None:
    """Count one traced ``name`` path (see ``JOIN_PATHS``)."""
    if paths is not None:
        paths[name] = paths.get(name, 0) + 1


def _probe_counts(probe: jax.Array, keys_sorted: jax.Array,
                  paths: Optional[Dict[str, int]] = None) -> jax.Array:
    """Join multiplicity of each probe key in a sorted key column -- the
    expansion-size probe of the match loop.  Blocked Pallas ``join_count``
    kernel (jit-safe static block plan) on TPU, ``kernels.ref`` oracle on
    CPU.  Sentinel table rows (INT32_MAX) never equal a real vertex id."""
    if _use_pallas_probes("join_count"):
        from ..kernels.ops import join_count
        _note(paths, "join_count_kernel")
        return join_count(probe, keys_sorted, jit_safe=True)
    _note(paths, "join_count_jnp")
    return kref.join_count_ref(probe, keys_sorted)


def _probe_pair_member(q_s: jax.Array, q_o: jax.Array,
                       t_s: jax.Array, t_o: jax.Array,
                       paths: Optional[Dict[str, int]] = None
                       ) -> jax.Array:
    """(q_s[i], q_o[i]) present among the table's (s, o) pairs?  The
    cycle-close probe: exact int32 pair membership (no 42-bit key
    composition, which would need the x64 mode jax disables by default).
    Blocked Pallas ``pair_semijoin`` on TPU, merge-rank oracle on CPU."""
    if _use_pallas_probes("pair_semijoin"):
        from ..kernels.ops import pair_semijoin
        _note(paths, "pair_semijoin_kernel")
        return pair_semijoin(q_s, q_o, t_s, t_o, jit_safe=True)
    _note(paths, "pair_semijoin_jnp")
    return kref.pair_semijoin_ref(q_s, q_o, t_s, t_o)


def _slot_rows(start: jax.Array, cnt: jax.Array, capacity: int
               ) -> jax.Array:
    """Output slot -> source row of an expansion step: for each slot ``t``
    below the total, the row whose run of ``cnt`` slots from offset
    ``start`` holds ``t``, which is ``searchsorted(start, t,
    side="right") - 1``.  Built in one pass, not a binary search: each
    row with slots in range writes its number at its offset, and a
    running maximum carries it over the row's slots.  ``start`` (the
    exclusive cumsum of ``cnt``) rises strictly over those rows until
    the int32 cumsum wraps, and the first wrap reads negative; rows from
    it on write nothing (only the caller's ``wrap_risk`` allows a wrap,
    and its overflow verdict discards every slot).  The other rows
    write past the end, each at its own index, so every index is
    unique."""
    C = start.shape[0]
    rows = jnp.arange(C, dtype=jnp.int32)
    live = (cnt > 0) & (jax.lax.cummin(start) >= 0) & (start < capacity)
    idx = jnp.where(live, start, capacity + rows)
    heads = jnp.full((capacity,), -1, jnp.int32).at[idx].set(
        rows, mode="drop", unique_indices=True)
    return jnp.clip(jax.lax.cummax(heads), 0, C - 1)


def _run_starts(keys_sorted: jax.Array, probe: jax.Array) -> jax.Array:
    """First position of each probe key in the ascending ``keys_sorted``,
    ``searchsorted(keys_sorted, probe, side="left")`` for every probe
    the table holds.  Each run head (a key unlike the one before it)
    writes its position into a dense table over the vertex ids
    (``MAX_VERTEX_ID + 1`` entries), and one gather reads it: no binary
    search.  Other positions and the sentinel tail write past the end,
    each at its own index, so every index is unique.  A probe the table
    does not hold reads an arbitrary entry (its join count is 0, so no
    slot reads it)."""
    W = keys_sorted.shape[0]
    pos = jnp.arange(W, dtype=jnp.int32)
    head = jnp.concatenate([jnp.ones((min(W, 1),), bool),
                            keys_sorted[1:] != keys_sorted[:-1]])
    head &= (keys_sorted >= 0) & (keys_sorted <= MAX_VERTEX_ID)
    idx = jnp.where(head, keys_sorted, MAX_VERTEX_ID + 1 + pos)
    first = jnp.zeros((MAX_VERTEX_ID + 1,), jnp.int32).at[idx].set(
        pos, mode="drop", unique_indices=True)
    return first[jnp.clip(probe, 0, MAX_VERTEX_ID)]


def _expand_fixed(bind: jax.Array, valid: jax.Array, col_vals: jax.Array,
                  keys_sorted: jax.Array, payload: jax.Array, capacity: int,
                  paths: Optional[Dict[str, int]] = None
                  ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Join-expand a binding table against a sorted (keys -> payload)
    edge table with a fixed output capacity.

    bind: (C, V) int32 (C need not equal capacity -- after a broadcast
    gather it is num_devices * capacity); valid: (C,) bool; col_vals:
    (C,) probe keys, vertex ids.  Returns (new_bind (capacity, V),
    new_payload_col, new_valid, overflow) where overflow is the number
    of result rows that did NOT fit (int32 scalar, 0 when exact).  Slot
    for slot the result of ``kernels.ref.expand_fixed_ref``; its two
    binary searches are the index maps ``_slot_rows`` and
    ``_run_starts`` here."""
    C, V = bind.shape
    probe = jnp.where(valid, col_vals, INT32_SENTINEL)
    cnt = jnp.where(valid, _probe_counts(probe, keys_sorted, paths), 0)
    cnt = cnt.astype(jnp.int32)
    # int32 cumsum can wrap past 2^31 total expansion rows and defeat
    # the overflow check (x64 is off, so no int64).  sum(cnt) cannot
    # wrap iff every cnt <= (2^31-1)/C; a larger cnt is treated as a
    # (conservative) overflow so the retry ladder -- not silent
    # truncation -- handles it.
    wrap_risk = (jnp.max(cnt, initial=0) > (2 ** 31 - 1) // max(C, 1)
                 if C else jnp.bool_(False))
    start = jnp.cumsum(cnt) - cnt                     # output offsets
    total = start[-1] + cnt[-1] if C else jnp.int32(0)
    _note(paths, "expand_direct")
    r = _slot_rows(start, cnt, capacity)
    # slot t < total lies in row r's run (rows with cnt 0 own no slot),
    # at edge-table position lo[r] + (t - start[r])
    t = jnp.arange(capacity, dtype=jnp.int32)
    ok = t < total
    shift = _run_starts(keys_sorted, probe) - start
    src = jnp.clip(t + shift[r], 0, keys_sorted.shape[0] - 1)
    new_col = jnp.where(ok, payload[src], -1)
    new_bind = jnp.where(ok[:, None], bind[r], -1)
    over = jnp.maximum(total - capacity, 0).astype(jnp.int32)
    over = jnp.where(wrap_risk, jnp.int32(capacity + 1), over)
    return new_bind, new_col, ok, over


def _dedup_padded(bind: jax.Array, valid: jax.Array,
                  paths: Optional[Dict[str, int]] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Invalidate duplicate rows of a padded binding table (exact -- no
    lossy hashing; row order never matters downstream).  After an
    all_gather the same partial match can arrive from several devices
    (replicated fragments); deduping before expansion keeps capacity
    pressure at the number of *distinct* partial matches.

    A column-wise lexicographic sort (``lexsort_passes``) brings equal
    rows together; rows come back sorted, which no caller observes.
    ``kernels.ref.dedup_rows_ref`` is its oracle."""
    C, V = bind.shape
    if V == 0:
        keep = jnp.zeros_like(valid).at[0].set(valid.any())
        return bind, keep
    from ..kernels.ops import lexsort_passes
    _note(paths, "lexsort_dedup")
    keys = tuple(bind[:, v] for v in range(V - 1, -1, -1)) \
        + ((~valid).astype(jnp.int32),)
    order = lexsort_passes(keys)               # invalid rows sort last
    bs, vs = bind[order], valid[order]
    dup = jnp.zeros((C,), bool).at[1:].set(
        jnp.all(bs[1:] == bs[:-1], axis=1) & vs[1:] & vs[:-1])
    keep = vs & ~dup
    return jnp.where(keep[:, None], bs, -1), keep


def _compress_rows(bind: jax.Array, keep: jax.Array, capacity: int
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Pack the rows selected by ``keep`` into a fresh capacity-row
    table.  Returns (bind, valid, overflow-row-count)."""
    from ..kernels.ops import compact_rows
    (out,), valid = compact_rows(keep, (bind,), capacity, fill=-1)
    over = jnp.maximum(keep.sum() - capacity, 0).astype(jnp.int32)
    return out, valid, over


def _var_col_trace(pattern: QueryGraph
                   ) -> Tuple[List[int], List[int], int]:
    """Host-side replay of ``_match_shard``'s column bookkeeping, without
    tracing.  Returns (final binding-column order, #columns entering each
    join step >= 1, #join steps that close a cycle) -- the second sizes
    the per-step broadcast-join gathers for the comm ledger.  A step
    closes a cycle when both its endpoints are variables already bound,
    so it probes pair membership (``pair_semijoin``) instead of
    expanding; a bound pair with a constant end probes alike but closes
    no cycle."""
    order = _connected_edge_order(pattern)
    edges = pattern.edges
    var_cols: List[int] = []
    step_in_cols: List[int] = []
    cycles = 0
    for step, ei in enumerate(order):
        e = edges[ei]
        if step == 0:
            if e.src < 0:
                var_cols.append(e.src)
            if e.dst < 0 and e.dst != e.src:
                var_cols.append(e.dst)
            continue
        step_in_cols.append(len(var_cols))
        s_known = e.src >= 0 or e.src in var_cols
        d_known = e.dst >= 0 or e.dst in var_cols
        if s_known and d_known:
            cycles += e.src < 0 and e.dst < 0
            continue
        if s_known:
            if e.dst < 0:
                var_cols.append(e.dst)
        else:
            if e.src < 0:
                var_cols.append(e.src)
    return var_cols, step_in_cols, cycles


def cycle_close_steps(pattern: QueryGraph) -> int:
    """How many join steps of ``_match_shard`` close a cycle (see
    ``_var_col_trace``)."""
    return _var_col_trace(pattern)[2]


def pattern_var_order(pattern: QueryGraph) -> List[int]:
    """Binding-table column order produced by ``_match_shard`` for this
    pattern -- the same bookkeeping, host-side, without tracing."""
    return _var_col_trace(pattern)[0]


def _match_shard(csr: Tuple[jax.Array, ...], prop_windows: Dict[int, int],
                 pattern: QueryGraph, capacity: int,
                 axis: Optional[str] = None,
                 comm: Optional[Sequence[StepComm]] = None,
                 axis_size: int = 1, seed_decimate: bool = False,
                 route_ranks: Optional[Sequence[int]] = None,
                 route_width: int = 0,
                 paths: Optional[Dict[str, int]] = None
                 ) -> Tuple[jax.Array, jax.Array, List[int], jax.Array,
                            jax.Array, jax.Array]:
    """Match ``pattern`` over one shard's packed edge tables, padded to
    ``capacity`` rows.  ``csr`` is this shard's slice of the
    ``SiteStore.csr_arrays()`` tuple, and ``prop_windows`` the static
    window rows of every property of the pattern
    (``SiteStore.prop_window``): every per-step edge table is a
    ``lax.dynamic_slice`` of a pre-sorted property run.  Returns
    (bindings (capacity, V), valid, var_order, overflow-row-count,
    per-step decisions, per-step shipped-row counts).

    With ``axis`` set (inside shard_map) every join step is a broadcast
    join whose shipping is chosen by ``comm`` (one ``StepComm`` per join
    step; ``None`` means ship bindings every step):

    * ship **bindings**: all_gather + exact dedup of the binding tables,
      then expand against THIS shard's edges -- a partial match
      discovered on any device picks up its next edge wherever that
      edge lives;
    * ship **edges**: each device's rows of the step's property are
      compacted into a static buffer and all_gather-ed instead, and the
      *local* bindings expand against the global edge table -- exactly
      equivalent, chosen in-trace (``lax.cond``) when the psum'd global
      binding count outweighs the property's resident rows.  The
      gathered global table is *cached across steps of this trace*:
      a later join step on the same property reuses it instead of
      re-gathering (decision code ``COMM_EDGE_CACHED``, zero wire
      bytes);
    * **skip**: the property is shard-complete, so the local edge table
      already is the global one -- no collective at all.

    In every mode the union over devices of the step's outputs is
    exactly the set of partial matches of the covered pattern prefix
    against the whole (distributed) graph.  With ``axis=None`` the loop
    is purely shard-local (single-device case; identical math, gathers
    skipped, decisions all ``COMM_SKIP``).  ``axis_size`` (static mesh
    extent) sizes the cache stand-in buffers.  ``seed_decimate`` (see
    ``plan_seed_decimation``) is only valid when step 0's property is
    shard-complete on every device -- or, with ``route_ranks`` set, on
    every route member.

    ``route_ranks`` (per-device stripe rank, -1 for devices outside
    the query's route -- ``RoutePlan.seed_ranks``) masks non-member
    devices out of step 0 entirely: they hold zero valid rows for the
    whole query, so every later collective only carries member data.
    With ``seed_decimate`` the seeds stripe over ``route_width``
    members instead of the whole mesh.

    jit-friendly: static pattern, static capacity, static per-step
    specs; overflow (result rows beyond capacity at any step) is
    counted, not silently dropped.  Step ``j`` (0 is the seed) traces
    under ``jax.named_scope(f"step{j}")``, so its operations carry the
    step in their HLO metadata and in a profiler trace.

    ``paths`` (a dict) receives the trace-time join-path counts
    (``JOIN_PATHS``).
    """
    from ..kernels.ops import compact_rows
    order = _connected_edge_order(pattern)
    edges = pattern.edges
    var_cols: List[int] = []
    imax = INT32_SENTINEL

    def col_idx(v: int) -> int:
        return var_cols.index(v)

    n_props = int(csr[4].shape[-1]) - 1

    def csr_window(prop: int, subject_side: bool,
                   size: Optional[int] = None, pay_fill: int = -1
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """(keys, payload, live-row count) for one property's packed
        run: a static-size dynamic_slice window over the pre-sorted
        CSR arrays, tail masked to the sentinels.  Keys ascend (the
        run is (s, o)- or (o, s)-sorted), so searchsorted probes and
        the blocked kernels work on it directly.  ``size`` defaults to
        the property's static window (``SiteStore.prop_window``, the
        same formula that sized the planner's gather buffers)."""
        sub_s_d, sub_o_d, obj_o_d, obj_s_d, offs_d = csr[:5]
        if size is None:
            size = prop_windows[prop]
        if not 0 <= prop < n_props:   # never stored: empty static table
            return (jnp.full((size,), imax, jnp.int32),
                    jnp.full((size,), pay_fill, jnp.int32), jnp.int32(0))
        arrk, arrp = ((sub_s_d, sub_o_d) if subject_side
                      else (obj_o_d, obj_s_d))
        start = offs_d[prop]
        n = offs_d[prop + 1] - start
        wk = jax.lax.dynamic_slice(arrk, (start,), (size,))
        wp = jax.lax.dynamic_slice(arrp, (start,), (size,))
        io = jnp.arange(size, dtype=jnp.int32)
        return (jnp.where(io < n, wk, imax),
                jnp.where(io < n, wp, pay_fill), n)

    def owned_run_window(prop: int, size: int,
                         n_live: jax.Array) -> jax.Array:
        """Owned-row flags aligned with ``csr_window(prop, True,
        size)``: the same dynamic_slice window over the per-device
        owned flags, tail masked (a window can spill into the next
        property's run, whose owned rows must not leak in)."""
        if not 0 <= prop < n_props:
            return jnp.zeros((size,), bool)
        start = csr[4][prop]
        w = jax.lax.dynamic_slice(csr[5], (start,), (size,))
        return w & (jnp.arange(size, dtype=jnp.int32) < n_live)

    bind = jnp.full((capacity, 0), -1, jnp.int32)
    valid = jnp.zeros((capacity,), bool)
    ovf = jnp.int32(0)
    decs: List[jax.Array] = []
    rows: List[jax.Array] = []
    # cross-step edge-gather cache: prop -> (keys(s), payload(o), have).
    # ``have`` derives only from psum'd predicates, so it is uniform
    # across devices and safe as a lax.cond predicate.
    edge_cache: Dict[int, Tuple[jax.Array, jax.Array, jax.Array]] = {}

    for step, ei in enumerate(order):
        with jax.named_scope(f"step{step}"):
            e = edges[ei]
            s_known = e.src >= 0 or e.src in var_cols
            d_known = e.dst >= 0 or e.dst in var_cols

            if step == 0:
                # initialize from the property's packed run: a static
                # window, identically (s, o)-ordered on every device, so
                # seed decimation stripes identically
                seed_s, seed_o, n_live = csr_window(e.prop, True)
                sel = jnp.arange(seed_s.shape[0], dtype=jnp.int32) < n_live
                if e.src >= 0:
                    sel &= seed_s == e.src
                if e.dst >= 0:
                    sel &= seed_o == e.dst
                if e.src < 0 and e.src == e.dst:
                    sel &= seed_s == seed_o
                if route_ranks is not None and axis is not None:
                    # routed execution: devices outside the route never
                    # seed (rank -1), so they hold zero valid rows for the
                    # whole query; with decimation the members additionally
                    # stripe the (route-complete, identically-ordered) seed
                    # list among themselves in rendezvous-rank order
                    my_rank = jnp.asarray(
                        list(route_ranks),
                        jnp.int32)[jax.lax.axis_index(axis)]
                    if seed_decimate:
                        rank = jnp.cumsum(sel) - 1
                        sel &= (rank % max(route_width, 1)) == my_rank
                    else:
                        sel &= my_rank >= 0
                elif seed_decimate and axis is not None:
                    # step 0's property is shard-complete: every device sees
                    # the identical, identically-ordered seed list, so each
                    # keeping every m-th row partitions the seeds exactly
                    # (balanced work, no cross-device duplicates, no m-fold
                    # blowup of downstream binding counts)
                    rank = jnp.cumsum(sel) - 1
                    sel &= (rank % axis_size) == jax.lax.axis_index(axis)
                (s_col, o_col), valid = compact_rows(sel, (seed_s, seed_o),
                                                     capacity, fill=-1)
                ovf = jnp.maximum(
                    ovf, sel.sum().astype(jnp.int32) - capacity)
                cols = []
                if e.src < 0:
                    var_cols.append(e.src)
                    cols.append(s_col)
                if e.dst < 0 and e.dst != e.src:
                    var_cols.append(e.dst)
                    cols.append(o_col)
                bind = (jnp.stack(cols, axis=1) if cols
                        else jnp.zeros((capacity, 0), jnp.int32)
                        ).astype(jnp.int32)
                continue

            sc = comm[step - 1] if comm is not None else None
            mode = ("skip" if axis is None
                    else sc.mode if sc is not None else "gather")
            n_in = len(var_cols)          # binding columns entering the step

            # cross-step cache state for this step's property ("dynamic"
            # steps only: "skip" never gathers, "gather" never ships edges)
            cache = edge_cache.get(e.prop) if mode == "dynamic" else None
            have0 = cache[2] if cache is not None else jnp.bool_(False)

            # -- shared builders for this step (all shapes static) ----------
            def local_pair_tables():
                t_s, t_o, _n = csr_window(e.prop, True, pay_fill=imax)
                return t_s, t_o

            def fresh_prop_tables():
                # the edge-shipping side: this device's OWNED rows of the
                # property, compacted into the static ship buffer
                # (sc.gather_cap == SiteStore.prop_ship_window) and
                # gathered from every device.  Ownership (exactly one
                # device per resident edge, see SiteStore) makes the
                # gathered table each resident edge exactly once: valid
                # rows on the wire, not the padded window, and no
                # replicated duplicates to re-expand.  Compacting a
                # subsequence of the (s, o)-sorted run keeps it sorted;
                # the imax fill sorts last.
                fk, fp, n_run = csr_window(e.prop, True, pay_fill=imax)
                ow = owned_run_window(e.prop, fk.shape[0], n_run)
                (ls, lo_), _ = compact_rows(ow, (fk, fp), sc.gather_cap)
                return (jax.lax.all_gather(ls, axis, tiled=True),
                        jax.lax.all_gather(lo_, axis, tiled=True))

            def gathered_prop_tables():
                # reuse an earlier step's gather of the same property when
                # this trace already holds one; gather fresh otherwise
                if cache is None:
                    return fresh_prop_tables()
                return jax.lax.cond(have0, lambda: (cache[0], cache[1]),
                                    fresh_prop_tables)

            def carry_prop_tables():
                # equal-shape stand-ins the binding-gather branch returns so
                # both lax.cond branches agree; an incumbent cache entry is
                # carried through unchanged (stand-ins are only ever stored
                # with have=False and never read back as tables)
                if cache is not None:
                    return cache[0], cache[1]
                rows_ = axis_size * sc.gather_cap
                return (jnp.full((rows_,), imax, jnp.int32),
                        jnp.full((rows_,), imax, jnp.int32))

            def gathered_bindings(bt, vt):
                gb = jax.lax.all_gather(bt, axis, tiled=True)
                gv = jax.lax.all_gather(vt, axis, tiled=True)
                shipped = gv.sum().astype(jnp.int32)   # rows on the wire
                gb, gv = _dedup_padded(gb, gv, paths)
                return gb, gv, shipped

            def ship_smaller_side(via_gather, via_edges):
                # dynamic decision: psum the live global binding count and
                # run the cheaper branch.  Cost comparison in float32:
                # n_glob * row_bytes can exceed int32 on big meshes, and
                # edge_bytes can exceed int32 as a trace-time constant;
                # mantissa rounding is harmless for a heuristic.  The byte
                # formulas are the ledger's (bind_row_bytes / edge_bytes),
                # so decision and accounting cannot diverge.  Both branches
                # return the (possibly stand-in) global edge tables last, so
                # the cross-step cache survives the cond; a cached table
                # makes the edge side free (COMM_EDGE_CACHED, zero bytes),
                # which the predicate accounts for.
                n_glob = jax.lax.psum(valid.sum().astype(jnp.int32), axis)
                gather_cost = n_glob.astype(jnp.float32) \
                    * float(bind_row_bytes(n_in))
                edge_cost = jnp.where(have0, jnp.float32(0.0),
                                      jnp.float32(sc.edge_bytes))
                pred = gather_cost <= edge_cost
                out = jax.lax.cond(pred, via_gather, via_edges, bind, valid)
                *res, c_ts, c_to = out
                edge_cache[e.prop] = (c_ts, c_to, have0 | ~pred)
                dec = jnp.where(
                    pred, COMM_GATHER,
                    jnp.where(have0, COMM_EDGE_CACHED, COMM_EDGE)
                ).astype(jnp.int32)
                return tuple(res), dec, n_glob

            if s_known and d_known:
                # cycle close: membership of the bound (src, dst) pair among
                # the property's edges.  Sentinel table rows (INT32_MAX,
                # INT32_MAX) never equal a real id pair; invalid probe rows
                # are masked via ``vt``.
                def pair_keep(bt, vt, t_s, t_o):
                    nr = bt.shape[0]
                    sv = (jnp.full((nr,), e.src, jnp.int32) if e.src >= 0
                          else bt[:, col_idx(e.src)])
                    dv = (jnp.full((nr,), e.dst, jnp.int32) if e.dst >= 0
                          else bt[:, col_idx(e.dst)])
                    return vt & _probe_pair_member(sv, dv, t_s, t_o, paths)

                def pair_via_gather(bt, vt):
                    gb, gv, shipped = gathered_bindings(bt, vt)
                    t_s, t_o = local_pair_tables()
                    nb, nv, over = _compress_rows(
                        gb, pair_keep(gb, gv, t_s, t_o), capacity)
                    return nb, nv, over, shipped

                def pair_via_gather_c(bt, vt):
                    c_ts, c_to = carry_prop_tables()
                    return pair_via_gather(bt, vt) + (c_ts, c_to)

                def pair_via_edges(bt, vt):
                    t_s, t_o = gathered_prop_tables()
                    keep = pair_keep(bt, vt, t_s, t_o)
                    return (jnp.where(keep[:, None], bt, -1), keep,
                            jnp.int32(0), jnp.int32(sc.edge_rows), t_s, t_o)

                if mode == "skip":
                    t_s, t_o = local_pair_tables()
                    valid = pair_keep(bind, valid, t_s, t_o)
                    bind = jnp.where(valid[:, None], bind, -1)
                    over = jnp.int32(0)
                    dec_v, row_v = jnp.int32(COMM_SKIP), jnp.int32(0)
                elif mode == "gather":
                    bind, valid, over, shipped = pair_via_gather(bind, valid)
                    dec_v, row_v = jnp.int32(COMM_GATHER), shipped
                else:  # dynamic: ship the smaller side
                    (bind, valid, over, _), dec_v, row_v = ship_smaller_side(
                        pair_via_gather_c, pair_via_edges)
                ovf = jnp.maximum(ovf, over)
            else:
                # expansion: probe the known endpoint against the property's
                # (key -> payload) table; keys are subjects when the source
                # is bound, objects when the destination is.
                known = e.src if s_known else e.dst

                def probe_vals(bt):
                    nr = bt.shape[0]
                    return (jnp.full((nr,), known, jnp.int32) if known >= 0
                            else bt[:, col_idx(known)])

                def local_table():
                    # the property's sorted (key -> payload) table: a CSR
                    # window slice, keys already sorted
                    keys, payload, _n = csr_window(e.prop, s_known)
                    return keys, payload

                def exp_via_gather(bt, vt):
                    gb, gv, shipped = gathered_bindings(bt, vt)
                    keys, payload = local_table()
                    nb, nc, nv, over = _expand_fixed(
                        gb, gv, probe_vals(gb), keys, payload, capacity,
                        paths)
                    return nb, nc, nv, over, shipped

                def exp_via_gather_c(bt, vt):
                    c_ts, c_to = carry_prop_tables()
                    return exp_via_gather(bt, vt) + (c_ts, c_to)

                def exp_via_edges(bt, vt):
                    g_s, g_o = gathered_prop_tables()
                    gk, gp = (g_s, g_o) if s_known else (g_o, g_s)
                    gorder = jnp.argsort(gk)
                    nb, nc, nv, over = _expand_fixed(
                        bt, vt, probe_vals(bt), gk[gorder], gp[gorder],
                        capacity, paths)
                    return nb, nc, nv, over, jnp.int32(sc.edge_rows), g_s, g_o

                if mode == "skip":
                    keys, payload = local_table()
                    bind, new_col, valid, over = _expand_fixed(
                        bind, valid, probe_vals(bind), keys, payload, capacity,
                        paths)
                    dec_v, row_v = jnp.int32(COMM_SKIP), jnp.int32(0)
                elif mode == "gather":
                    bind, new_col, valid, over, shipped = exp_via_gather(
                        bind, valid)
                    dec_v, row_v = jnp.int32(COMM_GATHER), shipped
                else:  # dynamic: ship the smaller side
                    (bind, new_col, valid, over, _), dec_v, row_v = \
                        ship_smaller_side(exp_via_gather_c, exp_via_edges)
                ovf = jnp.maximum(ovf, over)
                new_var = e.dst if s_known else e.src
                if new_var < 0:
                    var_cols.append(new_var)
                    bind = jnp.concatenate([bind, new_col[:, None]], axis=1)
                else:
                    valid = valid & (new_col == new_var)
                    bind = jnp.where(valid[:, None], bind, -1)

            decs.append(dec_v)
            rows.append(row_v)

    dec_arr = (jnp.stack(decs) if decs else jnp.zeros((0,), jnp.int32))
    row_arr = (jnp.stack(rows) if rows else jnp.zeros((0,), jnp.int32))
    return bind, valid, var_cols, jnp.maximum(ovf, 0), dec_arr, row_arr


# ----------------------------------------------------------------------
# shard_map distributed execution
# ----------------------------------------------------------------------

def make_spmd_matcher(mesh: Mesh, axis: str, pattern: QueryGraph,
                      capacity: int, prop_windows: Dict[int, int],
                      comm: Optional[Sequence[StepComm]] = None,
                      seed_decimate: bool = False,
                      route_ranks: Optional[Sequence[int]] = None,
                      route_width: int = 0,
                      join_paths: Optional[Dict[str, int]] = None):
    """Build a jitted SPMD function: the six site-sharded
    ``SiteStore.csr_arrays()`` tables (call
    ``fn(*store.csr_arrays())``) -> gathered binding tables
    (num_sites * capacity, V), validity mask, the per-device overflow
    row count (num_sites,), and the planner's per-join-step decision /
    shipped-row vectors (replicated).  ``prop_windows`` carries the
    static window rows of every property of ``pattern``
    (``SiteStore.prop_window``); the match loop slices pre-sorted
    property runs of that size.

    Every join step inside ``_match_shard`` broadcast-joins with the
    shipping mode chosen by ``comm`` (see ``plan_step_comm``; ``None``
    ships bindings every step -- the paper's 'ship intermediate
    results'); those bytes are what the §Roofline collective term
    counts.  A non-zero overflow entry means that device's table filled
    and the caller must retry at a higher capacity for an exact answer.

    ``seed_decimate=True`` asserts step 0's property is shard-complete
    (``plan_seed_decimation``): the seed rows are then striped across
    the mesh so replicated storage becomes partitioned work -- without
    it every device would duplicate every seed and the answer would
    ship ``m`` times.  Only valid when the completeness assertion
    holds.  ``route_ranks`` / ``route_width``
    (``RoutePlan.seed_ranks`` / ``RoutePlan.width``) restrict the
    query to its route members and re-scope the striping to them (see
    ``_match_shard``).

    ``join_paths`` (a dict) is filled with the join-path counts
    (``JOIN_PATHS``) when the function is first traced.
    """
    # on a 1-device mesh the per-step gathers are identity and the
    # gathered dedup can never find anything (folded site groups are
    # unique'd at store build) -- skip both, keeping the shard-local
    # fast path; the mesh size is static at trace time.
    m = int(np.prod(mesh.devices.shape))
    step_axis = axis if m > 1 else None

    def per_site(*arrs):
        csr = tuple(a[0] for a in arrs)
        bind, valid, cols, ovf, dec, rows = _match_shard(
            csr, prop_windows, pattern, capacity, axis=step_axis,
            comm=comm, axis_size=m, seed_decimate=seed_decimate,
            route_ranks=route_ranks, route_width=route_width,
            paths=join_paths)
        with jax.named_scope("final_gather"):
            g_bind = jax.lax.all_gather(bind, axis, tiled=True)
            g_valid = jax.lax.all_gather(valid, axis, tiled=True)
            g_ovf = jax.lax.all_gather(ovf[None], axis, tiled=True)
        return g_bind, g_valid, g_ovf, dec, rows

    fn = jax.shard_map(per_site, mesh=mesh,
                       in_specs=(P(axis, None),) * 6,   # csr_arrays()
                       out_specs=(P(), P(), P(), P(), P()), check_vma=False)
    return jax.jit(fn)


def _matcher_args(store: SiteStore) -> Tuple[jax.Array, ...]:
    """The device arrays a matcher of ``make_spmd_matcher`` takes."""
    return store.csr_arrays()


def _unique_rows(rows: np.ndarray) -> Tuple[np.ndarray, bool]:
    """Exactly ``np.unique(rows, axis=0)`` -- the same rows, dtype and
    lexicographic order -- and whether the packed path ran.

    ``axis=0`` sorts each row as one ``void`` scalar, field by field,
    which is slow.  When every value is a vertex id in
    ``[0, MAX_VERTEX_ID]`` and the columns fit 63 bits, each row packs
    into one int64 key, first column in the high bits (key order is row
    order), and one integer ``np.unique`` does the work.  Any other
    table (more columns, a value out of range, not integers) falls back
    to ``np.unique(rows, axis=0)``.  An empty table is returned as is.
    """
    n_cols = rows.shape[1]
    bits = MAX_VERTEX_ID.bit_length()
    mask = (1 << bits) - 1
    fits = (np.issubdtype(rows.dtype, np.integer)
            and 0 < n_cols * bits <= 63)
    if rows.size == 0:
        return rows, fits
    if fits and rows.min() >= 0 and rows.max() <= MAX_VERTEX_ID:
        key = np.zeros(rows.shape[0], np.int64)
        for j in range(n_cols):
            key <<= bits
            key |= rows[:, j].astype(np.int64, copy=False)
        key = np.unique(key)
        out = np.empty((key.shape[0], n_cols), rows.dtype)
        for j in range(n_cols - 1, -1, -1):
            out[:, j] = key & mask
            key >>= bits
        return out, True
    return np.unique(rows, axis=0), False


def spmd_match(store: SiteStore, mesh: Mesh, axis: str,
               pattern: QueryGraph, capacity: int = 4096
               ) -> Tuple[np.ndarray, List[int]]:
    """Run the SPMD matcher and return deduped host-side bindings."""
    windows = {e.prop: store.prop_window(e.prop) for e in pattern.edges}
    fn = make_spmd_matcher(mesh, axis, pattern, capacity, windows)
    bind, valid, _ovf, _dec, _rows = jax.device_get(
        fn(*_matcher_args(store)))
    cols = pattern_var_order(pattern)
    rows, _ = _unique_rows(bind[np.asarray(valid)])
    return rows, cols


# ----------------------------------------------------------------------
# SPMD execution engine (Engine protocol)
# ----------------------------------------------------------------------

#: a constant stays in the compiled pattern when its side of the
#: property (subjects or objects) averages at least this many edges per
#: distinct value: a class of ``rdf:type`` is such a hub, and stripping
#: it would scan every typing of every class.  Few values reach it, so
#: few programs are compiled for them.
HUB_EDGES_PER_VALUE = 4096

class SpmdEngine(EngineBase):
    """``Engine``-protocol front over the SPMD ``SiteStore`` path.

    Logical sites are folded round-robin onto the mesh devices (on a
    1-device CPU host everything lands in one shard; overlap across
    folded sites is removed by the final dedup, so answers stay exact).
    Beyond one device, every join step broadcast-joins the binding
    tables (``_match_shard`` with the mesh axis), so matches whose edges
    straddle devices are assembled exactly -- the SPMD backend answers
    identically to the exact host engine on any mesh.

    Queries are matched *whole* as one SPMD program; constants are
    normalized out of the compiled pattern and re-applied as a host-side
    filter, so the jit cache is keyed by query **shape** x **capacity
    tier** -- a workload of thousands of template-instantiated queries
    compiles once per template (per tier), and the cache persists across
    ``execute``/``execute_many`` calls for the engine's lifetime.  A
    constant on a hub side of its property (``HUB_EDGES_PER_VALUE``,
    e.g. the class of an ``rdf:type`` edge) stays in the compiled
    pattern and is matched on the device: the shape then includes it.

    ``capacity`` bounds the per-device binding table.  Overflow is
    counted in-trace; on overflow the query transparently re-executes
    with doubled capacity (at most log2(max_capacity/capacity)
    recompiles, each cached; an overflow that shows the table needs
    more skips the tiers below it) until exact.  If ``max_capacity`` is
    still not enough, a ``RuntimeError`` is raised -- never a silently
    truncated answer.  ``stats().extra`` reports ``capacity_retries``
    (re-executions at a higher tier) and ``overflow_events`` (attempts
    that overflowed).

    With ``comm_plan=True`` (default) every join step's shipping is
    planned size-aware (see ``plan_step_comm`` / ``_match_shard``):
    shard-complete properties skip the collective entirely, and
    otherwise the smaller of global-bindings vs. property-edge-rows is
    shipped.  Two further mechanisms ride on that: a gathered edge
    table is cached across the join steps of one query that share a
    property (``COMM_EDGE_CACHED``: reuse is free), and a query whose
    step-0 property is shard-complete stripes its seed rows across the
    mesh (``plan_seed_decimation``) so replicated storage -- e.g. from
    the plan's allocation-aware replication pass, whose property set
    arrives via ``replicated_props`` -- runs as balanced partitioned
    work instead of every device duplicating the whole query.
    ``stats().comm_bytes`` accounts the data-plane bytes
    actually put on the wire (valid binding rows / resident edge rows
    to each of the ``m - 1`` peers; control scalars such as the
    planner's psum'd binding count are not ledgered, matching the host
    engine's intermediate-result accounting), and ``stats().extra``
    counts per-step outcomes
    (``gather_steps`` / ``edge_shipped_steps`` / ``skipped_gathers``)
    and the ledger delta vs. always-gathering (``comm_bytes_saved``).
    ``comm_plan=False`` restores the naive gather-every-step plan
    (same exact answers, byte ledger accounted the same way).

    With ``routing=True`` (default, active only alongside the planner
    on a multi-device mesh) each query additionally runs on its
    ``RoutePlan`` (``repro.core.routing``): devices holding none of
    the query's non-replicated properties are masked out at step 0 and
    hold zero valid rows for the whole query, route-complete steps
    skip their collective (``route_skipped_steps``), fully-replicated
    shapes are rendezvous-pinned to one device, and every ledgered
    byte count uses ``route_width - 1`` peers.  ``stats().extra``
    counts ``routed_queries``; ``ExecStats.sites_touched`` shrinks to
    the route (feeding the online monitor's per-site heat gauges).
    ``routing=False`` restores whole-mesh execution bit-identically.

    With tracing enabled (``Session(trace=True)`` or a process-default
    tracer, see ``repro.obs``) every query's root span carries one
    structured record per join step per attempted capacity tier --
    decision (``gather`` / ``edge_ship`` / ``skip`` / ``edge_cached``),
    shipped rows, ledgered bytes, binding-table occupancy, capacity
    tier -- plus a ``final_gather`` record; the records are built from
    the same per-step decision/rows vectors the ledger reads, so their
    byte sum reconciles *exactly* with ``stats().comm_bytes`` and their
    per-decision counts with the step counters.  Under the root span,
    each capacity attempt opens a ``match`` span (the matcher call
    through ``block_until_ready``: the host waiting on the device;
    attrs ``capacity``, ``compiled``, ``overflow``, and the program's
    ``cycles`` (join steps that close a cycle, ``cycle_close_steps``)
    and ``edges`` (the pattern's edge count)) and a ``fetch``
    span (the copy to the host; ``bytes``), then the host work opens
    ``dedup`` (``rows_in``, ``rows_out``, ``reused``) and ``filter``
    (``rows``).  A member of a shape-shared batch group that reuses
    the group's device run has no ``match``/``fetch`` of its own.
    Tracing happens on the host: nothing new is traced inside
    ``shard_map``, and a disabled tracer opens no span and skips record
    building entirely.
    """

    trace_name = "spmd"

    def __init__(self, graph: RDFGraph, site_edge_ids: Sequence[np.ndarray],
                 mesh: Optional[Mesh] = None, axis: str = "sites",
                 capacity: int = 4096, cost: Optional[CostModel] = None,
                 max_capacity: Optional[int] = None,
                 comm_plan: bool = True,
                 replicated_props: Optional[set] = None,
                 routing: bool = True):
        self._init_engine_base()
        self.graph = graph
        # provenance from the allocation-aware replication pass: which
        # properties the plan replicated to every site.  Residency
        # metadata (not this set) is what *detects* shard-completeness;
        # the set only attributes skip decisions to replication in the
        # stats counters.
        self.replicated_props = set(replicated_props or ())
        self.logical_sites = len(site_edge_ids)
        if mesh is None:
            from ..launch.mesh import make_host_mesh
            mesh = make_host_mesh(len(jax.devices()), axis=axis)
        self.mesh, self.axis = mesh, axis
        m = int(np.prod(mesh.devices.shape))
        folded: List[List[np.ndarray]] = [[] for _ in range(m)]
        for j, eids in enumerate(site_edge_ids):
            folded[j % m].append(np.asarray(eids, np.int64))
        self.store = SiteStore.build(
            graph, [np.unique(np.concatenate(g)) if g
                    else np.zeros(0, np.int64) for g in folded],
            sharding=self._store_sharding())
        self.capacity = int(capacity)
        self.max_capacity = max(int(max_capacity) if max_capacity is not None
                                else max(self.capacity, 1 << 20),
                                self.capacity)
        self.cost = cost or CostModel()
        self.comm_plan = bool(comm_plan)
        # per-query routing (repro.core.routing): riding on the comm
        # planner's residency metadata, so planner off => routing off
        # (the naive arm must reproduce PR-3 ledger semantics exactly);
        # trivially off on a 1-device mesh
        self.routing = bool(routing)
        self._routes: Dict[Tuple, RoutePlan] = {}
        # keyed by exact edge structure (NOT QueryGraph, whose __eq__ is
        # canonical-isomorphism: isomorphic patterns with different edge
        # orders produce different binding-column orders and must not
        # share a compiled matcher) x capacity tier x store generation
        self._matchers: Dict[Tuple[Tuple, int, int], object] = {}
        # per compiled matcher, the join paths its trace took
        # (JOIN_PATHS; filled when the matcher first runs)
        self._join_paths: Dict[Tuple[Tuple, int, int], Dict[str, int]] = {}
        # per-pattern static communication specs (planner output)
        self._comm_specs: Dict[Tuple, Tuple[StepComm, ...]] = {}
        # per-pattern seed-decimation decision (store + planner mode are
        # fixed per engine, so the boolean is too)
        self._seed_decim: Dict[Tuple, bool] = {}
        # last capacity tier that answered this edge structure exactly:
        # repeat queries start the retry ladder there instead of
        # re-climbing (and re-executing) every lower tier
        self._cap_hints: Dict[Tuple, int] = {}
        # (property, subject side?) -> whether a constant there is kept
        # in the compiled pattern (HUB_EDGES_PER_VALUE)
        self._hub_sides: Dict[Tuple[int, bool], bool] = {}
        self._compiles = 0
        # bumped by swap_store: matcher cache entries are keyed by store
        # generation (a matcher closes over comm specs / routes planned
        # against one store's residency), and the serving layer reads it
        # to observe hot swaps
        self._store_gen = 0
        # batch-level shape sharing (_execute_batch): while a group of
        # same-normalized-shape queries executes, the first member's
        # device run is parked here and every later member reuses it
        self._shared_run = None
        self._shared_run_key: Optional[Tuple] = None
        self._bump("batch_shape_hits", 0)
        self._bump("dedup_packed", 0)
        self._bump("dedup_fallback", 0)
        self._bump("capacity_retries", 0)
        self._bump("overflow_events", 0)
        self._bump("gather_steps", 0)
        self._bump("edge_shipped_steps", 0)
        self._bump("skipped_gathers", 0)
        self._bump("comm_bytes_saved", 0)
        self._bump("replication_skipped_steps", 0)
        self._bump("edge_cache_hits", 0)
        self._bump("decimated_seed_queries", 0)
        self._bump("routed_queries", 0)
        self._bump("route_skipped_steps", 0)
        self._bump("store_swaps", 0)

    @property
    def num_sites(self) -> int:
        return self.logical_sites

    def _store_sharding(self) -> NamedSharding:
        """Row-sharding of every ``SiteStore`` array over the mesh axis:
        device ``j`` holds folded site group ``j``."""
        return NamedSharding(self.mesh, P(self.axis, None))

    # ------------------------------------------------------------------
    def _route(self, pattern: QueryGraph) -> Optional[RoutePlan]:
        """Cached ``plan_route`` for this pattern, or ``None`` when
        routing is inactive (disabled, planner off, or a 1-device mesh
        where there is nothing to route)."""
        if not (self.routing and self.comm_plan
                and self.store.num_sites > 1):
            return None
        rp = self._routes.get(pattern.edges)
        if rp is None:
            rp = plan_route(self.store, pattern)
            self._routes[pattern.edges] = rp
        return rp

    def _comm_spec(self, pattern: QueryGraph) -> Tuple[StepComm, ...]:
        """Static per-join-step communication spec for this pattern over
        the engine's store (cached; planner and routing on/off are
        fixed per engine)."""
        spec = self._comm_specs.get(pattern.edges)
        if spec is None:
            spec = plan_step_comm(self.store, pattern,
                                  enabled=self.comm_plan,
                                  route=self._route(pattern))
            self._comm_specs[pattern.edges] = spec
        return spec

    def _seed_decimation(self, pattern: QueryGraph) -> bool:
        """Cached seed-decimation decision for this pattern.  Routed
        execution uses the route's decision (completeness on the
        members is enough); otherwise ``plan_seed_decimation``'s
        mesh-wide rule.  Decimation is part of the planned-serving
        mode: with the planner off the engine must reproduce the naive
        gather-every-step baseline exactly (bench_spmd_comm's
        spmd_naive arm, the PR-3/PR-4 ledger semantics)."""
        dec = self._seed_decim.get(pattern.edges)
        if dec is None:
            route = self._route(pattern)
            if route is not None:
                dec = route.decimate
            else:
                dec = self.comm_plan and plan_seed_decimation(self.store,
                                                              pattern)
            self._seed_decim[pattern.edges] = dec
        return dec

    def _hub_side(self, prop: int, subject_side: bool) -> bool:
        """Whether ``prop``'s subjects (or objects) average at least
        ``HUB_EDGES_PER_VALUE`` edges per distinct value in the graph."""
        key = (prop, subject_side)
        hub = self._hub_sides.get(key)
        if hub is None:
            g = self.graph
            vals = (g.s if subject_side else g.o)[g.p == prop]
            hub = vals.size >= HUB_EDGES_PER_VALUE * max(
                np.unique(vals).size, 1)
            self._hub_sides[key] = hub
        return hub

    def _shape(self, query: QueryGraph
               ) -> Tuple[QueryGraph, Dict[int, int]]:
        """The pattern this engine compiles for ``query`` and its
        normalization map: constants stripped to variables, except those
        on a hub side of their property (``_hub_side``) while a variable
        remains."""
        keep = frozenset(
            v for e in query.edges
            for v, subject_side in ((e.src, True), (e.dst, False))
            if v >= 0 and self._hub_side(e.prop, subject_side))
        if not query.variables():
            keep = frozenset()
        return query.normalize(keep), query.normalization_map(keep)

    def _start_capacity(self, pattern: QueryGraph) -> int:
        """First capacity tier for a pattern with no retry-ladder hint.
        A decimated seed step over ``r`` route members concentrates
        only ``1/r`` of the seeds per member (vs. ``1/m`` assumed by
        the configured capacity when the property is mesh-complete), so
        for a *narrow* route over a non-mesh-complete seed property the
        ladder starts ``ceil(log2(m / r))`` tiers lower -- floored so
        the striped seed rows statically fit, and never above the
        configured capacity.  Cuts recompiles: narrow routes compile
        small tables first instead of paying the mesh-wide tier."""
        route = self._route(pattern)
        m = self.store.num_sites
        if (route is None or not route.decimate or route.p0_mesh_complete
                or not 1 <= route.width < m):
            return self.capacity
        shift = int(np.ceil(np.log2(m / route.width)))
        cap = max(self.capacity >> shift, 8)
        while cap < self.capacity and cap < route.seed_rows:
            cap *= 2
        return cap

    def _matcher(self, pattern: QueryGraph, capacity: int):
        key = (pattern.edges, capacity, self._store_gen)
        fn = self._matchers.get(key)
        if fn is None:
            windows = {e.prop: self.store.prop_window(e.prop)
                       for e in pattern.edges}
            route = self._route(pattern)
            paths: Dict[str, int] = {}
            fn = make_spmd_matcher(self.mesh, self.axis, pattern, capacity,
                                   windows,
                                   comm=self._comm_spec(pattern),
                                   seed_decimate=self._seed_decimation(
                                       pattern),
                                   route_ranks=(route.seed_ranks
                                                if route is not None
                                                else None),
                                   route_width=(route.width
                                                if route is not None
                                                else 0),
                                   join_paths=paths)
            self._matchers[key] = fn
            self._join_paths[key] = paths
            self._compiles += 1
        return fn

    def _run_exact(self, norm: QueryGraph
                   ) -> Tuple[np.ndarray, np.ndarray, List[int],
                              List[Tuple[np.ndarray, np.ndarray, int]]]:
        """Execute the matcher for a normalized pattern, geometrically
        doubling the binding-table capacity until no device overflows.
        Returns (bindings, valid, capacities attempted -- last one
        succeeded, per-attempt (step decisions, step shipped rows,
        final-gather valid rows) for the comm ledger).  Raises
        RuntimeError if ``max_capacity`` is still too small -- a
        truncated answer is never returned."""
        cap = self._cap_hints.get(norm.edges, self._start_capacity(norm))
        caps: List[int] = []
        attempts: List[Tuple[np.ndarray, np.ndarray, int]] = []
        tr = self.tracer
        trace_on = tr.enabled
        while True:
            caps.append(cap)
            n_compiled = self._compiles
            fn = self._matcher(norm, cap)
            with (tr.span("match", capacity=cap,
                          compiled=self._compiles > n_compiled,
                          cycles=cycle_close_steps(norm),
                          edges=norm.num_edges)
                  if trace_on else NULL_SPAN) as match_sp:
                out = fn(*_matcher_args(self.store))
                jax.block_until_ready(out)
            with (tr.span("fetch", bytes=sum(x.nbytes for x in out))
                  if trace_on else NULL_SPAN):
                bind, valid, ovf, dec, rows = jax.device_get(out)
            attempts.append((np.asarray(dec), np.asarray(rows),
                             int(np.asarray(valid).sum())))
            over_rows = int(np.max(np.asarray(ovf), initial=0))
            overflow = over_rows > 0
            match_sp.set("overflow", overflow)
            if not overflow:
                self._cap_hints[norm.edges] = cap
                return np.asarray(bind), np.asarray(valid), caps, attempts
            self._bump("overflow_events")
            if cap >= self.max_capacity:
                raise RuntimeError(
                    f"SPMD binding tables still overflow at max_capacity="
                    f"{cap} rows per device (started at {self.capacity}) "
                    f"for pattern {norm.edges}; refusing to return a "
                    f"truncated answer.  Raise Session(spmd_capacity=...)"
                    f"/spmd_max_capacity (or SpmdEngine capacity/"
                    f"max_capacity) for this workload.")
            # the overflow count is a floor of the rows the table must
            # hold (steps after the first overflowing one saw a cut
            # table): go straight to the first doubling that holds it
            need = cap + over_rows
            cap *= 2
            while cap < need and cap < self.max_capacity:
                cap *= 2
            cap = min(cap, self.max_capacity)
            self._bump("capacity_retries")

    def _execute(self, query: QueryGraph) -> QueryResult:
        """Match ``query`` whole as one SPMD program and return the
        exact ``QueryResult`` (see class docstring for the retry /
        planning behaviour).  Raises ``NotImplementedError`` for
        wildcard properties and ``RuntimeError`` when ``max_capacity``
        cannot hold the answer."""
        if any(e.prop == PROP_VAR for e in query.edges):
            raise NotImplementedError(
                "SPMD matcher requires constant properties (wildcard "
                "property labels would match the -1 padding)")
        t0 = time.perf_counter()
        norm, nmap = self._shape(query)
        # batch-level shape sharing: inside an _execute_batch group the
        # matcher output is identical for every member (same normalized
        # pattern, same store), so run the device program once and let
        # the rest of the group reuse (bind, valid, caps, attempts) --
        # per-query constants are re-applied host-side below either way
        reused = (self._shared_run is not None
                  and self._shared_run_key == norm.edges)
        if reused:
            bind, valid, caps, attempts = self._shared_run
            self._bump("batch_shape_hits")
        else:
            bind, valid, caps, attempts = self._run_exact(norm)
            if self._shared_run_key == norm.edges:
                self._shared_run = (bind, valid, caps, attempts)
        tr = self.tracer
        trace_on = tr.enabled
        with (tr.span("dedup", reused=reused) if trace_on
              else NULL_SPAN) as sp:
            rows = bind[valid]
            sp.set("rows_in", int(rows.shape[0]))
            rows, packed = _unique_rows(rows)
            sp.set("rows_out", int(rows.shape[0]))
            sp.set("packed", packed)
        self._bump("dedup_packed" if packed else "dedup_fallback")
        with (tr.span("filter") if trace_on else NULL_SPAN) as sp:
            # re-apply the constants the normalization stripped
            var_order, step_in_cols, _ = _var_col_trace(norm)
            col_of = {nv: i for i, nv in enumerate(var_order)}
            keep = np.ones(rows.shape[0], dtype=bool)
            for orig, nv in nmap.items():
                if orig >= 0 and nv < 0:
                    keep &= rows[:, col_of[nv]] == orig
            rows = rows[keep]
            bindings = {orig: rows[:, col_of[nv]].astype(np.int32)
                        for orig, nv in nmap.items() if orig < 0}
            n = int(rows.shape[0])
            sp.set("rows", n)
        # communication ledger, from the per-step decisions the matcher
        # reported: logical data-plane bytes on the wire per step (each
        # device ships to the other m-1 peers), either the valid
        # binding rows (cols * int32 + the valid byte), the property's
        # resident edge rows (two int32 columns), or nothing when the
        # step was skipped.  Control scalars (the planner's psum'd
        # binding count, the per-device overflow counts) are not
        # ledgered, matching the host engine's intermediate-result
        # accounting.  The final gather ships every device's full-width
        # valid rows once more.  Overflowed attempts really ran their
        # gathers on device, so every attempted tier is counted.
        m = self.store.num_sites
        V = len(col_of)
        spec = self._comm_spec(norm)
        route = self._route(norm)
        # ledger peers: routed execution only moves data among the
        # route's members (devices outside the route hold zero valid
        # rows at every step), so each step ships to width-1 peers.
        # With routing off (or a whole-mesh route) this is the old m-1.
        w = route.width if route is not None else m
        routed = route is not None and route.width < m
        comm = 0
        if reused:
            # the device run -- and every collective in it -- happened
            # once, for the group's first member; this member put
            # nothing on the wire and re-counting the shared steps
            # would double-ledger them
            if trace_on:
                tr.annotate(devices=m, capacity_tiers=caps,
                            shape_reused=True, route_width=w,
                            routed=routed,
                            comm_planner=bool(self.comm_plan))
        elif m > 1:             # 1 device: no peers, nothing ever ships
            decimated = self._seed_decimation(norm)
            if decimated:
                self._bump("decimated_seed_queries")
            if routed:
                self._bump("routed_queries")
            for ai, (dec, srows, n_final) in enumerate(attempts):
                for ji, sc in enumerate(spec):
                    d, r = int(dec[ji]), int(srows[ji])
                    row_bytes = bind_row_bytes(step_in_cols[ji])
                    step_bytes = 0
                    if d == COMM_GATHER:
                        step_bytes = (w - 1) * r * row_bytes
                        self._bump("gather_steps")
                    elif d == COMM_EDGE:
                        step_bytes = (w - 1) * sc.edge_bytes
                        self._bump("edge_shipped_steps")
                        self._bump("comm_bytes_saved",
                                   (w - 1) * (r * row_bytes
                                              - sc.edge_bytes))
                    elif d == COMM_EDGE_CACHED:
                        # the global edge table was already live in this
                        # trace: nothing on the wire, the whole binding
                        # gather avoided
                        self._bump("edge_cache_hits")
                        self._bump("comm_bytes_saved",
                                   (w - 1) * r * row_bytes)
                    else:
                        self._bump("skipped_gathers")
                        if sc.route_complete:
                            self._bump("route_skipped_steps")
                        if sc.prop in self.replicated_props:
                            self._bump("replication_skipped_steps")
                    comm += step_bytes
                    if trace_on:
                        # one structured record per join step per
                        # attempted tier: same vectors, same byte
                        # formulas as the ledger above -- trace and
                        # ledger cannot diverge
                        tr.add_record({
                            "kind": "comm_step", "attempt": ai,
                            "capacity": caps[ai], "step": ji + 1,
                            "prop": sc.prop,
                            "decision": COMM_DECISION_NAMES[d],
                            "rows": r, "bytes": step_bytes,
                            "route_width": w,
                            "occupancy": (r / (m * caps[ai])
                                          if d != COMM_SKIP else 0.0)})
                final_bytes = (w - 1) * n_final * bind_row_bytes(V)
                comm += final_bytes
                if trace_on:
                    tr.add_record({
                        "kind": "comm_step", "attempt": ai,
                        "capacity": caps[ai], "step": len(spec) + 1,
                        "prop": -1, "decision": "final_gather",
                        "rows": n_final, "bytes": final_bytes,
                        "route_width": w,
                        "occupancy": n_final / (m * caps[ai])})
            if trace_on:
                tr.annotate(devices=m, capacity_tiers=caps,
                            overflow_events=len(caps) - 1,
                            capacity_retries=len(caps) - 1,
                            seed_decimated=bool(decimated),
                            route_width=w, routed=routed,
                            comm_planner=bool(self.comm_plan))
        elif trace_on:
            # 1-device mesh: no peers, no collectives -- the span says
            # so instead of carrying zero-filled step records
            tr.annotate(devices=m, capacity_tiers=caps,
                        overflow_events=len(caps) - 1,
                        capacity_retries=len(caps) - 1,
                        seed_decimated=False,
                        route_width=1, routed=False,
                        comm_planner=bool(self.comm_plan))
        elapsed = time.perf_counter() - t0
        if routed:
            touched = {j for j in range(self.logical_sites)
                       if (j % m) in route.member_set}
            busy = {j: elapsed / max(w, 1) for j in route.members}
        else:
            touched = set(range(self.logical_sites))
            busy = {j: elapsed / max(m, 1) for j in range(m)}
        stats = ExecStats(elapsed, int(comm), touched, busy, n, 1)
        return self._finish(query, QueryResult(bindings, n, stats))

    def _execute_batch(self, batch: List[QueryGraph]) -> List[QueryResult]:
        """Group intra-batch queries by normalized shape key before
        dispatch.

        Queries sharing a compiled shape (``_shape``) hit the same jit
        cache entry AND -- because normalization strips the constants
        that differ between them -- produce the *identical* matcher
        output over this engine's store.  The sequential default would
        pay one full device round-trip per query; here each group runs
        the device program once and every later member reuses the
        binding tables, applying only its own host-side constant filter
        (counted as ``batch_shape_hits``, comm attributed to the first
        member only).  Results come back in input order, answers
        identical to sequential execution.
        """
        groups: Dict[Tuple, List[int]] = {}
        for i, q in enumerate(batch):
            if any(e.prop == PROP_VAR for e in q.edges):
                # will raise in _execute; keep it alone in its group so
                # the error surfaces for exactly this query
                groups.setdefault(("__prop_var__", i), []).append(i)
            else:
                groups.setdefault(self._shape(q)[0].edges, []).append(i)
        out: List[Optional[QueryResult]] = [None] * len(batch)
        for key, idxs in groups.items():
            # key[:1] is safe on the empty tuple (zero-edge queries
            # normalize to an empty edge key), unlike key[0]
            share = len(idxs) > 1 and key[:1] != ("__prop_var__",)
            self._shared_run_key = key if share else None
            self._shared_run = None
            try:
                for i in idxs:
                    out[i] = self.execute(batch[i])
            finally:
                self._shared_run_key = None
                self._shared_run = None
        return out

    @property
    def store_generation(self) -> int:
        """Monotonic counter bumped by every ``swap_store`` -- the
        serving layer's witness that a hot swap happened."""
        return self._store_gen

    def swap_store(self, site_edge_ids: Sequence[np.ndarray],
                   replicated_props: Optional[set] = None,
                   graph: Optional[RDFGraph] = None) -> int:
        """Atomically replace the folded ``SiteStore`` with one built
        for a new placement (and optionally a delta-updated graph) --
        the adaptive loop's hot-swap path: the engine object, its mesh,
        and its jit machinery survive a re-partition, so a serving
        front door keeps the same engine handle across plan versions.

        The new store is built *before* any engine state changes, then
        installed together with the planner caches' invalidation in one
        host-side step -- the engine is single-threaded per the Engine
        protocol, so an execute either runs entirely on the old store
        or entirely on the new one, never a mix.  Compiled matchers are
        keyed by store generation: entries for the old store stay in
        the cache (they are closed over retired comm specs, never
        matched again), while shapes re-planned against the new
        residency compile fresh on first use.

        Returns the new store generation.
        """
        if graph is not None:
            self.graph = graph
        m = int(np.prod(self.mesh.devices.shape))
        folded: List[List[np.ndarray]] = [[] for _ in range(m)]
        for j, eids in enumerate(site_edge_ids):
            folded[j % m].append(np.asarray(eids, np.int64))
        store = SiteStore.build(
            self.graph, [np.unique(np.concatenate(g)) if g
                         else np.zeros(0, np.int64) for g in folded],
            sharding=self._store_sharding())
        # install: everything planned against the old store's residency
        # (routes, comm specs, seed decimation, capacity hints) is
        # invalid for the new placement
        self.store = store
        self.logical_sites = len(site_edge_ids)
        if replicated_props is not None:
            self.replicated_props = set(replicated_props)
        self._routes.clear()
        self._comm_specs.clear()
        self._seed_decim.clear()
        self._cap_hints.clear()
        self._hub_sides.clear()
        self._shared_run = None
        self._shared_run_key = None
        self._store_gen += 1
        self._bump("store_swaps")
        return self._store_gen

    def route_key(self, query: QueryGraph) -> Optional[Tuple[int, ...]]:
        """Stable routing token for ``query``: its route's member
        devices, or ``None`` when routing is inactive (or the query is
        unroutable).  A pure function of the *normalized* shape, so the
        serving layer can fold it into its shape-bucket keys without
        ever splitting a same-shape batch (``repro.serve``)."""
        if any(e.prop == PROP_VAR for e in query.edges):
            return None
        route = self._route(query.normalize())
        return route.members if route is not None else None

    def _stats_extra(self) -> Dict[str, float]:
        from ..kernels.ops import _interpret_default
        kernels = sum(_use_pallas_probes(k) for k in TPU_KERNELS)
        # join paths summed over every matcher traced so far: which
        # kernels and which oracles the engine's programs contain
        traced = {f"traced_{name}": 0.0 for name in JOIN_PATHS}
        for paths in self._join_paths.values():
            for name, n in paths.items():
                traced[f"traced_{name}"] += n
        return {**traced,
                "pallas_interpret": float(kernels > 0
                                          and _interpret_default(None)),
                "compiled_shapes": float(self._compiles),
                "store_generation": float(self._store_gen),
                "devices": float(self.store.num_sites),
                "comm_planner": float(self.comm_plan),
                "routing": float(bool(self.routing and self.comm_plan
                                      and self.store.num_sites > 1)),
                "replicated_props": float(len(self.replicated_props)),
                "pallas_join_kernels": float(kernels)}
