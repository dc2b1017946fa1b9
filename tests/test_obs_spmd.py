"""SPMD trace <-> ledger reconciliation: the per-step records a traced
SPMD query attaches to its root span must account for the engine's
communication ledger *exactly* -- same decisions, same byte formulas --
at any device count (CI runs this at 1, 2, and 4 devices).

Two invariants per query:

* the sum of traced step ``bytes`` equals the query's ``comm_bytes``
  delta (and, aggregated, the cumulative ``stats().comm_bytes``);
* the per-decision record counts equal the ``gather_steps`` /
  ``edge_shipped_steps`` / ``skipped_gathers`` / ``edge_cache_hits``
  counter deltas.

On a 1-device mesh nothing ships, so both sides are zero and the root
span carries the ``devices=1`` annotation instead of step records.
"""
import numpy as np
import pytest

from repro.core import (PartitionConfig, Session, build_plan,
                        generate_watdiv, generate_workload,
                        make_shape_queries)
from repro.core import spmd as S
from repro.core.query import QueryGraph
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer

DECISION_COUNTERS = {"gather": "gather_steps",
                     "edge_ship": "edge_shipped_steps",
                     "skip": "skipped_gathers",
                     "edge_cached": "edge_cache_hits"}


@pytest.fixture(scope="module")
def spmd_setup():
    g = generate_watdiv(8_000, seed=5)
    wl = generate_workload(g, 500, seed=6)
    plan = build_plan(g, wl, PartitionConfig(kind="vertical", num_sites=4))
    return g, plan


def _shape_queries(g, per_shape=3, seed=9):
    rng = np.random.default_rng(seed)
    p = np.asarray(g.p)

    def rp():
        return int(p[rng.integers(0, len(p))])

    out = []
    for _ in range(per_shape):
        out.extend(make_shape_queries(rp).values())
    return out


def _counters(sess):
    extra = sess.stats().extra
    return {k: extra[k] for k in DECISION_COUNTERS.values()}


@pytest.mark.slow
def test_spmd_trace_reconciles_with_ledger(spmd_setup):
    g, plan = spmd_setup
    tracer = Tracer(enabled=True, capacity=256)
    sess = Session(plan, backend="spmd", tracer=tracer,
                   metrics_registry=MetricsRegistry())
    m = sess.engine.store.num_sites
    total_traced = 0
    for q in _shape_queries(g):
        before_comm = sess.stats().comm_bytes
        before = _counters(sess)
        sess.execute(q)
        delta_comm = sess.stats().comm_bytes - before_comm
        after = _counters(sess)
        root = tracer.store.spans()[-1]
        assert root.name == "query" and root.attrs["backend"] == "spmd"
        assert root.attrs["devices"] == m

        recs = [r for r in root.records if r["kind"] == "comm_step"]
        # invariant 1: traced step bytes sum to the ledger exactly
        assert sum(r["bytes"] for r in recs) == delta_comm
        total_traced += delta_comm

        # invariant 2: per-decision record counts == counter deltas
        for decision, counter in DECISION_COUNTERS.items():
            n_rec = sum(1 for r in recs if r["decision"] == decision)
            assert n_rec == after[counter] - before[counter], \
                f"{decision} records disagree with {counter}"

        if m > 1:
            # exactly one final gather per attempted capacity tier
            finals = [r for r in recs if r["decision"] == "final_gather"]
            assert len(finals) == len(root.attrs["capacity_tiers"])
            assert root.attrs["capacity_retries"] == \
                len(root.attrs["capacity_tiers"]) - 1
            for r in recs:
                assert r["bytes"] >= 0
                assert 0.0 <= r["occupancy"] <= 1.0
        else:
            assert recs == [] and delta_comm == 0

    # aggregate: the whole traced stream reconciles with the ledger
    assert total_traced == sess.stats().comm_bytes


@pytest.mark.slow
def test_routed_trace_carries_route_width_and_reconciles(spmd_setup):
    """Replica routing keeps the trace honest: every ``comm_step``
    record of a routed query carries ``route_width`` in [1, m] (the
    peer factor its byte formula used), the root span annotates the
    width and the routed flag, and the routed trace still reconciles
    with the ledger byte-for-byte -- delta zero on every query."""
    g, plan = spmd_setup
    tracer = Tracer(enabled=True, capacity=256)
    sess = Session(plan, backend="spmd", tracer=tracer,
                   metrics_registry=MetricsRegistry())
    m = sess.engine.store.num_sites
    assert sess.stats().extra["routing"] == float(m > 1)
    saw_narrow = False
    for q in _shape_queries(g):
        before = sess.stats().comm_bytes
        sess.execute(q)
        delta = sess.stats().comm_bytes - before
        root = tracer.store.spans()[-1]
        assert "route_width" in root.attrs
        w = root.attrs["route_width"]
        assert 1 <= w <= m
        assert root.attrs["routed"] == (w < m and m > 1)
        saw_narrow |= bool(root.attrs["routed"])
        recs = [r for r in root.records if r["kind"] == "comm_step"]
        # every record carries the width its byte formula used, and the
        # routed trace<->ledger delta is exactly zero
        assert all(r["route_width"] == w for r in recs)
        assert sum(r["bytes"] for r in recs) - delta == 0
    if m > 1:
        # the vertical allocation concentrates properties, so at least
        # one shape of the sweep must have routed below the full mesh
        assert saw_narrow
        assert sess.stats().extra["routed_queries"] > 0


@pytest.mark.slow
def test_spmd_trace_covers_retry_tiers(spmd_setup):
    """A query forced through the overflow retry ladder traces every
    attempted tier -- one ``match`` and one ``fetch`` span each, every
    tier's first call compiling, only the last not overflowing -- and
    the bytes of *all* tiers are ledgered."""
    g, plan = spmd_setup
    tracer = Tracer(enabled=True, capacity=64)
    sess = Session(plan, backend="spmd", tracer=tracer,
                   metrics_registry=MetricsRegistry(),
                   spmd_capacity=8, spmd_max_capacity=1 << 20)
    q = _shape_queries(g, per_shape=1)[0]
    sess.execute(q)
    root = tracer.store.spans()[-1]
    tiers = root.attrs["capacity_tiers"]
    assert tiers == sorted(tiers)
    matches = root.find("match")
    assert [m.attrs["capacity"] for m in matches] == tiers
    assert len(root.find("fetch")) == len(tiers)
    assert all(m.attrs["compiled"] for m in matches)
    assert [m.attrs["overflow"] for m in matches] == \
        [True] * (len(tiers) - 1) + [False]
    recs = [r for r in root.records if r["kind"] == "comm_step"]
    assert sum(r["bytes"] for r in recs) == sess.stats().comm_bytes
    if sess.engine.store.num_sites > 1 and len(tiers) > 1:
        # each attempt contributes a full set of step records
        attempts = {r["attempt"] for r in recs}
        assert attempts == set(range(len(tiers)))
        assert {r["capacity"] for r in recs} == set(tiers)


def test_spmd_disabled_tracer_records_nothing(spmd_setup):
    g, plan = spmd_setup
    tracer = Tracer(enabled=False)
    sess = Session(plan, backend="spmd", tracer=tracer,
                   metrics_registry=MetricsRegistry())
    sess.execute(_shape_queries(g, per_shape=1)[0])
    assert len(tracer.store) == 0
    # the ledger is tracing-independent
    assert sess.stats().queries == 1


@pytest.mark.slow
def test_spmd_ledger_identical_traced_vs_untraced(spmd_setup):
    """Enabling tracing must not change results or the ledger (tracing
    is host-side only; nothing new is traced inside shard_map)."""
    g, plan = spmd_setup
    qs = _shape_queries(g, per_shape=2)
    plain = Session(plan, backend="spmd",
                    metrics_registry=MetricsRegistry())
    traced = Session(plan, backend="spmd", trace=True,
                     metrics_registry=MetricsRegistry())
    rows_plain = [plain.execute(q).num_rows for q in qs]
    rows_traced = [traced.execute(q).num_rows for q in qs]
    assert rows_plain == rows_traced
    sp, st = plain.stats(), traced.stats()
    assert sp.comm_bytes == st.comm_bytes
    assert sp.extra["gather_steps"] == st.extra["gather_steps"]
    assert sp.extra["skipped_gathers"] == st.extra["skipped_gathers"]


# ----------------------------------------------------------------------
# Child spans of an SPMD query: match / fetch per capacity attempt, then
# the host's dedup and filter
# ----------------------------------------------------------------------

def _children(root):
    return [c.name for c in root.children]


def test_spmd_query_span_tree(spmd_setup):
    g, plan = spmd_setup
    tracer = Tracer(enabled=True, capacity=64)
    sess = Session(plan, backend="spmd", tracer=tracer,
                   metrics_registry=MetricsRegistry())
    q = _shape_queries(g, per_shape=1)[1]
    res = sess.execute(q)
    root = tracer.store.spans()[-1]
    assert root.name == "query"
    assert _children(root) == ["match", "fetch", "dedup", "filter"]
    match, fetch, dedup, filt = root.children
    assert all(c.parent_id == root.span_id and c.children == []
               for c in root.children)
    # nested in the query span and in time order
    assert root.start <= match.start
    assert match.end <= fetch.start and fetch.end <= dedup.start
    assert dedup.end <= filt.start and filt.end <= root.end
    assert match.attrs["capacity"] == root.attrs["capacity_tiers"][0]
    assert match.attrs["compiled"] is True
    assert match.attrs["overflow"] is False
    assert fetch.attrs["bytes"] > 0
    assert dedup.attrs["reused"] is False
    assert dedup.attrs["rows_in"] >= dedup.attrs["rows_out"] \
        >= filt.attrs["rows"] == res.num_rows
    sess.execute(q)                    # the same program, now compiled
    assert tracer.store.spans()[-1].children[0].attrs["compiled"] is False


def test_spmd_shape_shared_batch_members_reuse_the_run(spmd_setup):
    """A batch of one shape runs the device once: the later members
    have no ``match``/``fetch`` and their ``dedup`` is marked reused."""
    g, plan = spmd_setup
    tracer = Tracer(enabled=True, capacity=64)
    sess = Session(plan, backend="spmd", tracer=tracer,
                   metrics_registry=MetricsRegistry())
    s, p, o = (np.asarray(a) for a in (g.s, g.p, g.o))
    prop = int(p[0])
    objs = np.unique(o[p == prop])[:3]
    batch = [QueryGraph.make([(-1, -2, prop), (-2, int(c), prop)])
             for c in objs]
    sess.execute_many(batch, batch_size=len(batch))
    first, *rest = tracer.store.spans()[-len(batch):]
    tiers = len(first.attrs["capacity_tiers"])
    assert _children(first) == ["match", "fetch"] * tiers + ["dedup",
                                                            "filter"]
    assert first.children[-2].attrs["reused"] is False
    assert rest and all(_children(r) == ["dedup", "filter"] for r in rest)
    assert all(r.children[0].attrs["reused"] is True for r in rest)


def test_spmd_disabled_tracer_opens_no_child_span(spmd_setup,
                                                  monkeypatch):
    g, plan = spmd_setup
    tracer = Tracer(enabled=False)
    opened = []
    monkeypatch.setattr(tracer, "span",
                        lambda name, **kw: opened.append(name))
    sess = Session(plan, backend="spmd", tracer=tracer,
                   metrics_registry=MetricsRegistry())
    sess.execute_many(_shape_queries(g, per_shape=1))
    assert opened == [] and len(tracer.store) == 0


def test_matcher_ops_carry_step_and_final_gather_scopes(spmd_setup):
    """The named scopes are HLO metadata: every join step's operations
    carry ``step<j>``, the closing all_gathers ``final_gather``."""
    g, plan = spmd_setup
    sess = Session(plan, backend="spmd",
                   metrics_registry=MetricsRegistry())
    eng = sess.engine
    q = _shape_queries(g, per_shape=1)[1].normalize()
    fn = eng._matcher(q, 64)
    text = fn.lower(*S._matcher_args(eng.store)).as_text(
        debug_info=True)
    for j in range(q.num_edges):
        assert f"step{j}/" in text
    assert "final_gather/" in text


def test_spmd_dedup_takes_the_packed_path(spmd_setup):
    """Every query of a 2- and 3-variable batch on a 1-device mesh
    dedups on the packed int64 key: ``dedup_packed`` rises by the
    number of queries, ``dedup_fallback`` stays, every ``dedup`` span
    reads ``packed=True``, and the answers equal the host backend's."""
    from generators import answer_set
    from repro.launch.mesh import make_host_mesh
    g, plan = spmd_setup
    tracer = Tracer(enabled=True, capacity=64)
    sess = Session(plan, backend="spmd", tracer=tracer,
                   mesh=make_host_mesh(1),
                   metrics_registry=MetricsRegistry())
    host = Session(plan, backend="local",
                   metrics_registry=MetricsRegistry())
    p, o = np.asarray(g.p), np.asarray(g.o)
    prop = int(p[0])
    batch = [QueryGraph.make([(-1, -2, prop), (-2, int(c), prop)])
             for c in np.unique(o[p == prop])[:3]]
    batch.append(QueryGraph.make([(-1, -2, prop)]))
    before = dict(sess.stats().extra)
    got = sess.execute_many(batch, batch_size=len(batch))
    extra = sess.stats().extra
    assert extra["dedup_packed"] - before["dedup_packed"] == len(batch)
    assert extra["dedup_fallback"] == before["dedup_fallback"]
    roots = tracer.store.spans()[-len(batch):]
    dedups = [c for r in roots for c in r.children if c.name == "dedup"]
    assert len(dedups) == len(batch) and any(r.num_rows for r in got)
    assert all(d.attrs["packed"] is True for d in dedups)
    for q, r in zip(batch, got):
        want = host.execute(q)
        assert r.num_rows == want.num_rows
        assert answer_set(r) == answer_set(want)


def test_traced_expand_direct_counts_expansion_steps(spmd_setup):
    """``traced_expand_direct`` equals the expansion steps (join steps
    that bind a new variable, not pair probes) of every matcher the
    engine compiled: on a 1-device mesh each traces ``_expand_fixed``
    once."""
    from repro.core.query import _connected_edge_order
    from repro.launch.mesh import make_host_mesh
    g, _plan = spmd_setup
    sess = Session(_plan, backend="spmd", mesh=make_host_mesh(1),
                   metrics_registry=MetricsRegistry())
    for q in _shape_queries(g, per_shape=1):
        sess.execute(q)
    want = 0
    for edges, _cap, _gen in sess.engine._join_paths:
        bound = set()
        for step, ei in enumerate(_connected_edge_order(QueryGraph(edges))):
            e = edges[ei]
            known = [v >= 0 or v in bound for v in (e.src, e.dst)]
            want += step > 0 and not all(known)
            bound |= {v for v in (e.src, e.dst) if v < 0}
    assert want > 0
    assert sess.stats().extra["traced_expand_direct"] == want
