"""Differential SPMD exactness harness (the tentpole acceptance test).

tests/conftest.py forces a 4-device host mesh (unless XLA_FLAGS is
pinned), so these tests exercise the cross-device broadcast joins for
real: a seeded random graph + a workload generator sweeping star /
chain / cycle shapes (with and without constants), asserting that the
``spmd`` backend's *answer sets* -- full binding tuples, not just row
counts -- equal the exact host reference for every strategy in the
``StrategyRegistry``.  Plus: overflow auto-retry regressions (recovery,
stats, and the retry-cap RuntimeError) and the all-empty-site padding
regression.
"""
import warnings

import numpy as np
import pytest

from generators import (SEED, answer_set as _answer_set,
                        chain_query as _chain, random_graph,
                        shape_workload)
from repro.core import PartitionConfig, STRATEGIES, Session, build_plan
from repro.core.matching import match_pattern
from repro.core.query import QueryGraph
from repro.core.workload import Workload


@pytest.fixture(scope="module")
def rgraph():
    return random_graph(SEED)


@pytest.fixture(scope="module")
def rqueries(rgraph):
    return shape_workload(rgraph, SEED, n_props=rgraph.num_properties)


# ----------------------------------------------------------------------
# Differential harness: spmd vs exact host backend, every strategy
# ----------------------------------------------------------------------

@pytest.mark.parametrize("comm_plan,routing",
                         [(True, True), (True, False), (False, True)],
                         ids=["planned-routed", "planned-unrouted",
                              "naive"])
@pytest.mark.parametrize("kind", sorted(STRATEGIES.names()))
def test_spmd_answer_sets_match_host_backend(rgraph, rqueries, kind,
                                             comm_plan, routing):
    """The differential harness, with the size-aware communication
    planner both enabled (ship-smaller-side + shard-complete skip) and
    disabled (gather binding tables before every join step), and the
    replica router both on (mask non-resident sites, rendezvous seed
    balancing) and off (whole-mesh execution): answer sets must equal
    the exact host backend's every way, for every registered strategy.
    (Routing without the comm plan is inert, so the naive arm only
    needs one routing setting.)"""
    plan = build_plan(rgraph, Workload(list(rqueries)),
                      PartitionConfig(kind=kind, num_sites=4))
    host_backend = "local" if plan.frag is not None else "baseline"
    host = Session(plan, backend=host_backend)
    spmd = Session(plan, backend="spmd", spmd_comm_plan=comm_plan,
                   spmd_routing=routing)
    for q in rqueries:
        rh, rs = host.execute(q), spmd.execute(q)
        vh, sh = _answer_set(rh)
        vs, ss = _answer_set(rs)
        assert vh == vs, f"{kind}: variable sets diverged on {q.edges}"
        assert sh == ss, (f"{kind}: spmd answer set != {host_backend} "
                          f"on {q.edges} (comm_plan={comm_plan}, "
                          f"routing={routing})")


@pytest.mark.parametrize("mesh_n", [1, 2, 4])
def test_routed_unrouted_host_triple_parity(rgraph, rqueries, mesh_n):
    """Routed vs unrouted vs host at 1/2/4 devices: the three answer
    sets must be identical per query, and -- when neither SPMD arm had
    to climb the capacity ladder -- the routed ledger must not exceed
    the whole-mesh ledger (route masking shrinks the peer factor of
    every shard-incomplete step's collective and of the final gather;
    shard-complete steps ship nothing either way)."""
    from repro.launch.mesh import make_host_mesh
    plan = build_plan(rgraph, Workload(list(rqueries)),
                      PartitionConfig(kind="vertical", num_sites=4))
    mesh = make_host_mesh(mesh_n)
    host = Session(plan, backend="local")
    routed = Session(plan, backend="spmd", mesh=mesh)
    unrouted = Session(plan, backend="spmd", mesh=mesh,
                       spmd_routing=False)
    for q in rqueries:
        ah = _answer_set(host.execute(q))
        ar = _answer_set(routed.execute(q))
        au = _answer_set(unrouted.execute(q))
        assert ar == au == ah, f"mesh={mesh_n}: diverged on {q.edges}"
    rst, ust = routed.stats(), unrouted.stats()
    if mesh_n > 1:
        assert rst.extra["routed_queries"] > 0
    if (rst.extra["capacity_retries"] == 0
            and ust.extra["capacity_retries"] == 0):
        assert rst.comm_bytes <= ust.comm_bytes, (
            f"mesh={mesh_n}: routed ledger {rst.comm_bytes} > "
            f"whole-mesh {ust.comm_bytes}")


def test_spmd_matches_whole_graph_matcher(rgraph, rqueries):
    """Belt and braces: spmd against direct matching on the undivided
    graph (independent of any host engine)."""
    plan = build_plan(rgraph, Workload(list(rqueries)),
                      PartitionConfig(kind="shape", num_sites=4))
    spmd = Session(plan, backend="spmd")
    for q in rqueries:
        want = match_pattern(rgraph, q)
        got = spmd.execute(q)
        assert got.num_rows == want.num_rows, f"diverged on {q.edges}"


def test_multi_device_construction_is_warning_free(rgraph, rqueries):
    """The 'matches per shard only / results dropped' UserWarning is
    gone: multi-device meshes are exact now."""
    plan = build_plan(rgraph, Workload(list(rqueries)),
                      PartitionConfig(kind="shape", num_sites=4))
    with warnings.catch_warnings():
        warnings.simplefilter("error", UserWarning)
        sess = Session(plan, backend="spmd")
    assert sess.num_sites == 4


def test_isomorphic_patterns_do_not_share_matchers(rgraph):
    """Regression: ``QueryGraph`` equality is canonical-isomorphism, so
    a matcher cache keyed by the pattern object collides isomorphic
    patterns whose binding-column orders differ -- the second query came
    back with swapped binding columns.  The cache must key on exact edge
    structure."""
    from repro.core.spmd import SpmdEngine
    sites = [np.arange(rgraph.num_edges)[i::4] for i in range(4)]
    eng = SpmdEngine(rgraph, sites)
    q1 = QueryGraph.make([(-1, -2, 0), (-1, -3, 1)])
    q2 = QueryGraph.make([(-1, -2, 1), (-1, -3, 0)])   # isomorphic to q1
    assert q1 == q2                     # same canonical code ...
    for q in (q1, q2):                  # ... but answers must not mix
        want = match_pattern(rgraph, q)
        got = eng.execute(q)
        vars_ = sorted(want.columns)
        wset = {tuple(int(want.columns[v][i]) for v in vars_)
                for i in range(want.num_rows)}
        _, gset = _answer_set(got)
        assert gset == wset, f"columns swapped for {q.edges}"


def test_pallas_probe_path_is_exact_end_to_end(rgraph, monkeypatch):
    """REPRO_SPMD_PALLAS=1 swaps the probe oracles for the blocked
    Pallas kernels (interpret mode on CPU) inside the traced match loop;
    the cycle query exercises both join_count and pair_semijoin."""
    from repro.core.spmd import SpmdEngine
    q = QueryGraph.make([(-1, -2, 0), (-2, -3, 1), (-3, -1, 2)])
    want = match_pattern(rgraph, q).num_rows
    sites = [np.arange(rgraph.num_edges)[i::4] for i in range(4)]
    monkeypatch.setenv("REPRO_SPMD_PALLAS", "1")
    eng = SpmdEngine(rgraph, sites, capacity=1024)
    assert eng.execute(q).num_rows == want


@pytest.mark.parametrize("pallas", ["0", "1"], ids=["oracle", "kernel"])
def test_join_kernel_toggle_answer_sets_identical(rgraph, rqueries,
                                                  monkeypatch, pallas):
    """The blocked ``join_count`` and ``pair_semijoin`` probe kernels
    (REPRO_SPMD_PALLAS=1, interpret mode on CPU) produce answer sets
    identical to their jnp oracles (=0) -- end to end through the
    engine, star/chain/cycle shapes, forcing at least one overflow
    retry tier with a small starting capacity."""
    monkeypatch.setenv("REPRO_SPMD_PALLAS", pallas)
    plan = build_plan(rgraph, Workload(list(rqueries)),
                      PartitionConfig(kind="vertical", num_sites=4))
    sess = Session(plan, backend="spmd", spmd_capacity=64)
    for q in rqueries[:6]:
        want = _answer_set(match_pattern(rgraph, q))
        assert _answer_set(sess.execute(q)) == want, \
            f"pallas={pallas} diverged on {q.edges}"


# ----------------------------------------------------------------------
# Overflow auto-retry
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_cap_plan(rgraph, rqueries):
    return build_plan(rgraph, Workload(list(rqueries)),
                      PartitionConfig(kind="shape", num_sites=4))


def test_overflow_auto_retry_recovers_exact_answer(rgraph, tiny_cap_plan):
    q = QueryGraph.make([(-1, -2, 0)])    # every prop-0 edge matches
    want = match_pattern(rgraph, q).num_rows
    assert want > 8                        # default capacity must overflow
    sess = Session(tiny_cap_plan, backend="spmd", spmd_capacity=8)
    r = sess.execute(q)
    assert r.num_rows == want
    st = sess.stats()
    assert st.extra["capacity_retries"] > 0
    assert st.extra["overflow_events"] > 0


def test_overflow_auto_retry_multi_edge(rgraph, tiny_cap_plan):
    rng = np.random.default_rng(7)
    q = _chain(rng, 2)
    want = match_pattern(rgraph, q).num_rows
    sess = Session(tiny_cap_plan, backend="spmd", spmd_capacity=8)
    assert sess.execute(q).num_rows == want


def test_overflow_retry_count_is_logarithmic(rgraph, tiny_cap_plan):
    """Geometric doubling: at most log2(max_capacity / capacity)
    retries, one compile per capacity tier."""
    q = QueryGraph.make([(-1, -2, 0)])
    sess = Session(tiny_cap_plan, backend="spmd", spmd_capacity=8,
                   spmd_max_capacity=1 << 14)
    sess.execute(q)
    st = sess.stats()
    assert st.extra["capacity_retries"] <= np.log2((1 << 14) / 8)
    assert st.extra["compiled_shapes"] == st.extra["capacity_retries"] + 1
    # tier cache + capacity hint are warm: re-running the query compiles
    # nothing new and starts straight at the working tier (no re-climb)
    sess.execute(q)
    st2 = sess.stats()
    assert st2.extra["compiled_shapes"] == st.extra["compiled_shapes"]
    assert st2.extra["capacity_retries"] == st.extra["capacity_retries"]


def test_overflow_retry_skips_tiers_the_overflow_rules_out(rgraph,
                                                          tiny_cap_plan):
    """A one-edge pattern overflows at its seed step, whose overflow
    count is exact: one retry, straight to the first tier that holds the
    device's rows, where doubling alone would climb through 4 and 8."""
    q = QueryGraph.make([(-1, -2, 0)])
    want = match_pattern(rgraph, q).num_rows
    assert want > 4 * 8         # some device holds 16 rows or more
    sess = Session(tiny_cap_plan, backend="spmd", spmd_capacity=2,
                   spmd_max_capacity=1 << 14)
    assert sess.execute(q).num_rows == want
    st = sess.stats()
    assert st.extra["capacity_retries"] == 1
    assert st.extra["compiled_shapes"] == 2


def test_overflow_at_retry_cap_raises_instead_of_truncating(rgraph,
                                                            tiny_cap_plan):
    q = QueryGraph.make([(-1, -2, 0)])
    # >8 prop-0 matches overall, so SOME device's 8-row table overflows
    # (pigeonhole) and the exhausted retry budget must raise, never
    # return a truncated answer.
    assert match_pattern(rgraph, q).num_rows > 8 * 4
    sess = Session(tiny_cap_plan, backend="spmd", spmd_capacity=8,
                   spmd_max_capacity=8)
    with pytest.raises(RuntimeError, match="overflow"):
        sess.execute(q)


# ----------------------------------------------------------------------
# Empty-site padding regression
# ----------------------------------------------------------------------

def test_sitestore_pads_empty_sites_to_pad_multiple(rgraph):
    from repro.core.spmd import SiteStore
    store = SiteStore.build(rgraph, [np.zeros(0, np.int64)] * 4)
    assert store.e_max == 512            # 0 edges still pad to a full block
    for keys in (store.csr_sub_s, store.csr_obj_o):   # all padding
        assert keys.shape == (4, store.e_max + store.csr_pad)
        assert (np.asarray(keys) == np.iinfo(np.int32).max).all()
    for pay in (store.csr_sub_o, store.csr_obj_s):
        assert (np.asarray(pay) == -1).all()
    assert not np.asarray(store.owned).any()
    assert not np.asarray(store.csr_offs).any()


def test_all_empty_site_plan_executes_cleanly(rgraph):
    from repro.core.spmd import SpmdEngine
    eng = SpmdEngine(rgraph, [np.zeros(0, np.int64)] * 4)
    r = eng.execute(QueryGraph.make([(-1, -2, 0), (-2, -3, 1)]))
    assert r.num_rows == 0
    for col in r.bindings.values():
        assert col.shape == (0,)
    assert eng.stats().extra["overflow_events"] == 0


# ----------------------------------------------------------------------
# Shape-grouped batch dispatch (SpmdEngine._execute_batch)
# ----------------------------------------------------------------------

@pytest.mark.slow
def test_execute_batch_groups_shapes_exactly(rgraph, rqueries):
    """`execute_many` groups same-normalized-shape queries onto one
    device run (later members reuse the binding tables and apply only
    their host-side constant filters): answers must be identical to
    sequential `execute`, `batch_shape_hits` must count exactly the
    reused members, and the reused members must not re-ledger the
    first member's collectives."""
    plan = build_plan(rgraph, Workload(list(rqueries)),
                      PartitionConfig(kind="vertical", num_sites=4))
    queries = list(rqueries) * 3          # guaranteed same-shape groups
    seq = Session(plan, backend="spmd")
    bat = Session(plan, backend="spmd")
    direct = [seq.execute(q) for q in queries]
    batched = bat.execute_many(queries, batch_size=len(queries))
    assert len(batched) == len(queries)   # input order preserved
    for q, a, b in zip(queries, direct, batched):
        va, sa = _answer_set(a)
        vb, sb = _answer_set(b)
        assert va == vb, f"variable sets diverged on {q.edges}"
        assert sa == sb, f"batched answer set diverged on {q.edges}"
    n_shapes = len({q.normalize().edges for q in queries})
    hits = bat.stats().extra["batch_shape_hits"]
    assert hits == len(queries) - n_shapes
    # reuse members ship nothing: the grouped ledger can only be lower
    assert bat.stats().comm_bytes <= seq.stats().comm_bytes
    # the shared run never leaks past the batch
    assert bat.engine._shared_run is None
    assert bat.engine._shared_run_key is None


@pytest.mark.slow
def test_execute_batch_chunks_do_not_share_across_batches(rgraph,
                                                          rqueries):
    """Grouping happens within one `_execute_batch` chunk only: a
    batch_size smaller than the group still answers exactly."""
    plan = build_plan(rgraph, Workload(list(rqueries)),
                      PartitionConfig(kind="vertical", num_sites=4))
    sess = Session(plan, backend="spmd")
    queries = list(rqueries) * 2
    got = sess.execute_many(queries, batch_size=3)
    from repro.core.matching import match_pattern as _mp
    for q, r in zip(queries, got):
        assert r.num_rows == _mp(rgraph, q).num_rows, \
            f"diverged on {q.edges}"


def test_execute_batch_handles_zero_edge_group(rgraph, rqueries):
    """Regression: two zero-edge queries normalize to the EMPTY shape
    key, and the shape-sharing check used to read `key[0]` -- an
    IndexError that failed the whole `execute_many` call."""
    plan = build_plan(rgraph, Workload(list(rqueries)),
                      PartitionConfig(kind="vertical", num_sites=4))
    sess = Session(plan, backend="spmd")
    q0 = QueryGraph.make([])
    got = sess.execute_many([q0, q0, rqueries[0]], batch_size=3)
    assert [r.num_rows for r in got[:2]] == [0, 0]
    assert got[2].num_rows == match_pattern(rgraph, rqueries[0]).num_rows
