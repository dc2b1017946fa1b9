"""Matching engine vs brute-force homomorphism enumeration (property)."""
import itertools

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from seeded_fallback import given, settings, st

from repro.core.graph import RDFGraph, example_graph
from repro.core.matching import (count_matches, match_edge_ids, match_pattern)
from repro.core.query import QueryGraph


def V(i):
    return -(i + 1)


def brute_force_matches(graph: RDFGraph, pattern: QueryGraph):
    """Enumerate all homomorphisms by trying every variable assignment."""
    variables = sorted({v for v in pattern.vertices() if v < 0}, reverse=True)
    triples = set(zip(graph.s.tolist(), graph.p.tolist(), graph.o.tolist()))
    out = set()
    for combo in itertools.product(range(graph.num_vertices),
                                   repeat=len(variables)):
        asg = dict(zip(variables, combo))
        ok = True
        for e in pattern.edges:
            s = asg.get(e.src, e.src)
            d = asg.get(e.dst, e.dst)
            if (s, e.prop, d) not in triples:
                ok = False
                break
        if ok:
            out.add(combo)
    return out


@st.composite
def tiny_graph_and_pattern(draw):
    nv = draw(st.integers(4, 9))
    np_ = draw(st.integers(1, 3))
    ne = draw(st.integers(4, 14))
    rng = np.random.default_rng(draw(st.integers(0, 10_000)))
    s = rng.integers(0, nv, ne).astype(np.int32)
    p = rng.integers(0, np_, ne).astype(np.int32)
    o = rng.integers(0, nv, ne).astype(np.int32)
    g = RDFGraph(s, p, o, nv, np_)
    # connected pattern with <=3 vars
    n_pe = draw(st.integers(1, 3))
    edges = [(V(0), V(1), int(rng.integers(0, np_)))]
    for i in range(1, n_pe):
        a = draw(st.integers(0, min(i, 1)))
        edges.append((V(a), V(i + 1), int(rng.integers(0, np_))))
    return g, QueryGraph.make(edges)


@settings(max_examples=40, deadline=None)
@given(tiny_graph_and_pattern())
def test_matcher_equals_brute_force(gp):
    graph, pattern = gp
    res = match_pattern(graph, pattern)
    variables = sorted({v for v in pattern.vertices() if v < 0}, reverse=True)
    got = {tuple(int(res.columns[v][i]) for v in variables)
           for i in range(res.num_rows)}
    want = brute_force_matches(graph, pattern)
    assert got == want


def test_constant_patterns(watdiv_small):
    g = watdiv_small
    # take an actual edge and query it with its constant endpoints
    s0, p0, o0 = int(g.s[0]), int(g.p[0]), int(g.o[0])
    assert count_matches(g, QueryGraph.make([(s0, V(0), p0)])) >= 1
    assert count_matches(g, QueryGraph.make([(s0, o0, p0)])) >= 1
    assert count_matches(g, QueryGraph.make([(V(0), o0, p0)])) >= 1


def test_match_edge_ids_subset_of_graph(watdiv_small):
    g = watdiv_small
    pat = QueryGraph.make([(V(0), V(1), 1), (V(0), V(2), 2)])
    eids = match_edge_ids(g, pat)
    assert len(eids) == len(np.unique(eids))
    assert (eids >= 0).all() and (eids < g.num_edges).all()
    # every returned edge has one of the pattern's properties
    assert set(np.unique(g.p[eids])) <= {1, 2}


def test_empty_result():
    g = example_graph()
    # property that never connects these classes
    pat = QueryGraph.make([(V(0), V(1), 6), (V(1), V(2), 6)])
    res = match_pattern(g, pat)
    assert res.num_rows == 0


def test_truncation_flag():
    g = example_graph()
    pat = QueryGraph.make([(V(0), V(1), 0)])  # 'type' edges
    res = match_pattern(g, pat, max_rows=3)
    assert res.truncated and res.num_rows == 3


def test_hub_join_truncates_before_materializing():
    """A join through one hub vertex (n in-edges x n out-edges) would
    expand to n^2 rows; with ``max_rows`` the expansion builds only the
    first max_rows of them -- the same rows a full expansion cut to
    ``max_rows`` keeps -- and flags the truncation."""
    n, hub = 400, 0
    s = np.concatenate([np.arange(1, n + 1), np.full(n, hub)])
    p = np.concatenate([np.zeros(n), np.ones(n)])
    o = np.concatenate([np.full(n, hub), np.arange(n + 1, 2 * n + 1)])
    g = RDFGraph(s.astype(np.int32), p.astype(np.int32), o.astype(np.int32),
                 2 * n + 1, 2)
    pat = QueryGraph.make([(V(0), V(1), 0), (V(1), V(2), 1)])
    full = match_pattern(g, pat)
    assert full.num_rows == n * n and not full.truncated
    cut = match_pattern(g, pat, max_rows=1000)
    assert cut.truncated and cut.num_rows == 1000
    np.testing.assert_array_equal(cut.rows(), full.rows()[:1000])


@pytest.mark.parametrize("far", ["object", "subject"])
def test_hub_join_to_a_constant_stays_exact(far):
    """The same hub, but one end of the two-edge path is a constant: the
    answer (n rows) fits under ``max_rows`` though the unconstrained hub
    join (n^2 rows) does not, and it comes back exact and untruncated --
    a constant end is a filter on the rows kept, never a cut before it."""
    n, hub = 400, 0
    s = np.concatenate([np.arange(1, n + 1), np.full(n, hub)])
    p = np.concatenate([np.zeros(n), np.ones(n)])
    o = np.concatenate([np.full(n, hub), np.arange(n + 1, 2 * n + 1)])
    g = RDFGraph(s.astype(np.int32), p.astype(np.int32), o.astype(np.int32),
                 2 * n + 1, 2)
    if far == "object":     # ?a -0-> ?h -1-> y_7
        pat = QueryGraph.make([(V(0), V(1), 0), (V(1), n + 7, 1)])
        want = {(hub, a) for a in range(1, n + 1)}   # (?h, ?a)
    else:                   # x_7 -0-> ?h -1-> ?b
        pat = QueryGraph.make([(7, V(1), 0), (V(1), V(2), 1)])
        want = {(b, hub) for b in range(n + 1, 2 * n + 1)}   # (?b, ?h)
    res = match_pattern(g, pat, max_rows=1000)
    assert not res.truncated and res.num_rows == n
    assert {tuple(r) for r in res.rows().tolist()} == want


# ----------------------------------------------------------------------
# Sentinel-safety guard: the id-space bound shared with the SPMD /
# kernel layers (repro.constants).  Ids at the 2^21-1 bound stay far
# below the INT32_SENTINEL padding value and the int32 hash mixing, so
# they must construct and match; anything past the bound (or negative)
# must be rejected at RDFGraph construction, not corrupt a join later.
# ----------------------------------------------------------------------

def test_ids_just_under_bound_construct_and_match():
    from repro.constants import MAX_VERTEX_ID
    hi = MAX_VERTEX_ID                      # == 2**21 - 1
    s = np.array([hi - 1, hi], np.int32)
    p = np.zeros(2, np.int32)
    o = np.array([hi, hi - 1], np.int32)
    g = RDFGraph(s, p, o, hi + 1, 1)
    res = match_pattern(g, QueryGraph.make([(V(0), V(1), 0)]))
    got = {(int(res.columns[V(0)][i]), int(res.columns[V(1)][i]))
           for i in range(res.num_rows)}
    assert got == {(hi - 1, hi), (hi, hi - 1)}


@pytest.mark.parametrize("field", ["s", "o", "p"])
def test_ids_past_bound_raise_value_error(field):
    from repro.constants import MAX_PROPERTY_ID, MAX_VERTEX_ID
    cols = {"s": np.zeros(2, np.int32), "p": np.zeros(2, np.int32),
            "o": np.zeros(2, np.int32)}
    bound = MAX_PROPERTY_ID if field == "p" else MAX_VERTEX_ID
    cols[field] = np.array([0, bound + 1], np.int32)
    with pytest.raises(ValueError, match=field):
        RDFGraph(cols["s"], cols["p"], cols["o"], 4, 2)
    cols[field] = np.array([0, -1], np.int32)
    with pytest.raises(ValueError, match=field):
        RDFGraph(cols["s"], cols["p"], cols["o"], 4, 2)
