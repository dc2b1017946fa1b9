"""Query-graph layer: canonical DFS codes, normalization, subgraph iso."""
import itertools

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from seeded_fallback import given, settings, st

from repro.core.query import (QueryGraph, all_embeddings, find_embedding,
                              is_subgraph_of, min_dfs_code)


def V(i):
    return -(i + 1)


def test_normalize_replaces_constants():
    q = QueryGraph.make([(5, V(0), 2), (V(0), 9, 3)])
    n = q.normalize()
    assert all(v < 0 for v in n.vertices())
    assert n.properties() == [2, 3]


def test_constant_bindings_align_with_normalize():
    q = QueryGraph.make([(5, V(0), 2), (V(0), 9, 3)])
    binds = q.constant_bindings()
    assert set(binds.values()) == {5, 9}


def test_canonical_code_distinguishes_structure():
    star = QueryGraph.make([(V(0), V(1), 1), (V(0), V(2), 2)])
    path = QueryGraph.make([(V(0), V(1), 1), (V(1), V(2), 2)])
    assert star.canonical_code() != path.canonical_code()


def test_canonical_code_direction_sensitivity():
    a = QueryGraph.make([(V(0), V(1), 1)])
    b = QueryGraph.make([(V(1), V(0), 1)])
    # single edge with variables: same canonical form regardless of naming
    assert a.canonical_code() == b.canonical_code()
    fwd = QueryGraph.make([(V(0), V(1), 1), (V(1), V(2), 1)])
    fan = QueryGraph.make([(V(0), V(1), 1), (V(2), V(1), 1)])
    assert fwd.canonical_code() != fan.canonical_code()


@st.composite
def small_graphs(draw):
    n_edges = draw(st.integers(1, 5))
    n_vars = draw(st.integers(1, 4))
    edges = []
    used = []
    for i in range(n_edges):
        # connect: anchor every edge i at a vertex of edges 0..i-1
        # (min_dfs_code requires a connected query graph)
        if used:
            s = used[draw(st.integers(0, len(used) - 1))]
        else:
            s = V(draw(st.integers(0, n_vars - 1)))
        d = V(draw(st.integers(0, n_vars - 1)))
        p = draw(st.integers(0, 3))
        edges.append((s, d, p))
        for v in (s, d):
            if v not in used:
                used.append(v)
    return QueryGraph.make(edges)


@settings(max_examples=60, deadline=None)
@given(small_graphs(), st.permutations(list(range(8))))
def test_canonical_code_invariant_under_relabeling(g, perm):
    """Property: min DFS code is invariant under variable renaming."""
    mapping = {V(i): V(perm[i]) for i in range(8)}
    g2 = QueryGraph.make([(mapping[e.src], mapping[e.dst], e.prop)
                          for e in g.edges])
    assert min_dfs_code(g) == min_dfs_code(g2)


@settings(max_examples=40, deadline=None)
@given(small_graphs())
def test_self_subgraph(g):
    assert is_subgraph_of(g, g)


def test_subgraph_iso_embedding_is_consistent():
    pat = QueryGraph.make([(V(0), V(1), 1)])
    q = QueryGraph.make([(V(0), V(1), 1), (V(1), V(2), 2)])
    emb = find_embedding(pat, q)
    assert emb is not None
    assert emb[V(0)] == V(0) and emb[V(1)] == V(1)
    assert len(all_embeddings(pat, q)) == 1


def test_subgraph_iso_respects_labels_and_direction():
    pat = QueryGraph.make([(V(0), V(1), 7)])
    q = QueryGraph.make([(V(0), V(1), 1)])
    assert not is_subgraph_of(pat, q)
    pat2 = QueryGraph.make([(V(0), V(1), 1), (V(1), V(0), 1)])
    q2 = QueryGraph.make([(V(0), V(1), 1)])
    assert not is_subgraph_of(pat2, q2)


def test_embeddings_injective_on_edges():
    # pattern with two identical-label edges cannot map onto one edge
    pat = QueryGraph.make([(V(0), V(1), 1), (V(0), V(2), 1)])
    q = QueryGraph.make([(V(0), V(1), 1)])
    assert not is_subgraph_of(pat, q)


# LUBM Queries 2 and 9: a triangle with a ``type`` edge to a class at each
# corner (properties and class ids as the benchmark's LUBM graph numbers
# them).  The minimal-extension search alone dead-ends on this form.
TYPE = 0
LUBM_TRIANGLES = {
    "Q2": [(V(0), 7, TYPE), (V(1), 0, TYPE), (V(2), 1, TYPE),
           (V(0), V(2), 7), (V(2), V(1), 5), (V(0), V(1), 14)],
    "Q9": [(V(0), 17, TYPE), (V(1), 14, TYPE), (V(2), 8, TYPE),
           (V(0), V(1), 12), (V(1), V(2), 9), (V(0), V(2), 10)],
}


@pytest.mark.parametrize("normalized", [False, True],
                         ids=["raw", "normalized"])
@pytest.mark.parametrize("name", sorted(LUBM_TRIANGLES))
def test_lubm_triangle_has_one_code_under_every_order(name, normalized):
    """One code for the query under all 720 edge orders, each with its
    variables renamed at random."""
    edges = LUBM_TRIANGLES[name]
    rng = np.random.default_rng(15)
    codes = set()
    for perm in itertools.permutations(range(len(edges))):
        names = {V(i): V(int(j)) for i, j in
                 enumerate(rng.choice(10, size=3, replace=False))}
        g = QueryGraph.make([(names.get(s, s), names.get(d, d), p)
                             for s, d, p in (edges[i] for i in perm)])
        codes.add(min_dfs_code(g.normalize() if normalized else g))
    assert len(codes) == 1
    # the bare triangle and the triangle with fewer pendants differ
    bare = QueryGraph.make(edges[3:])
    assert min_dfs_code(bare) not in codes


def _cyclic_with_pendants(rng):
    """A connected all-variable pattern: a cycle of 3 or 4 edges, a
    pendant edge at some corners, sometimes a chord; two properties, so
    that unrelated draws are often isomorphic."""
    k = int(rng.integers(3, 5))
    edges = []
    for i in range(k):
        a, b = V(i), V((i + 1) % k)
        edges.append((a, b) if rng.random() < 0.5 else (b, a))
    n = k
    for i in range(k):
        if rng.random() < 0.6:
            edges.append((V(i), V(n)) if rng.random() < 0.5
                         else (V(n), V(i)))
            n += 1
    if k == 4 and rng.random() < 0.3:
        edges.append((V(0), V(2)))
    return [(a, b, int(rng.integers(0, 2))) for a, b in edges]


def _shuffled(edges, rng):
    """The same pattern with its edges reordered and variables renamed."""
    names = {V(i): V(int(j)) for i, j in
             enumerate(rng.permutation(12))}
    order = rng.permutation(len(edges))
    return QueryGraph.make([(names[edges[i][0]], names[edges[i][1]],
                             edges[i][2]) for i in order])


def _isomorphic(a, b):
    return (a.num_edges == b.num_edges
            and find_embedding(a, b) is not None
            and find_embedding(b, a) is not None)


@pytest.mark.parametrize("seed", range(3))
def test_codes_equal_iff_isomorphic_on_cyclic_patterns(seed):
    """Equal codes <=> isomorphic (embeddings both ways, equal sizes),
    over every pair of seeded cyclic patterns with pendant edges and
    their shuffled copies."""
    rng = np.random.default_rng(seed)
    graphs = []
    for _ in range(24):
        edges = _cyclic_with_pendants(rng)
        graphs += [QueryGraph.make(edges), _shuffled(edges, rng)]
    codes = [min_dfs_code(g) for g in graphs]
    same_pairs = 0
    for i, j in itertools.combinations(range(len(graphs)), 2):
        iso = _isomorphic(graphs[i], graphs[j])
        assert (codes[i] == codes[j]) == iso, (graphs[i], graphs[j])
        same_pairs += iso
    assert same_pairs >= 24       # at least each shuffled copy
