"""Cyclic patterns with pendant edges end to end: a workload holding
LUBM Query 2's and Query 9's form (a triangle with a ``type`` edge to a
class at each corner) is planned, and the SPMD engine answers it as the
whole-graph matcher does; the engine's ``match`` span says how many
join steps close a cycle.  A class on ``type``'s hub side stays in
the compiled pattern when the graph makes it a hub."""
import numpy as np
import pytest

from generators import answer_set
from repro.core import PartitionConfig, Session, build_plan
from repro.core.graph import RDFGraph
from repro.core.matching import match_pattern
from repro.core.query import QueryGraph
from repro.core import spmd
from repro.core.spmd import cycle_close_steps
from repro.core.workload import Workload
from repro.obs.trace import Tracer

#: vertices 0..3 are classes, property 0 is ``type``
CLASSES, TYPE, N_PROPS, N_VERTS = 4, 0, 4, 40


@pytest.fixture(scope="module")
def typed_graph():
    """Every entity typed with one or two classes, and dense random
    edges of properties 1..3, so that typed triangles exist."""
    rng = np.random.default_rng(13)
    ents = np.arange(CLASSES, N_VERTS)
    first = rng.integers(0, CLASSES, len(ents))
    second = rng.integers(0, CLASSES, len(ents))
    s = np.concatenate([ents, ents, rng.integers(CLASSES, N_VERTS, 600)])
    o = np.concatenate([first, second, rng.integers(CLASSES, N_VERTS, 600)])
    p = np.concatenate([np.full(2 * len(ents), TYPE),
                        rng.integers(1, N_PROPS, 600)])
    t = np.unique(np.stack([s, p, o], axis=1), axis=0)
    return RDFGraph(t[:, 0], t[:, 1], t[:, 2], N_VERTS, N_PROPS)


def triangle(classes, props):
    """LUBM Q2/Q9's form: ?X ?Y ?Z typed, and a triangle over them."""
    x, y, z = -1, -2, -3
    return QueryGraph.make([(x, classes[0], TYPE), (y, classes[1], TYPE),
                            (z, classes[2], TYPE), (x, z, props[0]),
                            (z, y, props[1]), (x, y, props[2])])


QUERIES = {
    "Q2-form": triangle((0, 1, 2), (1, 2, 3)),
    "Q9-form": triangle((3, 0, 1), (2, 1, 3)),
    "star": QueryGraph.make([(-1, 2, TYPE), (-1, -2, 1)]),
}


@pytest.fixture(scope="module")
def session(typed_graph):
    wl = Workload([QUERIES[n] for n in sorted(QUERIES) for _ in range(3)])
    plan = build_plan(typed_graph, wl,
                      PartitionConfig(kind="vertical", num_sites=4))
    tracer = Tracer(enabled=True, capacity=256)
    return Session(plan, backend="spmd", tracer=tracer), tracer


@pytest.mark.parametrize("name", sorted(QUERIES))
def test_spmd_answers_cyclic_patterns_like_match_pattern(
        typed_graph, session, name):
    sess, tracer = session
    q = QUERIES[name]
    want = match_pattern(typed_graph, q)
    got = sess.execute(q)
    assert answer_set(got) == answer_set(want)
    if name != "star":
        assert want.num_rows > 0        # the graph holds such triangles
    match = next(c for c in tracer.store.spans()[-1].children
                 if c.name == "match")
    norm = q.normalize()
    assert match.attrs["edges"] == norm.num_edges
    assert match.attrs["cycles"] == cycle_close_steps(norm) \
        == (0 if name == "star" else 1)


@pytest.mark.parametrize("edges, closes", [
    ([(-1, -2, 1)], 0),
    ([(-1, -2, 1), (-2, -3, 1), (-3, -1, 1)], 1),
    # a square with a chord: two steps close a cycle
    ([(-1, -2, 1), (-2, -3, 1), (-3, -4, 1), (-4, -1, 1), (-1, -3, 2)], 2),
    # a self-loop after the seed closes on its one bound vertex
    ([(-1, -2, 1), (-2, -2, 2)], 1),
    # a bound pair with a constant end probes alike but closes no cycle
    ([(-1, 5, 1), (5, 6, 2)], 0),
    ([(-1, -2, 1), (-2, 3, 0)], 0),
])
def test_cycle_close_steps(edges, closes):
    assert cycle_close_steps(QueryGraph.make(edges)) == closes


@pytest.fixture
def hub_session(typed_graph, monkeypatch):
    """A fresh engine that counts ``type``'s objects (about 15 edges a
    class in this graph) as a hub side, and no other side (at most about
    6 edges a value)."""
    monkeypatch.setattr(spmd, "HUB_EDGES_PER_VALUE", 10)
    wl = Workload([QUERIES[n] for n in sorted(QUERIES)])
    plan = build_plan(typed_graph, wl,
                      PartitionConfig(kind="vertical", num_sites=4))
    return Session(plan, backend="spmd")


def _with_entity(q, entity):
    """``q`` with one more edge, from its first variable to a constant
    entity: a stripped constant beside the kept classes."""
    e0 = q.edges[3]
    return QueryGraph.make([(e.src, e.dst, e.prop) for e in q.edges]
                           + [(e0.src, entity, e0.prop)])


@pytest.mark.parametrize("name", sorted(QUERIES))
def test_hub_constants_are_matched_on_the_device(typed_graph, hub_session,
                                                 name):
    """The classes stay in the compiled pattern, the entity constant is
    stripped and filtered on the host, and answers equal match_pattern."""
    eng = hub_session.engine
    q = QUERIES[name]
    qs = [q, _with_entity(q, CLASSES + 3)] if name != "star" else [q]
    for query in qs:
        norm, nmap = eng._shape(query)
        kept = {v for v, nv in nmap.items() if v >= 0 and v == nv}
        assert kept == {e.dst for e in query.edges if e.prop == TYPE}
        assert set(norm.constants()) == kept
        want = match_pattern(typed_graph, query)
        assert answer_set(eng.execute(query)) == answer_set(want)


def test_hub_constants_split_shape_groups(typed_graph, hub_session):
    """Queries that differ in a kept class run as two compiled shapes;
    queries that differ only in a stripped constant share one run."""
    eng = hub_session.engine
    by_class = [QueryGraph.make([(-1, c, TYPE), (-1, -2, 1)])
                for c in (1, 2)]
    by_entity = [QueryGraph.make([(-1, 1, TYPE), (-1, v, 1)])
                 for v in (CLASSES + 1, CLASSES + 2)]
    assert len({eng._shape(q)[0].edges for q in by_class}) == 2
    assert len({eng._shape(q)[0].edges for q in by_entity}) == 1
    before = eng.stats().extra["batch_shape_hits"]
    got = eng.execute_many(by_class + by_entity,
                           batch_size=len(by_class + by_entity))
    # the first entity query reuses the class-1 star's run, so does the
    # second: the class-2 star alone runs apart
    assert eng.stats().extra["batch_shape_hits"] - before == 2
    for q, r in zip(by_class + by_entity, got):
        assert answer_set(r) == answer_set(match_pattern(typed_graph, q))
