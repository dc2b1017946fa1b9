"""The plain reference against a hand-built graph with known answers,
and against brute force on a random one."""
import itertools

import numpy as np
import pytest

from harness import check, reference

F, L = 0, 1          # properties: follows, likes
#            a  b  c  d are people 0..3, p q are products 10, 11
TRIPLES = [(0, F, 1), (0, F, 2), (1, F, 2), (2, F, 0), (3, F, 3),
           (0, L, 10), (1, L, 10), (2, L, 11), (0, L, 11), (3, L, 11)]


@pytest.fixture(scope="module")
def index():
    s, p, o = (np.array(c) for c in zip(*TRIPLES))
    return reference.TripleIndex(s, p, o)


def answer(index, edges):
    names, rows = check.expected(index, edges)
    return names, sorted(map(tuple, rows.tolist()))


@pytest.mark.parametrize("edges, want", [
    # chain2 bound at its subject: a follows ?x, ?x likes ?y
    ([(0, -2, F), (-2, -3, L)], ([-3, -2], [(10, 1), (11, 2)])),
    # star2 bound at its centre
    ([(0, -2, L), (0, -3, F)],
     ([-3, -2], [(1, 10), (1, 11), (2, 10), (2, 11)])),
    # triangle ?a follows ?b . ?a likes ?p . ?b likes ?p with ?b = 2
    ([(-1, 2, F), (-1, -3, L), (2, -3, L)], ([-3, -1], [(11, 0)])),
    # the triangle with ?b free: rows (?p, ?b, ?a)
    ([(-1, -2, F), (-1, -3, L), (-2, -3, L)],
     ([-3, -2, -1], [(10, 1, 0), (11, 0, 2), (11, 2, 0), (11, 3, 3)])),
    # a self-loop variable
    ([(-1, -1, F)], ([-1], [(3,)])),
    # no answer
    ([(1, -2, L), (-2, -3, F)], ([-3, -2], [])),
])
def test_known_answers(index, edges, want):
    assert answer(index, edges) == want


def brute(triples, edges):
    """Every assignment of graph vertices to the pattern's variables
    that makes each edge a triple."""
    verts = sorted({v for s, _p, o in triples for v in (s, o)})
    tset = set(triples)
    names = sorted({v for e in edges for v in e[:2] if v < 0})
    rows = []
    for vals in itertools.product(verts, repeat=len(names)):
        m = dict(zip(names, vals))
        if all((m.get(a, a), p, m.get(b, b)) in tset for a, b, p in edges):
            rows.append(vals)
    return names, sorted(rows)


def test_random_graph_matches_brute_force():
    rng = np.random.default_rng(3)
    triples = sorted({(int(rng.integers(8)), int(rng.integers(3)),
                       int(rng.integers(8))) for _ in range(40)})
    s, p, o = (np.array(c) for c in zip(*triples))
    index = reference.TripleIndex(s, p, o)
    patterns = [[(-1, -2, 0), (-2, -3, 1)],
                [(-1, -2, 0), (-1, -3, 1), (-1, -4, 2)],
                [(-1, -2, 0), (-2, -3, 1), (-3, -1, 2)],
                [(3, -2, 0), (-2, -3, 2)],
                [(-1, 5, 1), (-1, -2, 0), (-2, 5, 2)]]
    for edges in patterns:
        assert answer(index, edges) == brute(triples, edges), edges


class _Result:
    def __init__(self, bindings):
        self.bindings = bindings
        self.num_rows = len(next(iter(bindings.values()), []))


class _Req:
    def __init__(self, edges, bindings, error=None, done=1.0):
        self.edges, self.error, self.done = edges, error, done
        self.result = _Result(bindings) if bindings is not None else None


def test_compare_counts_each_kind_of_fault(index):
    edges = [(0, -2, F), (-2, -3, L)]
    good = {-2: np.array([2, 1]), -3: np.array([11, 10])}
    reqs = [
        _Req(edges, good),                                        # right
        _Req(edges, {-2: np.array([1]), -3: np.array([10])}),     # missing
        _Req(edges, {-2: np.array([2, 1, 1]),
                     -3: np.array([11, 10, 10])}),                # repeated
        _Req(edges, {-2: np.array([2, 1]), -3: np.array([11, 11])}),  # altered
        _Req(edges, None, error="QueueFullError: shed"),          # failed
        _Req(edges, None, done=None),                             # never came
    ]
    counts, ok = check.compare(index, reqs)
    assert counts == {"wrong": 3, "unanswered": 1, "failed": 1}
    assert ok == [True, False, False, False, False, False]
    correct, checks = check.verdict(counts)
    assert not correct and checks == {"not_exact": {"value": 4,
                                                    "limit": 0}}
    # a shed request alone is no wrong answer
    assert check.verdict({"wrong": 0, "unanswered": 0, "failed": 2})[0]
