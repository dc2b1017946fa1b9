"""The mix generator: the same queries for the same seed, others for
another seed, the same shape counts for every seed, and constants drawn
from the data in the bind position."""
from collections import Counter

import pytest

import small
from harness import deploy, lubm
from harness.traffic import QueryStream, bind_pool, deck_counts

SEEDS = (7, 2**31 + 12345)


@pytest.fixture(scope="module")
def data():
    spec = small.cell("lubm20-1chip.c16")
    s, p, o, _nv = lubm.generate_graph(spec["config"]["graph"])
    return (spec["mix"], deploy.property_ids(spec["config"]),
            deploy.named_vertices(spec["config"]), s, p, o)


def draw(data, seed, n=300):
    mix, prop_id, named, s, p, o = data
    stream = QueryStream(mix, prop_id, named, s, p, o, seed)
    return [stream.next() for _ in range(n)]


@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_queries(data, seed):
    assert draw(data, seed) == draw(data, seed)


def test_other_seed_other_queries(data):
    a, b = (draw(data, seed) for seed in SEEDS)
    assert a != b
    assert [q for _s, q in a] != [q for _s, q in b]


def test_every_seed_sends_the_same_shape_counts(data):
    mix = data[0]
    deck = mix["popularity"]["deck"]
    n = len(mix["shapes"])
    counts = [Counter(shape for shape, _q in draw(data, seed, 3 * deck))
              for seed in SEEDS]
    assert counts[0] == counts[1]
    want = deck_counts([1.0] * n, deck)
    assert [counts[0][i] for i in range(n)] == [3 * c for c in want]
    assert sum(want) == deck


def test_constants_come_from_the_data_in_the_bind_position(data):
    mix, prop_id, named, s, p, o = data
    classes = set(named.values())
    for shape, edges in draw(data, SEEDS[1]):
        bind = mix["shapes"][shape]["bind"]
        consts = {v for e in edges for v in e[:2] if v >= 0} - classes
        if bind is None:
            assert not consts
            continue
        (c,) = consts
        assert c in set(bind_pool(bind, prop_id, named, s, p, o).tolist())
        assert bind["var"] not in {v for e in edges for v in e[:2]}


def test_classes_are_named_by_position():
    assert deploy.named_vertices({"graph": {"generator": "lubm"}}) == {
        c: i for i, c in enumerate(lubm.CLASSES)}
