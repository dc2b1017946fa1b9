"""The one traffic generator: a mix file's parameters and ``--seed`` in,
a deterministic stream of queries out.

The mix names its shapes as edge lists over property names (with fixed
constants, such as a class, by their name), the variable each shape
binds to a constant drawn from the data, the popularity of the shapes
and the skew of the constants.  The seed draws which shape comes when,
which constants each shape's pool holds, and which pool entry each
query takes; the shape counts per deck are the same for every seed.
"""
from __future__ import annotations

import threading
from typing import Dict, Iterator, List, Sequence, Tuple

import numpy as np

Edge = Tuple[int, int, int]


def deck_counts(weights: Sequence[float], deck: int) -> List[int]:
    """Shape counts of one deck: ``deck`` queries split by ``weights``
    with largest-remainder rounding."""
    w = np.asarray(weights, np.float64)
    share = w / w.sum() * deck
    counts = np.floor(share).astype(int)
    order = np.argsort(-(share - counts), kind="stable")
    counts[order[:deck - counts.sum()]] += 1
    return counts.tolist()


def shape_weights(mix: Dict) -> List[float]:
    """Zipf(``a``) over the shapes in their order, or ``equal``."""
    pop = mix["popularity"]
    n = len(mix["shapes"])
    if pop["kind"] == "equal":
        return [1.0] * n
    if pop["kind"] != "zipf":
        raise ValueError(f"unknown popularity kind {pop['kind']!r}")
    return list(1.0 / np.arange(1, n + 1) ** pop["a"])


def bind_pool(bind: Dict, prop_id: Dict[str, int], named: Dict[str, int],
              s: np.ndarray, p: np.ndarray, o: np.ndarray) -> np.ndarray:
    """Every vertex a shape may bind: the ``side`` end of each edge of
    ``prop`` (whose other end is the vertex named ``other``, if given)."""
    mask = p == prop_id[bind["prop"]]
    ends, others = (s, o) if bind["side"] == "subject" else (o, s)
    if "other" in bind:
        mask &= others == named[bind["other"]]
    return ends[mask]


class QueryStream:
    """Thread-safe source of the mix's queries, in one order per seed.

    ``next()`` returns ``(shape index, edge list with constants)``.
    Edges are ``(src, dst, prop_id)`` with variables negative and
    constants vertex ids, the program's ``QueryGraph.make`` encoding.
    """

    def __init__(self, mix: Dict, prop_id: Dict[str, int],
                 named: Dict[str, int], s: np.ndarray, p: np.ndarray,
                 o: np.ndarray, seed: int) -> None:
        self.mix = mix
        root = np.random.SeedSequence([int(seed), 0x6D6978])
        pool_seq, deck_seq, pick_seq = root.spawn(3)

        def end(v):
            return named[v] if isinstance(v, str) else v
        self.shapes = [[(end(a), end(b), prop_id[name])
                        for a, b, name in sh["edges"]]
                       for sh in mix["shapes"]]
        size = int(mix["constants"]["pool"])
        prng = np.random.default_rng(pool_seq)
        self.pools = []
        for sh in mix["shapes"]:
            if sh["bind"] is None:
                self.pools.append(np.zeros(1, np.int64))
                continue
            ends = bind_pool(sh["bind"], prop_id, named, s, p, o)
            self.pools.append(ends[prng.integers(
                0, len(ends), size)].astype(np.int64))
        self.counts = deck_counts(shape_weights(mix),
                                  int(mix["popularity"]["deck"]))
        self._deck_rng = np.random.default_rng(deck_seq)
        self._pick_rng = np.random.default_rng(pick_seq)
        self._zipf_s = float(mix["constants"]["zipf_s"])
        self._lock = threading.Lock()
        self._order: Iterator[int] = iter(())

    def _deal(self) -> Iterator[int]:
        deck = np.repeat(np.arange(len(self.counts)), self.counts)
        return iter(self._deck_rng.permutation(deck).tolist())

    def query(self, shape: int, pool_index: int) -> List[Edge]:
        """Shape ``shape`` with its bind variable set to pool entry
        ``pool_index``."""
        bind = self.mix["shapes"][shape]["bind"]
        if bind is None:
            return list(self.shapes[shape])
        var = bind["var"]
        cst = int(self.pools[shape][pool_index])
        return [(cst if a == var else a, cst if b == var else b, prop)
                for a, b, prop in self.shapes[shape]]

    def next(self) -> Tuple[int, List[Edge]]:
        with self._lock:
            shape = next(self._order, None)
            if shape is None:
                self._order = self._deal()
                shape = next(self._order)
            rank = int(self._pick_rng.zipf(self._zipf_s))
        return shape, self.query(shape, rank % len(self.pools[shape]))
