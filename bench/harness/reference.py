"""The plain reference: a basic-graph-pattern matcher in numpy.

It shares nothing with the program: it reads the triples the
benchmark generated and answers a pattern by expanding one edge at a
time over sorted (property, subject, object) and (property, object,
subject) keys.  Every solution over the whole graph comes out, each
once, as rows over the pattern's variables in ascending order of their
(negative) ids.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

Edge = Tuple[int, int, int]


class TripleIndex:
    """Triples sorted by (p, s, o) and by (p, o, s) for range lookups."""

    def __init__(self, s: np.ndarray, p: np.ndarray, o: np.ndarray) -> None:
        s, p, o = (np.asarray(a, np.int64) for a in (s, p, o))
        self.base = int(max(s.max(initial=0), o.max(initial=0))) + 2
        fwd = np.lexsort((o, s, p))
        self.fwd_key = p[fwd] * self.base + s[fwd]
        self.fwd_val = o[fwd]
        bwd = np.lexsort((s, o, p))
        self.bwd_key = p[bwd] * self.base + o[bwd]
        self.bwd_val = s[bwd]
        self.triple_key = np.sort((p * self.base + s) * self.base + o)
        self.prop_lo = np.searchsorted(self.fwd_key, np.arange(
            int(p.max(initial=0)) + 2) * self.base)

    def neighbours(self, prop: int, ends: np.ndarray, forward: bool
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """For each of ``ends`` (subjects if ``forward``, else objects)
        every other end of a ``prop`` edge: (index into ``ends``,
        neighbour)."""
        keys, vals = ((self.fwd_key, self.fwd_val) if forward
                      else (self.bwd_key, self.bwd_val))
        probe = prop * self.base + ends
        lo = np.searchsorted(keys, probe, side="left")
        hi = np.searchsorted(keys, probe, side="right")
        cnt = hi - lo
        src = np.repeat(np.arange(len(ends)), cnt)
        at = np.repeat(lo - np.cumsum(cnt) + cnt, cnt) + np.arange(cnt.sum())
        return src, vals[at]

    def has(self, s: np.ndarray, prop: int, o: np.ndarray) -> np.ndarray:
        """Is (s, prop, o) a triple, elementwise."""
        key = (prop * self.base + s) * self.base + o
        if not len(self.triple_key):
            return np.zeros(len(key), bool)
        at = np.minimum(np.searchsorted(self.triple_key, key),
                        len(self.triple_key) - 1)
        return self.triple_key[at] == key

    def edges(self, prop: int) -> Tuple[np.ndarray, np.ndarray]:
        """Every (subject, object) of ``prop``."""
        if prop + 1 >= len(self.prop_lo):
            return np.zeros(0, np.int64), np.zeros(0, np.int64)
        lo, hi = self.prop_lo[prop], self.prop_lo[prop + 1]
        return self.fwd_key[lo:hi] % self.base, self.fwd_val[lo:hi]


    def count(self, prop: int, end: int, forward: bool) -> int:
        """How many ``prop`` edges have ``end`` as subject (``forward``)
        or object."""
        keys = self.fwd_key if forward else self.bwd_key
        probe = prop * self.base + end
        return int(np.searchsorted(keys, probe, side="right")
                   - np.searchsorted(keys, probe, side="left"))


def _edge_order(index: TripleIndex, edges: Sequence[Edge]) -> List[int]:
    """Edges in the order they are joined: first the edge with a
    constant that has the fewest matches, then each edge whose two ends
    are bound (a filter) before any that binds a new variable, and an
    edge that touches no bound variable only when no other is left."""
    left = list(range(len(edges)))

    def matches(i: int) -> int:
        a, b, prop = edges[i]
        return min(index.count(prop, a, True) if a >= 0 else 1 << 62,
                   index.count(prop, b, False) if b >= 0 else 1 << 62)

    with_const = [i for i in left if edges[i][0] >= 0 or edges[i][1] >= 0]
    first = min(with_const, key=matches) if with_const else 0
    order = [first]
    left.remove(first)
    bound = {v for v in edges[first][:2] if v < 0}

    def rank(i: int) -> int:
        a, b, _prop = edges[i]
        ends = [v >= 0 or v in bound for v in (a, b)]
        touches = any(v < 0 and v in bound for v in (a, b))
        return 0 if all(ends) else 1 if touches else 2
    while left:
        nxt = min(left, key=rank)
        order.append(nxt)
        left.remove(nxt)
        bound |= {v for v in edges[nxt][:2] if v < 0}
    return order


def match(index: TripleIndex, edges: Sequence[Edge]
          ) -> Tuple[List[int], np.ndarray]:
    """(variables in ascending id order, solution rows) of the pattern."""
    cols: Dict[int, np.ndarray] = {}
    n = 1                                   # rows so far (one empty row)

    def values(v: int) -> np.ndarray:
        return cols[v] if v < 0 else np.full(n, v, np.int64)

    def bound(v: int) -> bool:
        return v >= 0 or v in cols

    for ei in _edge_order(index, edges):
        a, b, prop = edges[ei]
        if bound(a) and bound(b):
            keep = index.has(values(a), prop, values(b))
            cols = {v: c[keep] for v, c in cols.items()}
            n = int(keep.sum())
        elif bound(a) or bound(b):
            fwd = bound(a)
            src, other = index.neighbours(prop, values(a if fwd else b), fwd)
            cols = {v: c[src] for v, c in cols.items()}
            cols[b if fwd else a] = other
            n = len(src)
        else:
            subj, obj = index.edges(prop)
            if a == b:
                subj, obj = subj[subj == obj], obj[subj == obj]
            cols = {v: np.repeat(c, len(subj)) for v, c in cols.items()}
            cols[a] = np.tile(subj, n)
            cols[b] = np.tile(obj, n)
            n *= len(subj)
    names = sorted(cols)
    if not names:
        return names, np.zeros((n, 0), np.int64)
    return names, np.stack([cols[v] for v in names], 1)
