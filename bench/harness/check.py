"""What decides ``correct``: every request of the window against the
plain reference.

Each served answer is compared as a multiset of rows over the query's
variables with the reference's solutions: a missing, extra, altered or
repeated row makes the answer wrong.  The one number compared is
``not_exact``: requests of the run (pre-roll and window) whose answer
differs from the reference or never came (not even ``closed_loop.SETTLE_S`` seconds
after the window closed).  Its limit is 0, as for any exact
comparison.  A request that failed or was shed is no wrong answer: it
counts in the result line's ``failed`` and lies beyond every latency
limit.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from . import reference

LIMITS = {"not_exact": 0}


def served_rows(result) -> Tuple[List[int], np.ndarray]:
    """(variables in ascending id order, rows sorted) of a served
    ``QueryResult``."""
    names = sorted(result.bindings)
    if not names:
        return names, np.zeros((result.num_rows, 0), np.int64)
    rows = np.stack([np.asarray(result.bindings[v], np.int64)
                     for v in names], 1)
    return names, rows[np.lexsort(rows.T[::-1])]


def expected(index: reference.TripleIndex, edges: Sequence
             ) -> Tuple[List[int], np.ndarray]:
    names, rows = reference.match(index, edges)
    if rows.shape[1]:
        rows = np.unique(rows, axis=0)
    return names, rows


def compare(index: reference.TripleIndex, requests) -> Tuple[Dict, List[bool]]:
    """(counts of wrong, unanswered and failed requests, and for each
    request whether it was answered correctly)."""
    memo: Dict[tuple, Tuple[List[int], np.ndarray]] = {}
    counts = dict.fromkeys(("wrong", "unanswered", "failed"), 0)
    ok: List[bool] = []
    for req in requests:
        if req.done is None:
            counts["unanswered"] += 1
            ok.append(False)
            continue
        if req.error is not None:
            counts["failed"] += 1
            ok.append(False)
            continue
        key = tuple(map(tuple, req.edges))
        if key not in memo:
            memo[key] = expected(index, req.edges)
        want_vars, want = memo[key]
        got_vars, got = served_rows(req.result)
        same = (got_vars == want_vars and got.shape == want.shape
                and np.array_equal(got, want))
        counts["wrong"] += not same
        ok.append(same)
    return counts, ok


def verdict(counts: Dict) -> Tuple[bool, Dict]:
    """(correct, the result line's ``checks`` entry)."""
    numbers = {"not_exact": counts["wrong"] + counts["unanswered"]}
    checks = {k: {"value": int(numbers[k]), "limit": LIMITS[k]}
              for k in LIMITS}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
