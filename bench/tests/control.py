"""The control: the plain reference put in the program's place, with
one guarantee of the configuration broken.

The configuration guarantees exact answer sets: every solution over
the whole graph, each once.  The plan's sites overlap (an edge may lie
in fragments on two sites), and the program removes the copies, when
it folds sites onto a chip and in its final de-duplication.  The
control serves the sites' storage as it is, copies included: a match
over an edge that two sites hold comes back twice.  That is what a
store gets that skips de-duplication to save host time, and a
comparison that cannot tell it from the program is no comparison.
"""
import numpy as np

from harness import deploy, reference


class Undeduplicated:
    """``Engine``-protocol stand-in: the reference over the
    concatenated storage of every site."""

    def __init__(self, plan, s, p, o):
        e = np.concatenate(plan.site_edge_ids())
        self.index = reference.TripleIndex(s[e], p[e], o[e])

    def answer(self, query):
        from repro.core.executor import QueryResult
        names, rows = reference.match(
            self.index, [(e.src, e.dst, e.prop) for e in query.edges])
        bindings = {v: rows[:, i].astype(np.int32)
                    for i, v in enumerate(names)}
        return QueryResult(bindings, len(rows), None)

    def execute_many(self, queries, batch_size=64):
        return [self.answer(q) for q in queries]

    def serve(self, **kw):
        from repro.serve.frontdoor import FrontDoor, FrontDoorConfig
        return FrontDoor(self, FrontDoorConfig(**kw))

    def stats(self):
        from repro.core.engine import EngineStats
        return EngineStats(extra={"capacity_retries": 0.0})


def install(monkeypatch):
    """Serve every run's front door from ``Undeduplicated``."""
    def session(config, plan, devices, tracer):
        s, p, o, _nv = deploy.triples(config)
        return Undeduplicated(plan, s, p, o)
    monkeypatch.setattr(deploy, "session", session)
