"""engine_host_ms_per_query (ms): mean self time of the engine's
``query`` spans that opened in the window, taking the ``match`` spans
inside them as the children: each span's duration minus the part of it
that its ``match`` spans cover, so what is left is host work (the copy
to the host, ``dedup``, ``filter``, the ledger).  A program without
``match`` spans gives nothing to read."""
from harness.profile import union_length


def read(run):
    if run.spans is None:
        return None
    w0, w1 = run.window
    queries = [s for root in run.spans for s in root.walk()
               if s.name == "query" and w0 <= s.start < w1
               and s.end is not None]
    selves, matched = [], False
    for q in queries:
        runs = [(m.start, m.end) for m in q.walk()
                if m.name == "match" and m.end is not None]
        matched |= bool(runs)
        selves.append(q.end - q.start - union_length(runs, q.start, q.end))
    if not matched:
        return None
    return 1e3 * sum(selves) / len(selves)
