"""engine_ms_per_query (ms): mean duration of the engine's ``query``
spans that opened in the window (``repro.obs.trace``; the tracer is on
in the traced run only)."""


def read(run):
    if run.spans is None:
        return None
    w0, w1 = run.window
    spans = [s for root in run.spans for s in root.walk()
             if s.name == "query" and w0 <= s.start < w1
             and s.end is not None]
    if not spans:
        return None
    return 1e3 * sum(s.end - s.start for s in spans) / len(spans)
