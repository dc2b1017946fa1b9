"""match_ms_per_run (ms): mean duration of the engine's ``match`` spans
that opened in the window: one device run of a matcher program, from
the call to ``block_until_ready`` (``repro.core.spmd``; the tracer is on
in the traced run only).  A program without ``match`` spans gives
nothing to read."""


def read(run):
    if run.spans is None:
        return None
    w0, w1 = run.window
    spans = [s for root in run.spans for s in root.walk()
             if s.name == "match" and w0 <= s.start < w1
             and s.end is not None]
    if not spans:
        return None
    return 1e3 * sum(s.end - s.start for s in spans) / len(spans)
