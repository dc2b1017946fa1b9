"""cycle_match_ms_per_run (ms): mean duration of the engine's ``match``
spans that opened in the window and ran a program with a join step that
closes a cycle (the span's ``cycles`` attribute above 0,
``repro.core.spmd.cycle_close_steps``): one device run of such a
matcher.  A program whose spans carry no ``cycles`` gives nothing."""


def read(run):
    if run.spans is None:
        return None
    w0, w1 = run.window
    spans = [s for root in run.spans for s in root.walk()
             if s.name == "match" and w0 <= s.start < w1
             and s.end is not None and s.attrs.get("cycles", 0) > 0]
    if not spans:
        return None
    return 1e3 * sum(s.end - s.start for s in spans) / len(spans)
