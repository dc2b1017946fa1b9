"""device_idle (%): share of the window in which no operation ran on
the busiest device, from the profiler trace."""


def read(run):
    trace = run.trace
    if trace is None or not trace.ops:
        return None
    return 100.0 * (1.0 - trace.busy(trace.busiest()) / trace.window_s)
