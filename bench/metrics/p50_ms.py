"""p50_ms (ms): median latency, from the client's submit to the answer
in its hands, over every request of the window."""
from harness.latency import percentile_ms


def read(run):
    return percentile_ms(run, 50)
