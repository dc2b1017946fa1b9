"""p90_ms (ms): nearest-rank 90th percentile of the latencies of every
request of the window (a request without an answer lies beyond).  The
highest percentile with at least ten requests beyond it: a window of
``lubm20-1chip.c16`` holds about 175 requests, 17 beyond p90 and 8
beyond p95."""
from harness.latency import percentile_ms


def read(run):
    return percentile_ms(run, 90)
