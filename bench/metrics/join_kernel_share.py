"""join_kernel_share (%): device time of the Pallas join kernels over
device busy time, summed over the devices used, from the profiler
trace.  On a TPU the trace names a Pallas call after the function that
holds it (``per_site.<n>``), with ``custom_call_target="tpu_custom_call"``
in its text; the match loop's Pallas calls are its join kernels
(``join_count`` and ``pair_semijoin``, ``repro.core.spmd.TPU_KERNELS``)."""
from harness.profile import union_length

MARK = 'custom_call_target="tpu_custom_call"'


def read(run):
    trace = run.trace
    if trace is None or not trace.ops:
        return None
    lo, hi = trace.window
    busy = sum(trace.busy(d) for d in trace.ops)
    kernel = sum(union_length(((o.start, o.end) for o in ops
                               if MARK in o.text), lo, hi)
                 for ops in trace.ops.values())
    if busy <= 0 or kernel <= 0:
        return None
    return 100.0 * kernel / busy
