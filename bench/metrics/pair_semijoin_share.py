"""pair_semijoin_share (%): device time of the cycle-close kernel's
custom calls over the busy time of the busiest device, from the
profiler trace.  The match loop's join steps that close a cycle probe
pair membership through the Pallas ``pair_semijoin`` kernel on a TPU
(``repro.core.spmd.TPU_KERNELS``); its calls are told from
``join_count``'s by the event's instruction name, ``DeviceOp.name``
(``pair_semijoin.<n>``).  A run with no such call gives nothing."""
import re

from harness.profile import union_length

MARK = 'custom_call_target="tpu_custom_call"'
NAME = re.compile(r"pair_semijoin(\.\d+)?$")


def read(run):
    trace = run.trace
    if trace is None or not trace.ops:
        return None
    dev = trace.busiest()
    lo, hi = trace.window
    kernel = union_length(((o.start, o.end) for o in trace.ops[dev]
                           if MARK in o.text and NAME.match(o.name)), lo, hi)
    busy = trace.busy(dev)
    if busy <= 0 or kernel <= 0:
        return None
    return 100.0 * kernel / busy
