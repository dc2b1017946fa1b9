"""collective_share (%): on the busiest device, the share of its busy
time during which a collective operation (all-gather, all-reduce,
reduce-scatter, all-to-all, collective-permute; synchronous or
asynchronous) was in flight, from the profiler trace."""
import re

from harness.profile import union_length

COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|all-to-all|"
                        r"collective-permute)")


def read(run):
    trace = run.trace
    if trace is None or not trace.ops:
        return None
    dev = trace.busiest()
    lo, hi = trace.window
    busy_iv = [(o.start, o.end) for o in trace.ops[dev]]
    coll_iv = [(o.start, o.end)
               for o in trace.ops[dev] + trace.async_ops.get(dev, [])
               if COLLECTIVE.match(o.name)]
    busy, coll = union_length(busy_iv, lo, hi), union_length(coll_iv, lo, hi)
    both = busy + coll - union_length(busy_iv + coll_iv, lo, hi)
    if busy <= 0 or both <= 0:
        return None
    return 100.0 * both / busy
