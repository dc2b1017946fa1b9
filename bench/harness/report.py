"""The result line: the cell's metrics as their readers find them, the
device, the breakdown of a traced run, and the numbers compared."""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional

from . import device, profile, registry

#: entries kept in each list of the breakdown
TOP = 10


def metrics(specs: List[Dict], run) -> Dict[str, Dict]:
    """``{name: {"value", "unit"}}`` for each metric whose reader finds
    something to read in ``run``."""
    out = {}
    for spec in specs:
        value = registry.reader(spec["name"])(run)
        if value is not None:
            out[spec["name"]] = {"value": value, "unit": spec["unit"]}
    return out


def op_group(trace: profile.Trace, device: str, op: profile.DeviceOp
             ) -> str:
    """``<program>/<instruction>``: one instruction of one compiled
    program, the same in every run of that program."""
    module = trace.module_of(device, op.start)
    return f"{module}/{op.name}" if module else op.name


def host_activity(spans, t: float) -> str:
    """What the program was doing on the host at ``t``: the innermost of
    its spans (``serve_batch`` > ``query``) that covers ``t``."""
    for root in spans or ():
        if root.start <= t <= (root.end or root.start):
            for child in root.walk():
                if child is not root and child.start <= t <= (
                        child.end or child.start):
                    return f"host: in {child.name} span"
            return f"host: in {root.name} span, outside its children"
    return "host: outside the program's spans"


def breakdown(run) -> Optional[Dict]:
    """The device operations that took most time, and the longest idle
    gaps on the busiest device with what the host was doing."""
    trace = run.trace
    if trace is None or not trace.ops:
        return None
    lo, hi = trace.window
    total = defaultdict(float)
    for dev, ops in trace.ops.items():
        for op in ops:
            total[op_group(trace, dev, op)] += max(
                min(op.end, hi) - max(op.start, lo), 0.0)
    busiest = trace.busiest()
    idle = profile.gaps(((o.start, o.end) for o in trace.ops[busiest]),
                        lo, hi)
    idle.sort(key=lambda g: g[0] - g[1])
    return {
        "device_ops": [[k, v] for k, v in sorted(
            total.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[host_activity(run.spans, (a + b) / 2), b - a]
                      for a, b in idle[:TOP]]}


def line(cell: Dict, run, verdict: Dict, devices: List, traced: bool
         ) -> Dict:
    dev = device.describe(devices)
    dev["memory_peak_bytes"] = run.memory_peak_bytes
    out = {"correct": verdict["correct"],
           "attempted": len(run.in_window),
           "failed": sum(r.error is not None or r.done is None
                         for r in run.in_window),
           "metrics": metrics(cell["per_layer" if traced else "end_to_end"],
                              run),
           "device": dev}
    if traced and run.trace is not None:
        dev["busy_s"] = (sum(run.trace.busy(d) for d in run.trace.ops)
                         / run.chips)
        dev["window_s"] = run.trace.window_s
        bd = breakdown(run)
        if bd is not None:
            out["breakdown"] = bd
    out["checks"] = verdict["checks"]
    return out
