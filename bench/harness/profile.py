"""From a ``jax.profiler`` trace of the window to device intervals.

The traced run wraps its window in a ``TraceAnnotation`` named
``WINDOW``; host and device events of the trace share one time base,
so that annotation ties the trace to the host's ``perf_counter``, and
the program's spans and the device's operations lie on one time line.

On a TPU each ``/device:TPU:<n>`` plane has an ``XLA Modules`` line
(one event per program execution, named ``jit_<function>(<hash>)``)
and an ``XLA Ops`` line (one event per operation, named by its HLO
text ``%<op> = <shape> <opcode>(...)``); ``Async XLA Ops`` holds the
asynchronous copies and collectives.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

#: name of the host annotation around the measured window
WINDOW = "bench_window"
#: device planes, and their lines of operations and of programs
DEVICE_PLANE = "/device:TPU:"
OPS_LINES = ("XLA Ops", "Async XLA Ops")
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]


@dataclasses.dataclass
class DeviceOp:
    text: str             # the event's name: HLO text, or a program name
    start: float          # seconds on the host's perf_counter clock
    end: float
    line: str = OPS_LINES[0]

    @property
    def name(self) -> str:
        """The HLO instruction name (``while.64``), or the whole text
        where it is not HLO."""
        m = re.match(r"%(\S+) = ", self.text)
        return m.group(1) if m else self.text


@dataclasses.dataclass
class Trace:
    """Per device: its operations (``XLA Ops``), asynchronous operations
    and program executions, on the host's clock, in the window."""
    ops: Dict[str, List[DeviceOp]]
    window: Interval
    async_ops: Dict[str, List[DeviceOp]] = dataclasses.field(
        default_factory=dict)
    modules: Dict[str, List[DeviceOp]] = dataclasses.field(
        default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy(self, device: str) -> float:
        """Seconds of the window in which an operation ran on
        ``device``."""
        return union_length(((o.start, o.end) for o in self.ops[device]),
                            *self.window)

    def busiest(self) -> Optional[str]:
        if not self.ops:
            return None
        return max(self.ops, key=self.busy)

    def module_of(self, device: str, t: float) -> Optional[str]:
        """The program executing on ``device`` at ``t``."""
        for m in self.modules.get(device, ()):
            if m.start <= t <= m.end:
                return m.text
        return None


def union_length(intervals: Iterable[Interval], lo: float, hi: float
                 ) -> float:
    """Length of the union of ``intervals`` clipped to ``[lo, hi]``."""
    total, reach = 0.0, lo
    for a, b in sorted(intervals):
        a, b = max(a, reach), min(b, hi)
        if b > a:
            total += b - a
            reach = b
    return total


def gaps(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, reach = [], lo
    for a, b in sorted(intervals):
        if a > reach and a < hi:
            out.append((reach, min(a, hi)))
        reach = max(reach, b)
    if reach < hi:
        out.append((reach, hi))
    return out


def read(directory: str, w0: float, w1: float) -> Trace:
    """The trace written under ``directory``; ``w0``/``w1`` are the
    window's bounds on ``perf_counter``, ``w0`` taken as the
    ``WINDOW`` annotation opened."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {directory}, found "
                           f"{len(files)}")
    data = ProfileData.from_file(files[0])
    anchor = None
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        anchor = ev.start_ns
    if anchor is None:
        raise RuntimeError(f"no {WINDOW!r} annotation in the trace")

    def host(ns: float) -> float:
        return w0 + (ns - anchor) * 1e-9

    trace = Trace({}, (w0, w1))
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        dev = plane.name
        for line in plane.lines:
            dest = {OPS_LINES[0]: trace.ops, OPS_LINES[1]: trace.async_ops,
                    MODULES_LINE: trace.modules}.get(line.name)
            if dest is None:
                continue
            dest.setdefault(dev, []).extend(
                DeviceOp(ev.name, host(ev.start_ns),
                         host(ev.start_ns + ev.duration_ns), line.name)
                for ev in line.events)
    return trace
