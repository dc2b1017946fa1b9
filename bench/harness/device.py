"""The chip a run stands on: a TPU or nothing, and its published peaks.

A run without a TPU, with fewer chips than its cell asks for, or on a
device kind the peaks table does not list, stops here before any work.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List

PEAKS_FILE = Path(__file__).with_name("peaks.json")


class DeviceError(RuntimeError):
    """No TPU, too few chips, or a device kind without published peaks."""


def peaks(kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``kind`` (``peaks.json``)."""
    table = json.loads(PEAKS_FILE.read_text())["kinds"]
    if kind not in table:
        raise DeviceError(f"device kind {kind!r} has no entry in the peaks "
                          f"table ({PEAKS_FILE.name}: {sorted(table)})")
    return table[kind]


def require_tpu(devices: List, chips: int) -> List:
    """The first ``chips`` of ``devices`` (``jax.devices()``), which must
    be TPUs of a kind the peaks table lists."""
    platform = devices[0].platform if devices else "none"
    if platform != "tpu":
        raise DeviceError(f"no TPU: JAX found platform {platform!r}; the "
                          f"benchmark never falls back to another device")
    if len(devices) < chips:
        raise DeviceError(f"the cell needs {chips} chips, JAX found "
                          f"{len(devices)}")
    peaks(devices[0].device_kind)
    return list(devices[:chips])


def describe(devices: List) -> Dict[str, object]:
    """The result line's ``device`` entry (before the run's readings)."""
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices: List) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
