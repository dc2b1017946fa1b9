"""Name lookups: ``BENCHMARK.json`` says which configuration, mix and
metrics a cell has; the files are found by those names."""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from typing import Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def benchmark() -> Dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell(name: str) -> Dict:
    """The ``workloads`` entry of ``name`` with its files loaded
    (``load``)."""
    spec = benchmark()
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"({sorted(cells)})")
    return load(cells[name], spec)


def load(entry: Dict, spec: Dict) -> Dict:
    """A ``workloads`` entry with its configuration and mix loaded and
    its metrics listed: ``{"cell", "config", "mix", "end_to_end",
    "per_layer"}``.  The configuration file is the one ``spec`` names,
    else ``configs/<config>.json``."""
    files = {c["name"]: c["file"] for c in spec["configs"]}
    path = (ROOT / files[entry["config"]] if entry["config"] in files
            else BENCH / "configs" / f"{entry['config']}.json")
    name = entry["name"]

    def mine(metrics: List[Dict]) -> List[Dict]:
        return [m for m in metrics if name in m.get("workloads", [name])]

    return {"cell": entry, "config": json.loads(path.read_text()),
            "mix": json.loads((BENCH / "mixes" / f"{entry['traffic']}.json")
                              .read_text()),
            "end_to_end": mine(spec["end_to_end"]),
            "per_layer": mine(spec["per_layer"])}


def reader(metric: str):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    mod_name = "bench_metric_" + re.sub(r"\W", "_", metric)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or not path.exists():
        raise FileNotFoundError(f"no reader for metric {metric!r} at {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
