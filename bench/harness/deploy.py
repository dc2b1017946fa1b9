"""A configuration, deployed: graph, plan, session and front door.

Set-up that does not depend on ``--seed`` is cached inside the
checkout, keyed by the configuration's name and contents: the graph's
triples (``graph.npz``) and the plan (``PartitionPlan.save``).  The
first run of a cell writes them; every later run loads them.
"""
from __future__ import annotations

import hashlib
import importlib
import json
import shutil
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

from .traffic import QueryStream

BENCH = Path(__file__).resolve().parents[1]
#: fixed cache root inside the checkout
CACHE = BENCH / ".cache"


def generator(config: Dict):
    """The module that makes the configuration's graph,
    ``harness/<graph.generator>.py``."""
    return importlib.import_module(f"harness.{config['graph']['generator']}")


def property_ids(config: Dict) -> Dict[str, int]:
    names = generator(config).property_names(config["graph"])
    return {name: i for i, name in enumerate(names)}


def named_vertices(config: Dict) -> Dict[str, int]:
    return generator(config).named_vertices(config["graph"])


def planning_mix(config: Dict) -> Dict:
    name = config["plan"]["workload"]["mix"]
    return json.loads((BENCH / "mixes" / f"{name}.json").read_text())


def _fingerprint(config: Dict) -> str:
    keep = {k: config[k] for k in ("graph", "plan")}
    keep["planning_mix"] = planning_mix(config)
    return hashlib.sha256(json.dumps(keep, sort_keys=True).encode()
                          ).hexdigest()


def triples(config: Dict) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """(s, p, o, number of vertex ids) of the configuration's graph."""
    d = CACHE / "deploy" / config["name"]
    f = d / "graph.npz"
    fp = _fingerprint(config)
    if f.exists() and (d / "fingerprint").read_text() == fp:
        z = np.load(f)
        return z["s"], z["p"], z["o"], int(z["num_vertices"])
    shutil.rmtree(d, ignore_errors=True)
    d.mkdir(parents=True)
    s, p, o, nv = generator(config).generate_graph(config["graph"])
    np.savez(f, s=s, p=p, o=o, num_vertices=nv)
    (d / "fingerprint").write_text(fp)
    return s, p, o, nv


def program_graph(config: Dict, s, p, o, num_vertices: int):
    """The program's ``RDFGraph`` over the generated triples."""
    from repro.core import RDFGraph
    names = generator(config).property_names(config["graph"])
    return RDFGraph(s, p, o, num_vertices, len(names), None, names)


def planning_workload(config: Dict, s, p, o) -> List[list]:
    """The design workload the plan is built from: queries of the
    planning mix, drawn with the planning seed."""
    spec = config["plan"]["workload"]
    stream = QueryStream(planning_mix(config), property_ids(config),
                         named_vertices(config), s, p, o, int(spec["seed"]))
    return [stream.next()[1] for _ in range(int(spec["queries"]))]


def plan(config: Dict, graph):
    """The configuration's ``PartitionPlan``: loaded from the cache, or
    built from the planning workload and saved there."""
    from repro.core import (PartitionConfig, PartitionPlan, QueryGraph,
                            Workload, build_plan)
    path = CACHE / "deploy" / config["name"] / "plan"
    if (path / "plan.json").exists():
        return PartitionPlan.load(path, graph)
    spec = config["plan"]
    queries = planning_workload(config, graph.s, graph.p, graph.o)
    built = build_plan(graph, Workload([QueryGraph.make(q) for q in queries]),
                       PartitionConfig(kind=spec["kind"],
                                       num_sites=spec["num_sites"]))
    tmp = path.with_name("plan.tmp")
    shutil.rmtree(tmp, ignore_errors=True)
    built.save(tmp)
    tmp.rename(path)
    return built


def session(config: Dict, plan_, devices, tracer):
    """``Session(plan, backend="spmd")`` over a mesh of ``devices``."""
    import jax
    from repro.core import Session
    dep = config["deployment"]
    mesh = jax.sharding.Mesh(np.asarray(devices), (dep["mesh_axis"],),
                             axis_types=(jax.sharding.AxisType.Auto,))
    return Session(plan_, backend="spmd", mesh=mesh,
                   spmd_axis=dep["mesh_axis"],
                   spmd_capacity=dep["capacity_rows_per_device"],
                   spmd_max_capacity=dep["max_capacity_rows_per_device"],
                   spmd_comm_plan=dep["comm_plan"],
                   spmd_routing=dep["routing"], tracer=tracer)


def front_door(config: Dict, session_):
    """The session's ``FrontDoor`` with the configuration's settings."""
    fd = {k: v for k, v in config["front_door"].items() if k != "about"}
    return session_.serve(**fd)
