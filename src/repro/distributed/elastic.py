"""Elastic scaling + failure recovery.

Strategy (standard for 1000+ node fleets):
  * mesh shapes are *derived* from the live device set, never hard-coded;
  * on failure/preemption, shrink to the largest (data' x model) grid the
    survivors support, keeping the model axis intact (TP groups must stay
    whole -- losing one chip of a TP group kills the group);
  * parameters are restored from the latest checkpoint into the new
    sharding (checkpoint leaves are full arrays, so resharding is a
    device_put with the new NamedSharding);
  * the data pipeline is deterministic-addressable, so the batch cursor
    just continues (no replay, no skips);
  * for the RDF engine, fragment allocation is *re-clustered* with
    Algorithm 2 at m' = surviving site count (the paper's allocator is
    cheap: metadata-scale).
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np


@dataclasses.dataclass
class MeshPlan:
    shape: Tuple[int, ...]
    axes: Tuple[str, ...]
    devices_used: int


def plan_mesh(num_devices: int, model_parallel: int,
              pods: int = 1) -> MeshPlan:
    """Largest (pods, data, model) grid supported by ``num_devices``.

    Keeps ``model_parallel`` fixed (TP groups are whole or dead) and
    flexes the data axis; drops the pod axis when survivors < 2 pods.
    """
    if model_parallel > num_devices:
        raise ValueError("fewer devices than one TP group")
    if pods > 1:
        per_pod = num_devices // pods
        data = per_pod // model_parallel
        if data >= 1:
            return MeshPlan((pods, data, model_parallel),
                            ("pod", "data", "model"),
                            pods * data * model_parallel)
    data = num_devices // model_parallel
    return MeshPlan((data, model_parallel), ("data", "model"),
                    data * model_parallel)


class ElasticMeshManager:
    """Tracks the live device set and rebuilds meshes after failures.

    ``fail(device_ids)`` simulates losing devices (tests / dry-run);
    production would learn this from the coordination service heartbeat.
    """

    def __init__(self, model_parallel: int, pods: int = 1,
                 devices: Optional[Sequence] = None):
        import jax
        self._all = list(devices if devices is not None else jax.devices())
        self._dead: set = set()
        self.model_parallel = model_parallel
        self.pods = pods
        self.generation = 0

    @property
    def live(self) -> List:
        return [d for d in self._all if id(d) not in self._dead]

    def fail(self, devices: Sequence) -> None:
        for d in devices:
            self._dead.add(id(d))
        self.generation += 1

    def recover(self) -> None:
        self._dead.clear()
        self.generation += 1

    def current_plan(self) -> MeshPlan:
        return plan_mesh(len(self.live), self.model_parallel, self.pods)

    def make_mesh(self):
        import jax

        plan = self.current_plan()
        dev = np.asarray(self.live[: plan.devices_used]).reshape(plan.shape)
        return jax.sharding.Mesh(
            dev, plan.axes,
            axis_types=(jax.sharding.AxisType.Auto,) * len(plan.axes))

    def reshard(self, tree: Any, shardings: Any) -> Any:
        """Re-place a (restored) pytree onto the current mesh."""
        import jax
        flat_t, tdef = jax.tree.flatten(tree)
        flat_s = tdef.flatten_up_to(shardings)
        return jax.tree.unflatten(
            tdef, [jax.device_put(t, s) for t, s in zip(flat_t, flat_s)])


def replan_allocation(affinity: np.ndarray, surviving_sites: int,
                      sizes: Optional[np.ndarray] = None,
                      balance_factor: float = 0.25) -> np.ndarray:
    """Re-run the paper's Algorithm 2 for a shrunken site set (RDF
    engine elastic path).  Returns fragment -> new site."""
    from ..core.allocation import allocate
    alloc = allocate(affinity, surviving_sites, sizes, balance_factor)
    return alloc.site_of
