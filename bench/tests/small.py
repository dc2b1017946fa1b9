"""Cells of ``BENCHMARK.json`` cut to a size a CPU test run holds, and
the driver of a whole run on the CPU (the chip check skipped)."""
import time

#: departments of the test-size graph (one university) and per-device
#: capacity of the test-size cells
DEPARTMENTS, CAPACITY = 2, 1 << 13


#: the 2x2 cell, ready as files but not yet in ``BENCHMARK.json``
MESH = {"name": "lubm20-2x2.c16", "config": "lubm20-2x2",
        "traffic": "lubm-c16", "chips": 4}


def cell(name: str, departments: int = DEPARTMENTS,
         capacity: int = CAPACITY):
    """The cell ``name`` with its graph and capacity cut to test size."""
    from harness import registry
    spec = (registry.load(MESH, registry.benchmark()) if name == MESH["name"]
            else registry.cell(name))
    config = dict(spec["config"], name=f"{name}-test{departments}")
    graph = config["graph"]
    config["graph"] = dict(graph, universities=1, profile=dict(
        graph["profile"], departments_per_university=[departments] * 2))
    config["deployment"] = dict(config["deployment"],
                                capacity_rows_per_device=capacity,
                                max_capacity_rows_per_device=8 * capacity)
    config["plan"] = dict(config["plan"], workload=dict(
        config["plan"]["workload"], queries=200))
    spec["config"] = config
    spec["mix"] = dict(spec["mix"], preroll_s=0.5)
    return spec


def execute(spec, seed: int, seconds: float = 2.0, traced: bool = False):
    """A whole run of ``spec`` on the CPU devices: the result line."""
    import jax
    from harness import cell as cell_mod
    devices = jax.devices()[:spec["cell"]["chips"]]
    return cell_mod.execute(spec, jax, devices, time.perf_counter(), seed,
                            seconds, traced)
