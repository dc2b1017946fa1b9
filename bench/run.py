#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload lubm20-1chip.c16 --seed 7 \\
        --seconds 30 --trace 0

``--workload`` names an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration and traffic mix are found by name.  ``--trace 0``
measures the cell's end-to-end metrics with tracing off; ``--trace 1``
reads its per-layer metrics from the program's spans and a profiler
trace of the window.  The last line of standard output is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` ``breakdown``, and last ``checks``: each number
compared with its limit, also printed as the last lines of standard
error).  Without a TPU, or with fewer chips than the cell asks for, the
run exits non-zero before any work and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from harness import registry  # noqa: E402

#: exit code of a run without a TPU, or with too few chips
EXIT_NO_DEVICE = 3


def configure_compile_cache(jax) -> str:
    """JAX's persistent compilation cache where the program keeps it
    (``repro.launch.compile_cache``), keeping every program however
    fast it compiled."""
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")

    cell_spec = registry.cell(args.workload)
    import jax

    from harness import device
    try:
        devices = device.require_tpu(jax.devices(),
                                     int(cell_spec["cell"]["chips"]))
    except (device.DeviceError, RuntimeError) as exc:
        print(f"bench: {exc}", file=sys.stderr, flush=True)
        return EXIT_NO_DEVICE
    sys.path.insert(0, str(BENCH.parent / "src"))
    cache = configure_compile_cache(jax)
    from harness import cell
    cell.log(f"cell {args.workload}: {device.describe(devices)}, compile "
             f"cache {cache}")
    line = cell.execute(cell_spec, jax, devices, T_START, args.seed,
                        args.seconds, bool(args.trace))
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
