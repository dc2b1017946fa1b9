"""Self-tests of the benchmark, run on the CPU:

    python -m pytest bench/tests

Four virtual CPU devices stand in for the 2x2 mesh; the Pallas kernels
run in interpret mode there."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

BENCH = Path(__file__).resolve().parents[1]
for path in (BENCH, BENCH.parent / "src"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))
