"""Benchmark harness: one function per paper table/figure + the adaptive
drift benchmark + the roofline report from the dry-run artifacts.

  PYTHONPATH=src python -m benchmarks.run                 # all
  PYTHONPATH=src python -m benchmarks.run --only fig9     # substring match
  PYTHONPATH=src python -m benchmarks.run --json reports/BENCH_pr1.json
  PYTHONPATH=src python -m benchmarks.run --roofline-dir reports/dryrun_baseline
  PYTHONPATH=src python -m benchmarks.run --smoke         # CI quick subset
  PYTHONPATH=src python -m benchmarks.run --trace --trace-out reports/spans.jsonl

Output: CSV rows ``bench,variant,metric,value``; with ``--json PATH`` the
same rows are also written as a schema-versioned trajectory record
(``repro.bench/v1``: rows + per-bench wall time + git revision + device
count + a validated ``repro.obs`` metrics snapshot) so the perf
trajectory can be tracked across PRs.  ``--trace`` turns on the
process-default tracer before any bench constructs an engine (engines
bind the tracer at construction); ``--trace-out`` dumps the finished
root spans as JSONL.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

#: trajectory-record schema (bump on breaking payload changes)
BENCH_SCHEMA = "repro.bench/v1"


def _git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10, cwd=os.path.dirname(os.path.abspath(__file__)),
            check=True).stdout.strip()
    except Exception:
        return "unknown"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on bench names")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write results as a BENCH_*.json trajectory "
                         "record (schema repro.bench/v1, embeds the "
                         "metrics snapshot)")
    ap.add_argument("--roofline-dir", default="reports/dryrun_baseline")
    ap.add_argument("--skip-roofline", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="quick CI subset (engine-parity regression bench "
                         "+ telemetry latency bench + plan-lifecycle "
                         "bench); implies --skip-roofline")
    ap.add_argument("--trace", action="store_true",
                    help="enable the process-default span tracer for "
                         "every bench engine")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write finished root spans as JSONL "
                         "(implies --trace)")
    args = ap.parse_args()

    # Same default as tests/conftest.py: a 4-device host mesh, so the
    # SPMD benches (engine parity, spmd_comm) exercise the broadcast
    # joins and report a non-zero collective ledger.  A pinned
    # XLA_FLAGS wins; must run before the benches import jax.
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=4")

    from repro.launch.compile_cache import enable_compile_cache
    print(f"# compile cache: {enable_compile_cache()}", file=sys.stderr)

    tracer = None
    if args.trace or args.trace_out:
        # before any bench runs: engines bind the process tracer at
        # construction, so enabling it later would trace nothing
        from repro.obs.trace import enable_tracing
        tracer = enable_tracing(capacity=4096)

    from . import adaptive, lifecycle, paper_benches
    from .roofline import bench_roofline

    if args.smoke:
        args.skip_roofline = True
        benches = list(paper_benches.SMOKE) + list(lifecycle.ALL)
    else:
        benches = (list(paper_benches.ALL) + list(adaptive.ALL)
                   + list(lifecycle.ALL))

    timings = {}
    for fn in benches:
        name = fn.__name__
        if args.only and args.only not in name:
            continue
        t0 = time.perf_counter()
        print(f"# --- {name} ---", file=sys.stderr)
        fn()
        timings[name] = time.perf_counter() - t0
        print(f"# {name}: {timings[name]:.1f}s", file=sys.stderr)

    if not args.skip_roofline and (args.only is None
                                   or "roofline" in args.only):
        print("# --- roofline ---", file=sys.stderr)
        bench_roofline(args.roofline_dir)

    if args.trace_out:
        from repro.obs.export import dump_spans
        d = os.path.dirname(args.trace_out)
        if d:
            os.makedirs(d, exist_ok=True)
        n = dump_spans(tracer, args.trace_out)
        print(f"# wrote {n} spans to {args.trace_out}", file=sys.stderr)

    if args.json:
        import jax

        from repro.obs.export import snapshot, validate_snapshot
        metrics = snapshot(tracer=tracer)
        # fail loudly (CI gate): a pre-registered metric going missing
        # means an engine stopped publishing its telemetry
        validate_snapshot(metrics)
        payload = {
            "schema": BENCH_SCHEMA,
            "git_rev": _git_rev(),
            "device_count": len(jax.devices()),
            "rows": [{"bench": b, "variant": v, "metric": m, "value": val}
                     for b, v, m, val in paper_benches.ROWS],
            "bench_seconds": timings,
            "metrics": metrics,
        }
        d = os.path.dirname(args.json)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {len(payload['rows'])} rows to {args.json}",
              file=sys.stderr)


if __name__ == "__main__":
    main()
