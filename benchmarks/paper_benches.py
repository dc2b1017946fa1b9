"""One benchmark per paper table/figure (§8), on WatDiv-like data.

Emits CSV rows: ``bench,variant,metric,value``.  Absolute numbers are
host-dependent; the paper's *claims* are orderings and trends, asserted
in EXPERIMENTS.md §Paper-validation.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

import numpy as np

from repro.core import (BACKENDS, PartitionConfig, Session, build_plan,
                        generate_watdiv, generate_workload,
                        simulate_throughput)
from repro.core.matching import match_pattern
from repro.core.workload import TEMPLATE_CLASS

ROWS: List[Tuple[str, str, str, float]] = []

STRATEGY_OF = {"VF": "vertical", "HF": "horizontal",
               "SHAPE": "shape", "WARP": "warp"}


def emit(bench: str, variant: str, metric: str, value: float) -> None:
    ROWS.append((bench, variant, metric, value))
    print(f"{bench},{variant},{metric},{value:.6g}")


def _setup(n_triples=30_000, n_queries=2_000, sites=10, seed=1):
    g = generate_watdiv(n_triples, seed=seed)
    wl = generate_workload(g, n_queries, seed=seed + 1)
    return g, wl


def _plans(g, wl, sites=10):
    return {name: build_plan(g, wl, PartitionConfig(kind=kind,
                                                    num_sites=sites))
            for name, kind in STRATEGY_OF.items()}


def _engines(g, wl, sites=10):
    """name -> (Session, plan): workload-driven plans run on the exact
    local backend, hash/min-cut baselines on the gather-all backend."""
    out = {}
    for name, plan in _plans(g, wl, sites).items():
        backend = "local" if plan.frag is not None else "baseline"
        out[name] = (Session(plan, backend=backend), plan)
    return out


# ----------------------------------------------------------------------
# Fig. 8: effect of minSup on #FAPs and workload hit rate
# ----------------------------------------------------------------------

def bench_minsup() -> None:
    g, wl = _setup()
    for frac in [0.0005, 0.001, 0.005, 0.01, 0.05]:
        plan = build_plan(g, wl, PartitionConfig(
            min_sup_fraction=frac, num_sites=10))
        emit("fig8_minsup", f"{frac:g}", "num_faps",
             plan.stats.num_patterns_mined)
        emit("fig8_minsup", f"{frac:g}", "hit_rate", plan.stats.hit_rate)


# ----------------------------------------------------------------------
# Fig. 9 / Fig. 10: throughput + response time per strategy
# ----------------------------------------------------------------------

def bench_throughput() -> None:
    g, wl = _setup()
    engines = _engines(g, wl)
    sample = wl.queries[: len(wl.queries) // 10]   # paper samples 1%
    for name, (eng, _) in engines.items():
        thr, _ = simulate_throughput(eng, sample)
        emit("fig9_throughput", name, "queries_per_min", thr)


def bench_response() -> None:
    g, wl = _setup()
    engines = _engines(g, wl)
    sample = wl.queries[: len(wl.queries) // 10]
    for name, (eng, _) in engines.items():
        rts = [eng.execute(q).stats.response_time for q in sample]
        emit("fig10_response", name, "avg_response_sec", float(np.mean(rts)))
        emit("fig10_response", name, "p95_response_sec",
             float(np.percentile(rts, 95)))


# ----------------------------------------------------------------------
# Fig. 11: scalability with dataset size
# ----------------------------------------------------------------------

def bench_scalability() -> None:
    for n in [10_000, 20_000, 40_000, 80_000]:
        g, wl = _setup(n_triples=n, n_queries=800, seed=3)
        eng = Session(build_plan(g, wl, PartitionConfig(
            kind="vertical", num_sites=10)))
        sample = wl.queries[:80]
        thr, _ = simulate_throughput(eng, sample)
        rts = [eng.execute(q).stats.response_time for q in sample]
        emit("fig11_scalability", f"{n}", "queries_per_min", thr)
        emit("fig11_scalability", f"{n}", "avg_response_sec",
             float(np.mean(rts)))


# ----------------------------------------------------------------------
# Table 1: redundancy ratios
# ----------------------------------------------------------------------

def bench_redundancy() -> None:
    g, wl = _setup()
    for name, plan in _plans(g, wl).items():
        emit("table1_redundancy", name, "ratio", plan.redundancy_ratio())


# ----------------------------------------------------------------------
# Table 2: partitioning (offline) time
# ----------------------------------------------------------------------

def bench_offline() -> None:
    g, wl = _setup()
    for kind in ["vertical", "horizontal"]:
        t0 = time.perf_counter()
        plan = build_plan(g, wl, PartitionConfig(kind=kind, num_sites=10))
        total = time.perf_counter() - t0
        s = plan.stats
        name = "VF" if kind == "vertical" else "HF"
        emit("table2_offline", name, "mine_sec", s.mine_sec)
        emit("table2_offline", name, "select_sec", s.select_sec)
        emit("table2_offline", name, "fragment_sec", s.fragment_sec)
        emit("table2_offline", name, "allocate_sec", s.allocate_sec)
        emit("table2_offline", name, "total_sec", total)
    for name, kind in [("SHAPE", "shape"), ("WARP", "warp")]:
        t0 = time.perf_counter()
        build_plan(g, wl, PartitionConfig(kind=kind, num_sites=10))
        emit("table2_offline", name, "total_sec", time.perf_counter() - t0)


# ----------------------------------------------------------------------
# Fig. 12: per-query-class (L/S/F/C) response times
# ----------------------------------------------------------------------

def bench_queries() -> None:
    g, wl = _setup()
    engines = _engines(g, wl)
    by_class: Dict[str, List[int]] = {}
    for i, tid in enumerate(wl.template_ids or []):
        if tid is None or tid < 0 or i >= 400:
            continue
        by_class.setdefault(TEMPLATE_CLASS[tid], []).append(i)
    for cls in sorted(by_class):
        idxs = by_class[cls][:25]
        for name, (eng, _) in engines.items():
            rts = [eng.execute(wl.queries[i]).stats.response_time
                   for i in idxs]
            emit("fig12_query_classes", f"{name}_{cls}", "avg_response_sec",
                 float(np.mean(rts)))


# ----------------------------------------------------------------------
# Engine parity: the same plan + query set through every Session backend
# must produce identical answer counts (and match direct matching on the
# whole graph).  This is the CI smoke bench (`benchmarks.run --smoke`):
# a regression in any backend's execution path surfaces as mismatches>0.
# ----------------------------------------------------------------------

def bench_engine_parity() -> None:
    g = generate_watdiv(5_000, seed=2)
    wl = generate_workload(g, 400, seed=3)
    plan = build_plan(g, wl, PartitionConfig(kind="vertical", num_sites=4))
    sample = wl.queries[:16]
    want = [match_pattern(g, q).num_rows for q in sample]
    for backend in BACKENDS:
        t0 = time.perf_counter()
        # default SPMD capacity: the overflow auto-retry keeps the
        # answers exact, so no need to oversize the binding tables
        sess = Session(plan, backend=backend)
        rows = [r.num_rows for r in sess.execute_many(sample, batch_size=8)]
        dt = time.perf_counter() - t0
        emit("engine_parity", backend, "mismatches",
             sum(a != b for a, b in zip(rows, want)))
        emit("engine_parity", backend, "wall_sec", dt)
        emit("engine_parity", backend, "rows", sum(rows))
        if backend == "spmd":
            emit("engine_parity", backend, "capacity_retries",
                 sess.stats().extra["capacity_retries"])


# ----------------------------------------------------------------------
# SPMD vs local communication cost: the same plan + star/chain/cycle
# queries served by the host engine (ship-the-smaller-side joins along
# the optimized plan) and by the SPMD backend twice -- naive (all_gather
# the binding tables before every join step) and planned (the size-aware
# communication planner: ship the smaller of bindings vs. edge rows,
# skip shard-complete steps).  All are renderings of §7.3's "ship
# intermediate results"; the bench records the byte ledgers side by
# side per query shape.  On this seeded workload the planned ledger
# never exceeds the naive one (strictly lower wherever a skip or an
# edge-ship fires) -- an empirical, per-workload property the
# `planned_leq_naive` row reports; plus the SPMD capacity-retry
# behaviour under the default (not oversized) binding-table capacity.
# ----------------------------------------------------------------------

def _shape_workload(g, per_shape: int = 4, seed: int = 9):
    """star/chain/cycle query shapes (the shared ``make_shape_queries``
    definition) with edge properties sampled frequency-weighted from
    the graph, so joins actually produce rows."""
    from repro.core import make_shape_queries
    rng = np.random.default_rng(seed)
    p = np.asarray(g.p)

    def rp() -> int:
        return int(p[rng.integers(0, len(p))])

    shapes: Dict[str, list] = {"star": [], "chain": [], "cycle": []}
    for _ in range(per_shape):
        for name, q in make_shape_queries(rp).items():
            shapes[name].append(q)
    return shapes


def _ledger_comparison(bench: str, g, sessions: Dict[str, Session]
                       ) -> Tuple[Dict[str, Dict[str, int]],
                                  Dict[str, int]]:
    """Shared scaffold of the SPMD ledger benches: run the star/chain/
    cycle workload through every session, emit per-shape mismatch/
    comm/wall rows and per-session totals, and return (shape ->
    session -> shipped bytes, session -> total bytes) for the closing
    comparisons."""
    totals = {name: 0 for name in sessions}
    per_shape: Dict[str, Dict[str, int]] = {}
    for shape, qs in _shape_workload(g).items():
        want = [match_pattern(g, q).num_rows for q in qs]
        by_session: Dict[str, int] = {}
        for name, sess in sessions.items():
            before = sess.stats().comm_bytes
            t0 = time.perf_counter()
            rows = [sess.execute(q).num_rows for q in qs]
            dt = time.perf_counter() - t0
            shipped = sess.stats().comm_bytes - before
            totals[name] += shipped
            by_session[name] = shipped
            emit(bench, f"{name}_{shape}", "mismatches",
                 sum(a != b for a, b in zip(rows, want)))
            emit(bench, f"{name}_{shape}", "comm_bytes", float(shipped))
            emit(bench, f"{name}_{shape}", "wall_sec", dt)
        per_shape[shape] = by_session
    for name in sessions:
        emit(bench, name, "comm_bytes_total", float(totals[name]))
    return per_shape, totals


def bench_spmd_comm() -> None:
    g, wl = _setup(n_triples=8_000, n_queries=500, seed=5)
    plan = build_plan(g, wl, PartitionConfig(kind="vertical", num_sites=4))
    sessions = {
        "local": Session(plan, backend="local"),
        "spmd_naive": Session(plan, backend="spmd", spmd_comm_plan=False),
        "spmd_planned": Session(plan, backend="spmd"),
    }
    _, totals = _ledger_comparison("spmd_comm", g, sessions)
    st = sessions["spmd_planned"].stats()
    for key in ("gather_steps", "edge_shipped_steps", "skipped_gathers",
                "comm_bytes_saved", "capacity_retries", "overflow_events",
                "devices"):
        emit("spmd_comm", "spmd_planned", key, st.extra[key])
    emit("spmd_comm", "planned_vs_naive", "planned_leq_naive",
         float(totals["spmd_planned"] <= totals["spmd_naive"]))


# ----------------------------------------------------------------------
# Allocation-aware replication: the same plan built twice -- PR-4 style
# (size-aware comm planning only) and with the budgeted replication pass
# (`replication_budget_bytes`), serving the same star/chain/cycle
# workload on the SPMD backend.  Replicated hot properties are
# shard-complete, so their join steps skip the collective and
# replicated-seed queries decimate their seeds across the mesh; the
# acceptance property is that the replicated ledger never exceeds the
# planned one on any shape and is strictly lower on at least one
# (`replicated_leq_planned_all` / `replicated_lt_planned_any` rows).
# Both sessions run at the same oversized capacity so neither pays
# retry tiers and the ledgers compare like for like.
# ----------------------------------------------------------------------

def bench_spmd_replication() -> None:
    g, wl = _setup(n_triples=8_000, n_queries=500, seed=5)
    budget = 500_000
    plans = {
        "spmd_planned": build_plan(g, wl, PartitionConfig(
            kind="vertical", num_sites=4)),
        "spmd_replicated": build_plan(g, wl, PartitionConfig(
            kind="vertical", num_sites=4,
            replication_budget_bytes=budget)),
    }
    emit("spmd_replication", "spmd_replicated", "replicated_props",
         float(len(plans["spmd_replicated"].replicated_props)))
    emit("spmd_replication", "spmd_replicated", "replica_budget_bytes",
         float(budget))
    emit("spmd_replication", "spmd_replicated", "replica_spent_bytes",
         float(plans["spmd_replicated"].replication.spent_bytes))
    sessions = {name: Session(plan, backend="spmd", spmd_capacity=16384)
                for name, plan in plans.items()}
    per_shape, _ = _ledger_comparison("spmd_replication", g, sessions)
    st = sessions["spmd_replicated"].stats()
    for key in ("skipped_gathers", "replication_skipped_steps",
                "decimated_seed_queries", "edge_cache_hits",
                "gather_steps", "edge_shipped_steps",
                "capacity_retries", "devices"):
        emit("spmd_replication", "spmd_replicated", key, st.extra[key])
    emit("spmd_replication", "replicated_vs_planned",
         "replicated_leq_planned_all",
         float(all(v["spmd_replicated"] <= v["spmd_planned"]
                   for v in per_shape.values())))
    emit("spmd_replication", "replicated_vs_planned",
         "replicated_lt_planned_any",
         float(any(v["spmd_replicated"] < v["spmd_planned"]
                   for v in per_shape.values())))


# ----------------------------------------------------------------------
# Replica-aware routing: the same plan served by the routed SPMD engine
# (default) and the whole-mesh engine (`spmd_routing=False`) on the
# star/chain/cycle workload.  Routing masks non-resident sites out of
# every collective (peer factor = route width - 1) and rendezvous-pins
# fully-replicated queries to one device, so the acceptance property is
# the routed ledger never exceeding the whole-mesh ledger on any shape
# and strictly undercutting it on at least one
# (`routed_leq_unrouted_all` / `routed_lt_unrouted_any` rows).  Both
# sessions run at the same oversized capacity so neither pays retry
# tiers and the ledgers compare like for like.
# ----------------------------------------------------------------------

def bench_spmd_routing() -> None:
    g, wl = _setup(n_triples=8_000, n_queries=500, seed=5)
    plan = build_plan(g, wl, PartitionConfig(
        kind="vertical", num_sites=4,
        replication_budget_bytes=500_000))
    sessions = {
        "spmd_unrouted": Session(plan, backend="spmd",
                                 spmd_capacity=16384,
                                 spmd_routing=False),
        "spmd_routed": Session(plan, backend="spmd",
                               spmd_capacity=16384),
    }
    per_shape, _ = _ledger_comparison("spmd_routing", g, sessions)
    st = sessions["spmd_routed"].stats()
    for key in ("routed_queries", "route_skipped_steps",
                "skipped_gathers", "decimated_seed_queries",
                "gather_steps", "edge_shipped_steps",
                "capacity_retries", "devices"):
        emit("spmd_routing", "spmd_routed", key, st.extra[key])
    emit("spmd_routing", "routed_vs_unrouted", "routed_leq_unrouted_all",
         float(all(v["spmd_routed"] <= v["spmd_unrouted"]
                   for v in per_shape.values())))
    emit("spmd_routing", "routed_vs_unrouted", "routed_lt_unrouted_any",
         float(any(v["spmd_routed"] < v["spmd_unrouted"]
                   for v in per_shape.values())))


# ----------------------------------------------------------------------
# Serving front door (repro.serve): three claims on one seeded
# star/chain/cycle workload.  (1) Parity -- answers through the full
# admission -> micro-batch -> dispatch path are set-identical to direct
# Session.execute on every backend.  (2) Amortization -- shape-keyed
# micro-batched SPMD dispatch (one device run per shape group,
# `batch_shape_hits` reuses) beats the sequential per-query baseline on
# the same offered load (`batched_ge_seq` row).  (3) The RFC-003
# capacity model -- offered load at 1x/4x/16x of the measured
# sequential base rate, reporting achieved qps (and qps/device),
# p50/p99 admission-to-completion latency, and the shed rate per tier.
# ----------------------------------------------------------------------

def _answer_set(res):
    """(sorted vars, set of binding tuples) -- order-insensitive
    answer identity."""
    vars_sorted = sorted(res.bindings)
    cols = [np.asarray(res.bindings[v]).tolist() for v in vars_sorted]
    return tuple(vars_sorted), set(zip(*cols)) if cols else set()


def bench_serve() -> None:
    from repro.serve import FrontDoor, FrontDoorConfig, measure_capacity

    g, wl = _setup(n_triples=8_000, n_queries=500, seed=5)
    plan = build_plan(g, wl, PartitionConfig(kind="vertical", num_sites=4))
    queries = [q for qs in _shape_workload(g).values() for q in qs]

    # (1) served-vs-direct parity, every backend
    for backend in BACKENDS:
        sess = Session(plan, backend=backend)
        direct = [sess.execute(q) for q in queries]
        with sess.serve(max_batch=8, max_delay_ms=1.0) as door:
            futs = [door.submit(q, deadline_s=120.0) for q in queries]
            served = [f.result(timeout=120) for f in futs]
        emit("bench_serve", backend, "parity_mismatches",
             float(sum(_answer_set(a) != _answer_set(b)
                       for a, b in zip(direct, served))))

    # (2) sequential per-query dispatch vs shape-keyed micro-batching,
    # same queries, same engine, jit cache warm for both arms
    sess = Session(plan, backend="spmd")
    offered = queries * 4
    sess.execute_many(queries, batch_size=len(queries))      # warm-up
    t0 = time.perf_counter()
    for q in offered:
        sess.execute(q)
    wall_seq = time.perf_counter() - t0
    t0 = time.perf_counter()
    sess.execute_many(offered, batch_size=len(offered))
    wall_batched = time.perf_counter() - t0
    emit("bench_serve", "spmd", "qps_sequential",
         len(offered) / max(wall_seq, 1e-12))
    emit("bench_serve", "spmd", "qps_batched",
         len(offered) / max(wall_batched, 1e-12))
    emit("bench_serve", "spmd", "batch_shape_hits",
         sess.stats().extra["batch_shape_hits"])
    emit("bench_serve", "spmd_batched_vs_seq", "batched_ge_seq",
         float(wall_batched <= wall_seq))

    # (3) capacity model: fresh door per tier over the warm session
    t0 = time.perf_counter()
    for q in queries:
        sess.execute(q)
    base_qps = len(queries) / max(time.perf_counter() - t0, 1e-12)
    emit("bench_serve", "capacity", "base_qps", base_qps)
    reports = measure_capacity(
        lambda: FrontDoor(sess, FrontDoorConfig(
            max_queue=256, max_batch=8, max_delay_ms=2.0)),
        queries, base_qps, multipliers=(1.0, 4.0, 16.0),
        duration_s=1.0, seed=11, deadline_s=5.0)
    n_dev = sess.stats().extra["devices"]
    for rep in reports:
        variant = f"load_{rep.offered_multiplier:g}x"
        for metric, value in rep.to_row().items():
            emit("bench_serve", variant, metric, float(value))
        emit("bench_serve", variant, "qps_per_device",
             rep.achieved_qps / max(n_dev, 1.0))


ALL = [bench_minsup, bench_throughput, bench_response, bench_scalability,
       bench_redundancy, bench_offline, bench_queries, bench_engine_parity,
       bench_spmd_comm, bench_spmd_replication, bench_spmd_routing,
       bench_serve]

SMOKE = [bench_engine_parity, bench_spmd_routing]
