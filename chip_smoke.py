#!/usr/bin/env python3
"""Smoke test of the system's main path on a TPU.

    python chip_smoke.py                    # one chip, full size
    python chip_smoke.py --triples 400000   # a faster rehearsal
    python chip_smoke.py --chips 4          # only the sharded 4-chip path

Everything runs in this one process.  The phases:

1. **device** -- JAX must report a TPU; anything else (a CPU-only host,
   ``JAX_PLATFORMS=cpu``) exits non-zero before any work is done.
2. **kernels** (one chip only) -- the three Pallas join kernels
   (``semijoin``, ``join_count``, ``pair_semijoin``) compiled for the
   chip, each compared with its ``kernels.ref`` oracle or a numpy one.
3. **plan** -- a WatDiv-shaped graph (``generate_watdiv``), a 2000-query
   workload and a vertical 4-site ``PartitionPlan``.
4. **serve** -- ``Session(plan, backend="spmd")`` behind
   ``Session.serve()`` answers the star / chain / cycle queries of
   ``smoke_queries``, every answer set compared with
   ``core.matching.match_pattern`` over the whole graph; then each query
   once more, timed, with everything compiled.

The size: WatDiv (Aluc et al., ISWC 2014) at scale factor 100 is about
10M triples; the default ``--triples 4000000`` (about 3.4M stored
triples after dedup, about 0.9M vertex ids) is cut to about SF35 to stay
under the ``MAX_VERTEX_ID`` bound of ``repro.constants``.

Any failed phase, answer mismatch, interpret-mode kernel or uncounted
kernel path exits non-zero and prints no result.  On success the last
line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The per-query times printed are smoke timings of one run, not a
benchmark.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

#: WatDiv scale factor 100 (the published deployment), in triples
SF100_TRIPLES = 10_000_000

#: starting binding-table capacity tier per device: the largest
#: intermediate table of the served shapes (1,152,410 rows for chain2
#: at the default size) fits, so no shape climbs the retry ladder
CAPACITY = 1 << 21

#: workload templates served (indices into ``watdiv_templates``):
#: L1 and L3 chains, S1 and S3 stars
TEMPLATES = {"chain2": 0, "chain3": 2, "star2": 3, "star3": 5}


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ----------------------------------------------------------------------
# compile accounting (jax's own monitoring events)
# ----------------------------------------------------------------------

class CompileMeter:
    """Backend compiles and their seconds, from jax's monitoring
    events, plus persistent-cache hits."""

    #: the duration event jax records for every backend compile
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax) -> None:
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.count, self.seconds, self.cache_hits


# ----------------------------------------------------------------------
# phases
# ----------------------------------------------------------------------

def phase_device(jax, chips: int):
    devs = jax.devices()
    platform = devs[0].platform
    check(platform == "tpu",
          f"no TPU: JAX found platform {platform!r} "
          f"({len(devs)} {devs[0].device_kind!r} device(s))")
    check(len(devs) >= chips,
          f"--chips {chips} needs {chips} TPU devices, JAX found "
          f"{len(devs)}")
    log(f"device: {platform} kind={devs[0].device_kind!r} "
        f"count={len(devs)}")
    return devs


def kernel_cases(seed: int):
    """The three join kernels at the smoke's shapes: name -> (op, numpy
    operands).  ``tests/test_tpu_compile.py`` compiles the same calls
    for a described chip."""
    import numpy as np

    from repro.kernels import ops
    rng = np.random.default_rng(seed)

    def ids(hi, *shape):
        return rng.integers(0, hi, shape).astype(np.int32)

    table = np.sort(ids(1 << 16, 65536))
    return {
        "semijoin": (functools.partial(ops.semijoin, jit_safe=True),
                     (ids(1 << 16, 16384), table)),
        "join_count": (functools.partial(ops.join_count, jit_safe=True),
                       (ids(1 << 16, 16384), table)),
        "pair_semijoin": (functools.partial(ops.pair_semijoin,
                                            jit_safe=True),
                          (ids(512, 16384), ids(512, 16384),
                           ids(512, 32768), ids(512, 32768))),
    }


def phase_kernels(seed: int) -> None:
    """The three join kernels, compiled (never interpreted) on the chip,
    against the ``kernels.ref`` oracles and numpy on seeded data."""
    import numpy as np

    from repro.kernels import ops, ref
    check(not ops._interpret_default(None),
          "kernels would run in interpret mode on this TPU")
    cases = kernel_cases(seed)

    def run(name):
        op, args = cases[name]
        return op(*args), args

    def same(name, got, want):
        got, want = np.asarray(got), np.asarray(want)
        check(got.shape == want.shape and np.array_equal(got, want),
              f"kernel {name} disagrees with its oracle")
        log(f"kernel {name}: exact vs oracle, shape {got.shape}")

    for name, oracle in (("semijoin", ref.semijoin_mask_ref),
                         ("join_count", ref.join_count_ref)):
        got, args = run(name)
        same(name, got, oracle(*args))
    # numpy pair membership: the jnp oracle's three-key sort alone takes
    # minutes to compile for the chip
    got, (qs, qo, ts, to) = run("pair_semijoin")
    same("pair_semijoin", got,
         np.isin((qs.astype(np.int64) << 32) | qo,
                 (ts.astype(np.int64) << 32) | to))


def phase_plan(triples: int, seed: int):
    from repro.core import (PartitionConfig, build_plan, generate_watdiv,
                            generate_workload)
    t0 = time.perf_counter()
    g = generate_watdiv(triples, seed=seed)
    wl = generate_workload(g, 2000, seed=seed + 1)
    plan = build_plan(g, wl, PartitionConfig(kind="vertical", num_sites=4))
    secs = time.perf_counter() - t0
    sf = 100.0 * triples / SF100_TRIPLES
    log(f"plan: requested {triples} triples (WatDiv SF100 = "
        f"{SF100_TRIPLES}, cut to about SF{sf:.0f}), stored "
        f"{g.num_edges} triples, {g.num_vertices} vertex ids, "
        f"{g.num_properties} properties; vertical plan over "
        f"{plan.num_sites} sites built in {secs:.3f} s")
    return g, wl, plan


def smoke_queries(g, wl, seed: int):
    """The served query list: for each template of ``TEMPLATES`` the
    first four constant-bound workload queries (their constants come
    from the workload's pool, so most answers are empty) plus two with a
    constant drawn from the data (a vertex in the bound position that
    has edges of its property, so answers are usually non-empty); and
    four triangles ``?a follows ?b . ?a likes ?p . ?b likes ?p`` with a
    bound ``?b`` -- the cycle-close probe."""
    import numpy as np

    from repro.core.query import QueryGraph
    from repro.core.workload import PROP, watdiv_templates
    rng = np.random.default_rng(seed)
    tmpl = watdiv_templates()
    out = []

    def data_vertex(prop: int, subject: bool) -> int:
        ids = np.flatnonzero(np.asarray(g.p) == prop)
        e = int(ids[rng.integers(0, len(ids))])
        return int((g.s if subject else g.o)[e])

    def bind(q, var: int, value: int):
        return QueryGraph.make([(value if e.src == var else e.src,
                                 value if e.dst == var else e.dst, e.prop)
                                for e in q.edges])

    for name, ti in TEMPLATES.items():
        picked = [q for q, tid in zip(wl.queries, wl.template_ids)
                  if tid == ti and q.constants()][:4]
        t = tmpl[ti]
        e0 = t.edges[0]
        for _ in range(2):
            picked.append(bind(t, e0.src, data_vertex(e0.prop, True)))
        out += [(name, q) for q in picked]
    tri = QueryGraph.make([(-1, -2, PROP["follows"]),
                           (-1, -3, PROP["likes"]),
                           (-2, -3, PROP["likes"])])
    for _ in range(2):
        out.append(("cycle3", bind(tri, -2,
                                   data_vertex(PROP["follows"], False))))
    for q in [q for q in wl.queries if q.constants()][:2]:
        out.append(("cycle3", bind(tri, -2, q.constants()[0])))
    return out


def answer_rows(columns):
    """(sorted variables, unique rows) of a var -> column mapping."""
    import numpy as np
    names = sorted(columns)
    if not names:
        return names, np.zeros((0, 0), np.int32)
    rows = np.stack([np.asarray(columns[v], np.int64) for v in names], 1)
    return names, np.unique(rows, axis=0)


def phase_serve(g, plan, queries, chips: int, meter):
    import jax
    import numpy as np

    from repro.core import Session
    from repro.core.matching import _PropIndex, match_pattern
    from repro.core.spmd import TPU_KERNELS
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(chips, axis="sites")
    t0 = time.perf_counter()
    session = Session(plan, backend="spmd", mesh=mesh,
                      spmd_capacity=CAPACITY,
                      spmd_max_capacity=2 * CAPACITY)
    log(f"serve: SPMD session on a {chips}-device mesh, capacity tier "
        f"{CAPACITY} rows/device (max {2 * CAPACITY}), store built in "
        f"{time.perf_counter() - t0:.3f} s")
    engine = session.engine
    store = engine.store
    for name in ("csr_sub_s", "csr_sub_o", "csr_obj_o", "csr_obj_s",
                 "csr_offs", "owned"):
        arr = getattr(store, name)
        shards = arr.addressable_shards
        check(len(shards) == chips
              and all(sh.data.shape[0] == 1 for sh in shards)
              and arr.sharding.spec[0] == "sites",
              f"SiteStore.{name} is not row-sharded over the mesh "
              f"({arr.sharding})")
    log(f"serve: SiteStore arrays row-sharded over {chips} device(s), "
        f"{store.e_max} rows per device")

    idx = _PropIndex(g)
    t0 = time.perf_counter()
    want = []
    for _name, q in queries:
        ref = match_pattern(g, q, idx)
        check(not ref.truncated, f"reference truncated for {q.edges}")
        want.append(answer_rows(ref.columns))
    log(f"serve: reference answers for {len(queries)} queries in "
        f"{time.perf_counter() - t0:.3f} s")

    c0 = meter.snapshot()
    t0 = time.perf_counter()
    with session.serve(max_batch=16, max_delay_ms=5.0,
                       default_deadline_s=3600.0) as door:
        futs = [door.submit(q) for _name, q in queries]
        got = [f.result(timeout=3600.0) for f in futs]
        warm_s = time.perf_counter() - t0
        c1 = meter.snapshot()
        mismatches = 0
        nonempty = 0
        for (name, q), res, (wv, wrows) in zip(queries, got, want):
            gv, grows = answer_rows(res.bindings)
            if gv != wv or not np.array_equal(grows, wrows):
                mismatches += 1
                log(f"MISMATCH {name} {q.edges}: served {len(grows)} rows, "
                    f"reference {len(wrows)}")
            nonempty += len(wrows) > 0
        log(f"serve: first pass (compiles included) {len(queries)} "
            f"queries in {warm_s:.3f} s; {nonempty} non-empty answers, "
            f"{sum(len(w[1]) for w in want)} answer rows in all")
        log(f"compiles: {c1[0] - c0[0]} backend compiles, "
            f"{c1[1] - c0[1]:.3f} s compiling, {c1[2] - c0[2]} "
            f"persistent-cache hits; {session.stats().extra['compiled_shapes']:.0f}"
            f" matchers")
        # second pass: every shape compiled and its capacity tier known
        times = {}
        for (name, q), (wv, wrows) in zip(queries, want):
            t1 = time.perf_counter()
            res = door.execute(q, timeout=3600.0)
            times.setdefault(name, []).append(time.perf_counter() - t1)
            gv, grows = answer_rows(res.bindings)
            if gv != wv or not np.array_equal(grows, wrows):
                mismatches += 1
                log(f"MISMATCH (timed pass) {name} {q.edges}")
        c2 = meter.snapshot()
    extra = session.stats().extra
    log(f"answer-set mismatches vs match_pattern: {mismatches}")
    paths = {k[len("traced_"):]: int(v) for k, v in extra.items()
             if k.startswith("traced_")}
    log("join steps traced per path: " + json.dumps(paths, sort_keys=True))
    log(f"kernels on: {extra['pallas_join_kernels']:.0f} of the match "
        f"loop's two ({', '.join(TPU_KERNELS)}), interpret "
        f"mode: {bool(extra['pallas_interpret'])}; capacity retries "
        f"{extra['capacity_retries']:.0f}, overflow events "
        f"{extra['overflow_events']:.0f}, routed queries "
        f"{extra['routed_queries']:.0f}")
    shipped = {k: int(extra[k]) for k in ("gather_steps",
                                          "edge_shipped_steps",
                                          "edge_cache_hits",
                                          "skipped_gathers")}
    log("join-step shipping: " + json.dumps(shipped))
    log(f"compiles in the timed pass: {c2[0] - c1[0]}")
    for name, ts in times.items():
        log(f"smoke timing (one run, not a benchmark) {name}: "
            f"{len(ts)} queries, median {statistics.median(ts):.6f} s, "
            f"max {max(ts):.6f} s")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    log(f"peak_bytes_in_use per device: {peaks}")

    check(mismatches == 0, f"{mismatches} answer sets differ from "
          f"match_pattern")
    check(extra["pallas_join_kernels"] == len(TPU_KERNELS),
          "the match loop's TPU kernels are not all on")
    check(extra["pallas_interpret"] == 0.0,
          "the Pallas join kernels ran in interpret mode")
    kernel_steps = sum(v for k, v in paths.items()
                       if k.endswith("_kernel"))
    check(kernel_steps > 0, "no join step traced a Pallas kernel")
    check(chips == 1 or shipped["gather_steps"]
          + shipped["edge_shipped_steps"] > 0,
          "no join step shipped rows between the devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--triples", type=int, default=4_000_000,
                    help="requested graph size (generate_watdiv)")
    ap.add_argument("--seed", type=int, default=1,
                    help="graph seed; the workload uses seed + 1")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: kernels + plan + serve on one chip; 4: only "
                         "the sharded serve path on a 4-device mesh")
    args = ap.parse_args(argv)

    phase = "device"
    try:
        import jax
        devs = phase_device(jax, args.chips)
        sys.path.insert(0, SRC)
        from repro.launch.compile_cache import enable_compile_cache
        log(f"compile cache: {enable_compile_cache()}")
        meter = CompileMeter(jax)
        if args.chips == 1:
            phase = "kernels"
            phase_kernels(args.seed)
        phase = "plan"
        g, wl, plan = phase_plan(args.triples, args.seed)
        phase = "serve"
        queries = smoke_queries(g, wl, args.seed + 2)
        log(f"serve: {len(queries)} queries: " + ", ".join(
            f"{n} x{sum(1 for m, _ in queries if m == n)}"
            for n in dict.fromkeys(n for n, _ in queries)))
        phase_serve(g, plan, queries, args.chips, meter)
    except Exception as exc:            # any phase failing fails the run
        print(f"chip_smoke: FAILED in phase {phase}: "
              f"{type(exc).__name__}: {exc}", file=sys.stderr, flush=True)
        if not isinstance(exc, SmokeFailure):
            import traceback
            traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
