"""Telemetry layer unit tests: span tracer, metrics registry,
exporters, and the engine-side wiring (root spans, metric publication,
hook-error isolation)."""
import json

import pytest

from repro.core import PartitionConfig, Session, build_plan
from repro.core import generate_watdiv, generate_workload
from repro.obs.export import (REQUIRED_METRICS, SNAPSHOT_SCHEMA, dump_spans,
                              registry_from_snapshot, snapshot, to_prom_text,
                              validate_snapshot)
from repro.obs.metrics import (Gauge, Histogram, MetricsRegistry,
                               get_registry, set_registry)
from repro.obs.trace import (NULL_SPAN, TraceStore, Tracer, enable_tracing,
                             get_tracer, set_tracer)


class FakeClock:
    """Deterministic monotonic clock: each call advances by ``step``."""

    def __init__(self, step=1.0):
        self.t = 0.0
        self.step = step

    def __call__(self):
        self.t += self.step
        return self.t


# ----------------------------------------------------------------------
# Tracer / spans
# ----------------------------------------------------------------------

def test_span_nesting_and_ordering():
    tr = Tracer(enabled=True, clock=FakeClock())
    with tr.span("query", backend="x") as root:
        with tr.span("site_match", subquery=0) as a:
            a.set("rows", 3)
        with tr.span("join", subquery=1) as b:
            with tr.span("inner") as c:
                assert tr.current is c
    assert tr.current is None
    roots = tr.store.spans()
    assert len(roots) == 1 and roots[0] is root
    assert [s.name for s in root.walk()] == ["query", "site_match", "join",
                                             "inner"]
    assert a.parent_id == root.span_id
    assert b.parent_id == root.span_id
    assert c.parent_id == b.span_id
    assert {s.trace_id for s in root.walk()} == {root.trace_id}
    # fake clock: start/end strictly ordered, duration deterministic
    assert root.start < a.start < a.end <= b.start < c.start
    assert root.end > c.end
    assert root.duration > 0
    assert root.attrs == {"backend": "x"} and a.attrs["rows"] == 3


def test_two_roots_get_distinct_traces():
    tr = Tracer(enabled=True, clock=FakeClock())
    with tr.span("query"):
        pass
    with tr.span("query"):
        pass
    r1, r2 = tr.store.spans()
    assert r1.trace_id != r2.trace_id
    assert tr.store.finished_total == 2


def test_ring_buffer_caps_memory():
    tr = Tracer(enabled=True, clock=FakeClock(), capacity=4)
    for i in range(10):
        with tr.span("query", i=i):
            pass
    assert len(tr.store) == 4
    assert tr.store.finished_total == 10
    assert [s.attrs["i"] for s in tr.store.spans()] == [6, 7, 8, 9]


def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    sp = tr.span("query", backend="x")
    assert sp is NULL_SPAN                  # shared instance, no alloc
    with sp as inner:
        inner.set("rows", 1)                # all no-ops
        tr.annotate(rows=2)
        tr.add_record({"bytes": 3})
    assert len(tr.store) == 0 and tr.store.finished_total == 0
    assert NULL_SPAN.attrs == {} and NULL_SPAN.records == []


def test_exception_unwinds_span_stack():
    tr = Tracer(enabled=True, clock=FakeClock())
    with pytest.raises(RuntimeError):
        with tr.span("query"):
            with tr.span("join"):
                raise RuntimeError("boom")
    assert tr.current is None
    (root,) = tr.store.spans()
    assert root.end is not None
    assert all(s.end is not None for s in root.walk())
    # tracer still usable afterwards
    with tr.span("query"):
        pass
    assert tr.store.finished_total == 2


def test_add_record_lands_on_innermost_span():
    tr = Tracer(enabled=True, clock=FakeClock())
    with tr.span("query") as root:
        tr.add_record({"a": 1})
        with tr.span("child") as ch:
            tr.add_record({"b": 2})
    assert root.records == [{"a": 1}]
    assert ch.records == [{"b": 2}]


def test_store_jsonl_roundtrip(tmp_path):
    tr = Tracer(enabled=True, clock=FakeClock())
    with tr.span("query", backend="spmd"):
        tr.add_record({"bytes": 96})
        with tr.span("child"):
            pass
    path = tmp_path / "spans.jsonl"
    assert dump_spans(tr, str(path)) == 2
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert lines[0]["name"] == "query" and lines[0]["parent_id"] is None
    assert lines[0]["records"] == [{"bytes": 96}]
    assert lines[1]["parent_id"] == lines[0]["span_id"]


def test_default_tracer_swap_restores():
    prev = get_tracer()
    try:
        t = enable_tracing(capacity=8)
        assert get_tracer() is t and t.enabled
    finally:
        set_tracer(prev)
    assert get_tracer() is prev


def test_trace_store_rejects_bad_capacity():
    with pytest.raises(ValueError):
        TraceStore(0)


class MirrorSpy:
    """Fake profiler mirror: logs every annotation's enter and exit."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        spy = self

        class _Annotation:
            def __enter__(self):
                spy.log.append(("enter", name))

            def __exit__(self, *exc):
                spy.log.append(("exit", name, exc[0]))
        return _Annotation()


def test_mirror_wraps_every_span_and_unwinds_on_exception():
    spy = MirrorSpy()
    tr = Tracer(enabled=True, clock=FakeClock(), mirror=spy)
    with pytest.raises(RuntimeError):
        with tr.span("query"):
            with tr.span("match"):
                pass
            with tr.span("dedup"):
                raise RuntimeError("boom")
    assert spy.log == [("enter", "query"), ("enter", "match"),
                       ("exit", "match", None), ("enter", "dedup"),
                       ("exit", "dedup", RuntimeError),
                       ("exit", "query", RuntimeError)]
    assert tr.current is None and tr.store.finished_total == 1


def test_disabled_tracer_opens_no_mirror():
    spy = MirrorSpy()
    tr = Tracer(enabled=False, mirror=spy)
    with tr.span("query"):
        with tr.span("match"):
            pass
    assert spy.log == [] and len(tr.store) == 0


def _profiled(tmp_path, tracers):
    """Open ``query`` > (``match``, ``dedup``) on each tracer under a
    CPU profiler session; the host-plane events by name, as
    ``(start_ns, end_ns)``."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        for tr in tracers:
            with tr.span("query"):
                with tr.span("match"):
                    pass
                with tr.span("dedup"):
                    pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in ("query", "match", "dedup"):
                        events.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    return events


def test_spans_appear_nested_in_a_profiler_trace(tmp_path):
    ev = _profiled(tmp_path, [Tracer(enabled=True)])
    (q,), (m,), (d,) = ev["query"], ev["match"], ev["dedup"]
    assert q[0] <= m[0] <= m[1] <= d[0] <= d[1] <= q[1]


def test_disabled_tracer_writes_nothing_to_a_profiler_trace(tmp_path):
    assert _profiled(tmp_path, [Tracer(enabled=False)]) == {}


# ----------------------------------------------------------------------
# Histogram percentile math
# ----------------------------------------------------------------------

def test_histogram_bucket_edges_le_semantics():
    h = Histogram(buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 99.0):
        h.observe(v)
    # le semantics: a value equal to a bound lands in that bound's bucket
    assert h.counts == [2, 2, 2, 1]
    assert h.count == 7 and h.sum == pytest.approx(111.0)


def test_histogram_percentiles():
    h = Histogram(buckets=(1.0, 2.0, 4.0))
    assert h.percentile(0.5) == 0.0          # empty -> 0.0
    for _ in range(10):
        h.observe(1.5)                       # all in (1, 2]
    # all mass in one bucket: interpolation stays within (1, 2]
    assert 1.0 <= h.percentile(0.01) <= 2.0
    assert 1.0 <= h.percentile(0.99) <= 2.0
    assert h.percentile(1.0) == 2.0          # upper edge of the bucket
    h.observe(100.0)                         # +Inf bucket
    # rank in the overflow bucket reports the largest finite bound
    assert h.percentile(1.0) == 4.0
    with pytest.raises(ValueError):
        h.percentile(1.5)


def test_histogram_merge_and_rebucket_refusal():
    a = Histogram(buckets=(1.0, 2.0))
    b = Histogram(buckets=(1.0, 2.0))
    a.observe(0.5)
    b.observe(1.5)
    b.observe(9.0)
    a.merge(b)
    assert a.counts == [1, 1, 1] and a.count == 3
    with pytest.raises(ValueError):
        a.merge(Histogram(buckets=(1.0, 3.0)))
    with pytest.raises(ValueError):
        Histogram(buckets=())
    with pytest.raises(ValueError):
        Histogram(buckets=(2.0, 1.0))


# ----------------------------------------------------------------------
# Registry
# ----------------------------------------------------------------------

def test_registry_families_and_type_safety():
    reg = MetricsRegistry()
    c1 = reg.counter("repro_x_total", backend="a")
    c2 = reg.counter("repro_x_total", backend="b")
    assert c1 is not c2
    assert reg.counter("repro_x_total", backend="a") is c1
    with pytest.raises(TypeError):
        reg.gauge("repro_x_total", backend="a")
    reg.histogram("repro_h", buckets=(1.0,))
    with pytest.raises(ValueError):
        reg.histogram("repro_h", buckets=(2.0,))


def test_gauge_history_dedups_unchanged_sets():
    g = Gauge()
    g.set(1.0)
    g.set(1.0)
    g.set(2.0)
    g.set(2.0)
    assert g.value == 2.0
    assert [v for _, v in g.history] == [1.0, 2.0]


def test_registry_merge():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("c", backend="x").inc(2)
    b.counter("c", backend="x").inc(3)
    b.counter("c", backend="y").inc(7)
    b.gauge("g").set(5.0)
    b.histogram("h", buckets=(1.0, 2.0)).observe(1.5)
    a.merge(b)
    assert a.counter("c", backend="x").value == 5
    assert a.counter("c", backend="y").value == 7
    assert a.gauge("g").value == 5.0
    assert a.histogram("h", buckets=(1.0, 2.0)).count == 1


# ----------------------------------------------------------------------
# Exporters
# ----------------------------------------------------------------------

def _populated_registry():
    reg = MetricsRegistry()
    reg.counter("repro_queries_total", backend="local").inc(4)
    g = reg.gauge("repro_epochs", backend="adaptive")
    g.set(1.0)
    g.set(2.0)
    h = reg.histogram("repro_query_latency_seconds", backend="local")
    for v in (1e-4, 1e-3, 0.5, 20.0):
        h.observe(v)
    return reg


def test_snapshot_roundtrip_exact():
    reg = _populated_registry()
    doc = snapshot(registry=reg)
    assert doc["schema"] == SNAPSHOT_SCHEMA
    rebuilt = registry_from_snapshot(doc)
    assert snapshot(registry=rebuilt) == doc
    with pytest.raises(ValueError):
        registry_from_snapshot({"schema": "nope"})


def test_validate_snapshot():
    reg = _populated_registry()
    doc = snapshot(registry=reg)
    validate_snapshot(doc, required=("repro_queries_total",
                                     "repro_query_latency_seconds"))
    with pytest.raises(ValueError, match="missing"):
        validate_snapshot(doc, required=("repro_not_there_total",))
    with pytest.raises(ValueError, match="schema"):
        validate_snapshot({"schema": "other"}, required=())
    bad = snapshot(registry=reg)
    bad["histograms"][0]["counts"][0] += 1
    with pytest.raises(ValueError, match="sum"):
        validate_snapshot(bad, required=())


def test_prom_text_exposition():
    reg = _populated_registry()
    text = to_prom_text(reg)
    assert "# TYPE repro_queries_total counter" in text
    assert 'repro_queries_total{backend="local"} 4' in text
    assert "# TYPE repro_query_latency_seconds histogram" in text
    assert 'le="+Inf"' in text
    assert 'repro_query_latency_seconds_count{backend="local"} 4' in text
    # cumulative bucket series are monotone non-decreasing
    cum = [int(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
           if ln.startswith("repro_query_latency_seconds_bucket")]
    assert cum == sorted(cum) and cum[-1] == 4


# ----------------------------------------------------------------------
# Engine wiring (root spans, metric publication, hook isolation)
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_plan():
    g = generate_watdiv(2_000, seed=3)
    wl = generate_workload(g, 120, seed=4)
    return g, wl, build_plan(g, wl, PartitionConfig(kind="vertical",
                                                    num_sites=4))


def test_session_trace_and_metrics_knobs(tiny_plan):
    g, wl, plan = tiny_plan
    reg = MetricsRegistry()
    sess = Session(plan, backend="local", trace=True, metrics_registry=reg)
    assert sess.tracer.enabled and sess.metrics is reg
    qs = wl.queries[:5]
    for q in qs:
        sess.execute(q)
    roots = sess.tracer.store.spans()
    assert len(roots) == len(qs)
    for root in roots:
        assert root.name == "query"
        assert root.attrs["backend"] == "local"
        # _finish annotated the root with the per-query ledger
        assert {"rows", "comm_bytes", "response_time"} <= set(root.attrs)
    # multi-subquery queries show site_match/join children
    assert any(root.find("site_match") for root in roots)
    # metric publication matches the engine counters
    st = sess.stats()
    assert reg.counter("repro_queries_total",
                       backend="local").value == len(qs)
    assert reg.counter("repro_comm_bytes_total",
                       backend="local").value == st.comm_bytes
    h = reg.histogram("repro_query_latency_seconds", backend="local")
    assert h.count == len(qs)
    assert h.sum == pytest.approx(st.response_time)
    # default engines stay untraced
    assert not Session(plan, backend="local").tracer.enabled


def test_hook_error_does_not_abort_query(tiny_plan):
    g, wl, plan = tiny_plan
    reg = MetricsRegistry()
    sess = Session(plan, backend="local", metrics_registry=reg)
    seen = []

    def bad_hook(q, r):
        raise ValueError("observer bug")

    sess.post_execute_hooks.append(bad_hook)
    sess.post_execute_hooks.append(lambda q, r: seen.append(r.num_rows))
    q = wl.queries[0]
    with pytest.warns(RuntimeWarning, match="post_execute_hook"):
        r1 = sess.execute(q)
    assert r1 is not None
    assert len(seen) == 1                      # later hooks still ran
    # warns once per engine; keeps counting
    import warnings as _w
    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter("always")
        sess.execute(q)
    assert not [w for w in rec if "post_execute_hook" in str(w.message)]
    assert len(seen) == 2
    assert sess.stats().extra["hook_errors"] == 2.0
    assert reg.counter("repro_hook_errors_total",
                       backend="local").value == 2.0


def test_default_registry_swap_restores():
    prev = get_registry()
    try:
        reg = MetricsRegistry()
        assert set_registry(reg) is prev
        assert get_registry() is reg
    finally:
        set_registry(prev)
    assert get_registry() is prev


def test_adaptive_epoch_gauges(tiny_plan):
    from repro.online.loop import AdaptiveConfig

    g, wl, plan = tiny_plan
    reg = MetricsRegistry()
    sess = Session(plan, backend="adaptive", metrics_registry=reg,
                   adaptive_config=AdaptiveConfig(epoch_len=5))
    for q in wl.queries[:10]:
        sess.execute(q)
    eng = sess.engine
    assert eng.epoch == 2
    # "index" carries the id of the last *closed* epoch (0-based)
    assert reg.gauge("repro_epoch_index", backend="adaptive").value == 1.0
    assert reg.gauge("repro_epoch_queries", backend="adaptive").value == 5.0
    # drift report gauges published whenever the detector ran
    names = reg.names()
    assert "repro_epoch_tv_distance" in names
    assert "repro_epoch_coverage_loss" in names
    assert "repro_epoch_moved_bytes" in names
    assert "repro_epoch_replica_ships" in names
    # inner host engine shares the session registry
    assert reg.counter("repro_queries_total", backend="local").value == 10


def test_adaptive_trace_nesting(tiny_plan):
    g, wl, plan = tiny_plan
    sess = Session(plan, backend="adaptive", trace=True,
                   metrics_registry=MetricsRegistry())
    sess.execute(wl.queries[0])
    (root,) = sess.tracer.store.spans()
    assert root.attrs["backend"] == "adaptive"
    inner = root.find("query")
    assert len(inner) == 2                     # adaptive root + local child
    assert inner[1].attrs["backend"] == "local"


def test_required_metrics_pre_registered(tiny_plan):
    """Every REQUIRED_METRICS name exists before any query runs, so the
    CI snapshot gate cannot pass vacuously."""
    g, wl, plan = tiny_plan
    reg = MetricsRegistry()
    sess = Session(plan, backend="spmd", metrics_registry=reg)
    sess.execute(wl.queries[0])                # registers _finish metrics
    doc = snapshot(registry=reg)
    validate_snapshot(doc, required=REQUIRED_METRICS)


# ----------------------------------------------------------------------
# Thread safety: the serving front door hammers these series from a
# dispatcher thread while submit threads shed/count and exporters
# scrape, so lost updates here silently corrupt the capacity model.
# ----------------------------------------------------------------------

def test_metrics_concurrent_hammer():
    """N threads x M updates on the SAME counter/gauge/histogram plus
    racing first-registration through the registry: final counts must
    be exact (the unlocked `+=` / check-then-insert versions lose
    updates and duplicate instances under this load)."""
    import threading

    reg = MetricsRegistry()
    n_threads, n_iter = 8, 2_000
    barrier = threading.Barrier(n_threads)
    errors = []

    def hammer(tid: int) -> None:
        try:
            barrier.wait()
            for i in range(n_iter):
                # racing fetch-or-create of shared series every round:
                # a lost race would hand this thread a private instance
                # whose increments vanish from the registry
                reg.counter("hammer_total", backend="serve").inc()
                reg.histogram("hammer_seconds",
                              backend="serve").observe(i * 1e-4)
                reg.gauge("hammer_depth", backend="serve").set(float(i))
                reg.counter(f"private_{tid}_total").inc()
        except Exception as exc:  # pragma: no cover - failure reporting
            errors.append(exc)

    threads = [threading.Thread(target=hammer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    total = n_threads * n_iter
    assert reg.counter("hammer_total", backend="serve").value == total
    h = reg.histogram("hammer_seconds", backend="serve")
    assert h.count == total
    assert sum(h.counts) == total                # no torn bucket writes
    for t in range(n_threads):
        assert reg.counter(f"private_{t}_total").value == n_iter
    g = reg.gauge("hammer_depth", backend="serve")
    assert 0.0 <= g.value <= float(n_iter - 1)


def test_metrics_concurrent_collect_while_writing():
    """Exporters scrape (collect + percentile) concurrently with
    writers; the walk must never blow up on a mid-registration dict and
    percentiles must read a consistent (counts, count) pair."""
    import threading

    reg = MetricsRegistry()
    stop = threading.Event()
    errors = []

    def writer(tid: int) -> None:
        try:
            i = 0
            while not stop.is_set():
                reg.counter(f"w{tid}_{i % 50}_total").inc()
                reg.histogram("lat_seconds").observe((i % 100) * 1e-4)
                i += 1
        except Exception as exc:  # pragma: no cover
            errors.append(exc)

    def scraper() -> None:
        try:
            while not stop.is_set():
                for _name, _labels, m in reg.collect():
                    if isinstance(m, Histogram):
                        assert m.percentile(0.99) >= 0.0
                snapshot(registry=reg)
        except Exception as exc:  # pragma: no cover
            errors.append(exc)

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(4)] + [threading.Thread(target=scraper)]
    for t in threads:
        t.start()
    import time as _time
    _time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join()
    assert not errors
