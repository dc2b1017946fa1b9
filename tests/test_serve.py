"""Serving front door (`repro.serve`): fake-clock unit tests for the
state machine -- batcher flush semantics, backpressure shedding,
deadline expiry, circuit-breaker transitions, poison-batch fallback --
plus the end-to-end served-vs-direct answer-set parity harness over
every backend (driven through the real dispatcher thread on the mesh
the suite runs at: CI covers 1/2/4 devices).

The unit tests never spawn threads or sleep: the FrontDoor is built
with ``start=False`` and an injected manual clock, and dispatch is
driven by explicit ``pump()`` / ``drain()`` calls, so every transition
is deterministic.
"""
import threading

import numpy as np
import pytest

from generators import SEED, answer_set as _answer_set, random_graph, \
    shape_workload
from repro.obs.export import (REQUIRED_SERVE_METRICS, snapshot,
                              validate_snapshot)
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer
from repro.serve import (BreakerOpenError, CircuitBreaker,
                         DeadlineExceededError, FrontDoor, FrontDoorConfig,
                         LoadgenReport, QueueFullError, ShapeBatcher,
                         arrival_offsets, run_open_loop)


# ----------------------------------------------------------------------
# Fakes: deterministic clock, shape-keyed query stubs, scriptable engine
# ----------------------------------------------------------------------

class ManualClock:
    def __init__(self) -> None:
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


class FakeQuery:
    """Stub with the two things the serve layer reads: ``edges`` (for
    the PROP_VAR check nothing here triggers) and ``normalize()``."""

    def __init__(self, shape: str, const: int):
        self.shape, self.const = shape, const
        self.edges = (shape, const)

    def normalize(self):
        q, shape = self, self.shape

        class _N:
            edges = (shape,)
        return _N()


class FakeEngine:
    """Scriptable engine: records every dispatched batch; can be told
    to fail whole batches or specific poison queries."""

    def __init__(self):
        self.batches = []
        self.fail_next = 0          # fail this many upcoming dispatches
        self.poison = set()         # consts whose presence fails a batch

    def execute_many(self, queries, batch_size=64):
        self.batches.append([q.const for q in queries])
        if self.fail_next > 0:
            self.fail_next -= 1
            raise RuntimeError("scripted backend failure")
        if any(q.const in self.poison for q in queries):
            raise RuntimeError("poison query in batch")
        return [f"r{q.shape}:{q.const}" for q in queries]


def make_door(engine=None, clock=None, **cfg):
    clock = clock or ManualClock()
    engine = engine or FakeEngine()
    cfg.setdefault("max_queue", 8)
    cfg.setdefault("max_batch", 3)
    cfg.setdefault("max_delay_ms", 10.0)
    cfg.setdefault("default_deadline_s", 100.0)
    door = FrontDoor(engine, FrontDoorConfig(**cfg), clock=clock,
                     registry=MetricsRegistry())
    return door, engine, clock


# ----------------------------------------------------------------------
# Batcher flush semantics
# ----------------------------------------------------------------------

class _Req:
    def __init__(self, q, t):
        self.query, self.enqueued_at = q, t


def test_batcher_max_batch_flush():
    b = ShapeBatcher(max_batch=2, max_delay_s=1.0)
    b.add(_Req(FakeQuery("a", 1), 0.0))
    assert b.take_ready(0.0) == [] and len(b) == 1
    b.add(_Req(FakeQuery("a", 2), 0.0))          # bucket full
    ready = b.take_ready(0.0)
    assert len(ready) == 1 and ready[0].reason == "full"
    assert [r.query.const for r in ready[0].requests] == [1, 2]
    assert len(b) == 0


def test_batcher_max_delay_flush_per_key():
    b = ShapeBatcher(max_batch=10, max_delay_s=0.5)
    b.add(_Req(FakeQuery("a", 1), 0.0))
    b.add(_Req(FakeQuery("b", 2), 0.3))
    assert b.take_ready(0.4) == []               # neither old enough
    ready = b.take_ready(0.5)                    # only shape a is due
    assert [r.reason for r in ready] == ["delay"]
    assert ready[0].key == ("a",) and len(b) == 1
    ready = b.take_ready(0.8)                    # now shape b
    assert ready[0].key == ("b",) and len(b) == 0


def test_batcher_keys_do_not_mix_shapes():
    b = ShapeBatcher(max_batch=2, max_delay_s=1.0)
    b.add(_Req(FakeQuery("a", 1), 0.0))
    b.add(_Req(FakeQuery("b", 2), 0.0))
    assert b.take_ready(0.0) == []               # two half-full buckets
    b.add(_Req(FakeQuery("a", 3), 0.0))
    ready = b.take_ready(0.0)
    assert len(ready) == 1
    assert {r.query.const for r in ready[0].requests} == {1, 3}


def test_batcher_next_due_and_flush_all():
    b = ShapeBatcher(max_batch=2, max_delay_s=0.5)
    assert b.next_due() is None
    b.add(_Req(FakeQuery("a", 1), 1.0))
    assert b.next_due() == pytest.approx(1.5)
    b.add(_Req(FakeQuery("a", 2), 1.1))          # fills -> ready now
    assert b.next_due() == float("-inf")
    b.add(_Req(FakeQuery("b", 3), 1.2))
    out = b.flush_all()
    assert {batch.reason for batch in out} == {"full", "drain"}
    assert len(b) == 0 and b.next_due() is None


def test_batcher_validates_config():
    with pytest.raises(ValueError):
        ShapeBatcher(max_batch=0)
    with pytest.raises(ValueError):
        ShapeBatcher(max_delay_s=-1.0)
    with pytest.raises(ValueError):
        FrontDoorConfig(max_queue=0)
    with pytest.raises(ValueError):
        FrontDoorConfig(breaker_failure_ratio=0.0)


# ----------------------------------------------------------------------
# Admission, backpressure, deadlines (manual pump, fake clock)
# ----------------------------------------------------------------------

def test_submit_pump_roundtrip_and_order():
    door, eng, clk = make_door(max_batch=2)
    f1 = door.submit(FakeQuery("a", 1))
    f2 = door.submit(FakeQuery("a", 2))          # fills the bucket
    assert not f1.done()
    assert door.pump() == 1
    assert f1.result(0) == "ra:1" and f2.result(0) == "ra:2"
    assert eng.batches == [[1, 2]]               # ONE dispatch, in order
    assert f1.outcome == "completed" and f1.latency_s is not None


def test_short_bucket_flushes_on_max_delay():
    door, eng, clk = make_door(max_batch=100, max_delay_ms=10.0)
    f = door.submit(FakeQuery("a", 1))
    assert door.pump() == 0                      # not due yet
    clk.advance(0.011)
    assert door.pump() == 1                      # age-triggered flush
    assert f.result(0) == "ra:1"


def test_queue_full_sheds_loudly():
    door, eng, clk = make_door(max_queue=3, max_batch=100)
    for i in range(3):
        door.submit(FakeQuery("a", i))
    with pytest.raises(QueueFullError):
        door.submit(FakeQuery("a", 99))
    assert door.stats()["shed_queue_full"] == 1
    assert door.queue_depth == 3                 # shed request not queued
    door.drain()
    assert door.queue_depth == 0
    door2 = door.submit(FakeQuery("a", 100))     # capacity freed again
    assert door2 is not None


def test_deadline_expiry_never_reaches_engine():
    door, eng, clk = make_door(max_batch=100, max_delay_ms=10.0)
    f_dead = door.submit(FakeQuery("a", 1), deadline_s=0.005)
    f_live = door.submit(FakeQuery("a", 2), deadline_s=100.0)
    clk.advance(0.02)                            # past deadline AND delay
    assert door.pump() == 1
    with pytest.raises(DeadlineExceededError):
        f_dead.result(0)
    assert f_dead.outcome == "deadline"
    assert f_live.result(0) == "ra:2"
    assert eng.batches == [[2]]                  # expired one never ran
    assert door.stats()["deadline_expired"] == 1


def test_future_timeout_raises_timeouterror():
    door, eng, clk = make_door()
    f = door.submit(FakeQuery("a", 1))
    with pytest.raises(TimeoutError):
        f.result(timeout=0.01)


# ----------------------------------------------------------------------
# Circuit breaker
# ----------------------------------------------------------------------

def test_breaker_unit_transitions():
    br = CircuitBreaker(window=8, min_events=4, failure_ratio=0.5,
                        cooldown_s=1.0, probes=2)
    assert br.state == "closed"
    for _ in range(3):
        br.record(False, 0.0)
    assert br.state == "closed"                  # below min_events
    br.record(False, 0.0)
    assert br.state == "open" and br.opens_total == 1
    assert not br.allow(0.9)                     # cooling down
    assert br.allow(1.1)                         # half-open, probe 1
    assert br.state == "half_open"
    assert br.allow(1.1)                         # probe 2
    assert not br.allow(1.1)                     # probe budget exhausted
    br.record(True, 1.2)
    br.record(True, 1.3)                         # both probes succeeded
    assert br.state == "closed"


def test_breaker_half_open_failure_reopens():
    br = CircuitBreaker(window=8, min_events=2, failure_ratio=0.5,
                        cooldown_s=1.0, probes=2)
    br.record(False, 0.0)
    br.record(False, 0.0)
    assert br.state == "open"
    assert br.allow(1.5)
    br.record(False, 1.6)                        # probe failed
    assert br.state == "open" and br.opens_total == 2
    assert not br.allow(2.0)                     # new cooldown from 1.6
    assert br.allow(2.7)


def test_breaker_mixed_window_below_ratio_stays_closed():
    br = CircuitBreaker(window=8, min_events=4, failure_ratio=0.5)
    for ok in [True, False, True, True, False, True]:
        br.record(ok, 0.0)
    assert br.state == "closed"                  # 2/6 < 0.5


def test_door_breaker_closed_open_halfopen_closed():
    door, eng, clk = make_door(max_batch=1, breaker_window=8,
                               breaker_min_events=2,
                               breaker_failure_ratio=0.5,
                               breaker_cooldown_s=1.0, breaker_probes=1)
    eng.fail_next = 2
    for i in range(2):
        f = door.submit(FakeQuery("a", i))
        door.pump()
        with pytest.raises(RuntimeError):
            f.result(0)
    assert door.breaker_state == "open"
    assert door.stats()["breaker_opens"] == 1
    with pytest.raises(BreakerOpenError):        # sheds while open
        door.submit(FakeQuery("a", 9))
    assert door.stats()["shed_breaker"] == 1
    clk.advance(1.5)                             # past cooldown: probe
    f = door.submit(FakeQuery("a", 10))
    assert door.breaker_state == "half_open"
    door.pump()
    assert f.result(0) == "ra:10"                # probe succeeded
    assert door.breaker_state == "closed"
    f = door.submit(FakeQuery("a", 11))          # healthy again
    door.pump()
    assert f.result(0) == "ra:11"


def test_breaker_probes_collapsed_into_one_dispatch_still_close():
    """Regression: with breaker_probes=2, two same-shape probes collapse
    into ONE micro-batched dispatch -> one success outcome.  Per-request
    crediting must close the breaker instead of wedging it half-open
    with zero budget forever."""
    door, eng, clk = make_door(max_batch=2, breaker_window=8,
                               breaker_min_events=2,
                               breaker_failure_ratio=0.5,
                               breaker_cooldown_s=1.0, breaker_probes=2)
    eng.fail_next = 2
    for i in range(2):
        f = door.submit(FakeQuery("a", i))
        clk.advance(0.011)                       # age-flush the lone req
        door.pump()
        with pytest.raises(RuntimeError):
            f.result(0)
    assert door.breaker_state == "open"
    clk.advance(1.5)
    f1 = door.submit(FakeQuery("a", 10))         # probe 1
    f2 = door.submit(FakeQuery("a", 11))         # probe 2, fills bucket
    assert door.breaker_state == "half_open"
    door.pump()                                  # ONE dispatch, both probes
    assert f1.result(0) == "ra:10" and f2.result(0) == "ra:11"
    assert eng.batches[-1] == [10, 11]
    assert door.breaker_state == "closed"        # not wedged
    f3 = door.submit(FakeQuery("a", 12))         # traffic flows again
    clk.advance(0.011)
    door.pump()
    assert f3.result(0) == "ra:12"


def test_queue_full_shed_does_not_consume_probe_budget():
    """Regression: submit() used to decrement the half-open probe
    budget before the queue-full check, so a QueueFullError leaked a
    probe slot whose outcome could never be recorded."""
    door, eng, clk = make_door(max_batch=1, max_queue=1,
                               breaker_window=8, breaker_min_events=2,
                               breaker_failure_ratio=0.5,
                               breaker_cooldown_s=1.0, breaker_probes=2)
    eng.fail_next = 2
    for i in range(2):
        f = door.submit(FakeQuery("a", i))
        door.pump()
        with pytest.raises(RuntimeError):
            f.result(0)
    assert door.breaker_state == "open"
    clk.advance(1.5)
    f1 = door.submit(FakeQuery("a", 10))         # probe 1 (budget 2 -> 1)
    with pytest.raises(QueueFullError):
        door.submit(FakeQuery("a", 11))          # shed BEFORE the breaker
    door.pump()
    assert f1.result(0) == "ra:10"
    assert door.breaker_state == "half_open"     # 1 of 2 successes so far
    f2 = door.submit(FakeQuery("a", 12))         # slot NOT leaked to shed
    door.pump()
    assert f2.result(0) == "ra:12"
    assert door.breaker_state == "closed"


def test_deadline_dropped_probe_refunds_budget():
    """Regression: a probe admitted in half-open but dropped by
    deadline expiry never produces a dispatch outcome; its slot must be
    refunded or the breaker wedges on an exhausted budget."""
    door, eng, clk = make_door(max_batch=100, max_delay_ms=10.0,
                               breaker_window=8, breaker_min_events=2,
                               breaker_failure_ratio=0.5,
                               breaker_cooldown_s=1.0, breaker_probes=1)
    eng.fail_next = 2
    for i in range(2):
        f = door.submit(FakeQuery("a", i))
        clk.advance(0.011)
        door.pump()
        with pytest.raises(RuntimeError):
            f.result(0)
    assert door.breaker_state == "open"
    clk.advance(1.5)
    f1 = door.submit(FakeQuery("a", 10), deadline_s=0.005)  # the 1 probe
    clk.advance(0.02)                            # expires before dispatch
    door.pump()
    with pytest.raises(DeadlineExceededError):
        f1.result(0)
    assert door.breaker_state == "half_open"
    f2 = door.submit(FakeQuery("a", 11))         # refunded slot reused
    clk.advance(0.011)
    door.pump()
    assert f2.result(0) == "ra:11"
    assert door.breaker_state == "closed"


def test_breaker_half_open_stall_backstop_reopens():
    """A half-open breaker whose probe outcomes never arrive (slot
    leaked by a crash path) re-opens after a full cooldown instead of
    shedding forever, so fresh probe budget is eventually minted."""
    br = CircuitBreaker(window=8, min_events=2, failure_ratio=0.5,
                        cooldown_s=1.0, probes=1)
    br.record(False, 0.0)
    br.record(False, 0.0)
    assert br.state == "open"
    assert br.allow(1.1)                         # the only probe: leaked
    assert not br.allow(1.2)                     # budget 0, within cooldown
    assert br.state == "half_open"
    assert not br.allow(2.2)                     # stalled a full cooldown
    assert br.state == "open" and br.opens_total == 2
    assert br.allow(3.3)                         # fresh budget minted
    br.record(True, 3.4)
    assert br.state == "closed"


def test_failed_batch_fallback_rechecks_deadline():
    """Regression: after a SLOW failed batch dispatch, per-request
    fallback must not execute requests whose deadline already passed --
    they complete with DeadlineExceededError and never hit the
    backend."""
    door, eng, clk = make_door(max_batch=2)
    orig = eng.execute_many

    def slow_failing_batch(queries, batch_size=64):
        if len(queries) > 1:
            clk.advance(5.0)                     # slow, then fails
            raise RuntimeError("scripted slow batch failure")
        return orig(queries, batch_size=batch_size)

    eng.execute_many = slow_failing_batch
    f_dead = door.submit(FakeQuery("a", 1), deadline_s=2.0)
    f_live = door.submit(FakeQuery("a", 2), deadline_s=100.0)
    door.pump()
    with pytest.raises(DeadlineExceededError):
        f_dead.result(0)
    assert f_dead.outcome == "deadline"
    assert f_live.result(0) == "ra:2"
    assert eng.batches == [[2]]                  # expired one never re-ran
    assert door.stats()["deadline_expired"] == 1
    assert door.stats()["completed"] == 1


def test_sheds_and_deadlines_do_not_trip_breaker():
    door, eng, clk = make_door(max_queue=2, max_batch=100,
                               breaker_min_events=1,
                               breaker_failure_ratio=0.01)
    door.submit(FakeQuery("a", 1), deadline_s=0.001)
    door.submit(FakeQuery("a", 2))
    with pytest.raises(QueueFullError):
        door.submit(FakeQuery("a", 3))
    clk.advance(0.02)
    door.pump()                                  # expires #1, runs #2
    assert door.stats()["deadline_expired"] == 1
    assert door.breaker_state == "closed"        # load != backend health


def test_poison_batch_falls_back_per_request():
    door, eng, clk = make_door(max_batch=3)
    eng.poison = {2}
    futs = [door.submit(FakeQuery("a", i)) for i in range(1, 4)]
    door.pump()
    assert futs[0].result(0) == "ra:1"
    assert futs[2].result(0) == "ra:3"
    with pytest.raises(RuntimeError):
        futs[1].result(0)
    assert futs[1].outcome == "failed"
    # one failed batch dispatch, then one isolated dispatch per request
    assert eng.batches == [[1, 2, 3], [1], [2], [3]]
    assert door.stats()["batch_fallbacks"] == 1
    assert door.stats()["failed"] == 1 and door.stats()["completed"] == 2


def test_single_request_batch_failure_is_not_retried():
    door, eng, clk = make_door(max_batch=1)
    eng.fail_next = 1
    f = door.submit(FakeQuery("a", 1))
    door.pump()
    with pytest.raises(RuntimeError):
        f.result(0)
    assert eng.batches == [[1]]                  # no pointless retry
    assert door.stats()["batch_fallbacks"] == 0


# ----------------------------------------------------------------------
# Telemetry wiring
# ----------------------------------------------------------------------

def test_serve_metrics_preregistered_and_snapshot_validates():
    door, eng, clk = make_door()
    doc = snapshot(door.metrics)
    validate_snapshot(doc, required=REQUIRED_SERVE_METRICS)


def test_span_chain_admission_batch_execute():
    tracer = Tracer(enabled=True, clock=ManualClock())
    door, eng, clk = make_door(max_batch=2)
    door.tracer = tracer
    door.submit(FakeQuery("a", 1))
    door.submit(FakeQuery("a", 2))
    door.pump()
    roots = tracer.store.spans()
    assert [s.name for s in roots] == ["serve_batch"]
    sp = roots[0]
    assert sp.attrs["batch"] == 2 and sp.attrs["flush"] == "full"
    waits = [r for r in sp.records if r.get("kind") == "admission"]
    assert len(waits) == 2                       # one per admitted member


def test_dispatch_and_queue_wait_counters_count_exactly():
    door, eng, clk = make_door(max_batch=2, max_delay_ms=10.0)
    door.submit(FakeQuery("a", 1))
    clk.advance(0.25)
    door.submit(FakeQuery("a", 2))               # fills the bucket
    clk.advance(0.5)
    door.submit(FakeQuery("b", 3))
    clk.advance(1.0)                             # "b" is past its delay
    assert door.pump() == 2
    st = door.stats()
    assert st["dispatched"] == 3
    # waits on the door's clock: 1.75 and 1.5 ("a"), 1.0 ("b")
    assert st["queue_wait_s"] == pytest.approx(4.25)
    f = door.submit(FakeQuery("c", 4), deadline_s=0.5)
    clk.advance(1.0)
    door.pump()                                  # expired: not dispatched
    assert f.outcome == "deadline"
    assert door.stats()["dispatched"] == 3
    prom = door.metrics.counter("repro_serve_queue_wait_s_total",
                                backend="serve")
    assert prom.value == pytest.approx(4.25)


def test_queue_depth_gauge_tracks_lifecycle():
    door, eng, clk = make_door(max_batch=100)
    g = door.metrics.gauge("repro_serve_queue_depth", backend="serve")
    door.submit(FakeQuery("a", 1))
    door.submit(FakeQuery("a", 2))
    assert g.value == 2.0
    door.drain()
    assert g.value == 0.0


# ----------------------------------------------------------------------
# Dispatcher thread + load generator (still the fake engine: fast)
# ----------------------------------------------------------------------

def test_dispatcher_thread_end_to_end():
    eng = FakeEngine()
    door = FrontDoor(eng, FrontDoorConfig(max_batch=4, max_delay_ms=1.0),
                     registry=MetricsRegistry())
    with door:
        futs = [door.submit(FakeQuery("s" + str(i % 2), i))
                for i in range(20)]
        got = [f.result(timeout=10.0) for f in futs]
    assert got == [f"rs{i % 2}:{i}" for i in range(20)]
    # micro-batching really grouped by shape: no mixed-shape dispatch
    for batch in eng.batches:
        assert len({c % 2 for c in batch}) == 1


def test_close_drains_pending_requests():
    eng = FakeEngine()
    door = FrontDoor(eng, FrontDoorConfig(max_batch=100,
                                          max_delay_ms=60_000.0),
                     registry=MetricsRegistry()).start()
    futs = [door.submit(FakeQuery("a", i)) for i in range(3)]
    door.close(drain=True)                       # delay never elapsed
    assert [f.result(0) for f in futs] == ["ra:0", "ra:1", "ra:2"]


def test_arrival_offsets_seeded_and_bounded():
    a = arrival_offsets(200.0, 0.5, seed=3)
    b = arrival_offsets(200.0, 0.5, seed=3)
    assert np.array_equal(a, b)
    assert len(a) > 20 and float(a[-1]) < 0.5
    assert not np.array_equal(a, arrival_offsets(200.0, 0.5, seed=4))


def test_run_open_loop_report_accounting():
    eng = FakeEngine()
    door = FrontDoor(eng, FrontDoorConfig(max_batch=8, max_delay_ms=1.0),
                     registry=MetricsRegistry()).start()
    try:
        rep = run_open_loop(door, [FakeQuery("a", 1), FakeQuery("b", 2)],
                            qps=400.0, duration_s=0.25, seed=5)
    finally:
        door.close()
    assert rep.submitted == rep.admitted == rep.completed > 0
    assert rep.shed_rate == 0.0 and rep.failed == 0
    assert rep.achieved_qps > 0 and rep.p99_latency_s >= rep.p50_latency_s
    row = rep.to_row()
    assert row["completed"] == rep.completed
    assert isinstance(rep, LoadgenReport)


# ----------------------------------------------------------------------
# End-to-end: served answers == direct Session.execute, every backend
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def served_setup():
    from repro.core import PartitionConfig, Session, build_plan
    from repro.core.workload import Workload
    g = random_graph(SEED)
    queries = shape_workload(g, SEED, n_props=g.num_properties)
    plan = build_plan(g, Workload(list(queries)),
                      PartitionConfig(kind="vertical", num_sites=4))
    return plan, queries


@pytest.mark.parametrize("backend", ["local", "baseline", "spmd",
                                     "adaptive"])
def test_served_answers_match_direct_execution(served_setup, backend):
    """The acceptance-criteria parity harness: every query through the
    full admission -> micro-batch -> dispatch path (real dispatcher
    thread) answers set-identically to direct ``Session.execute`` --
    per backend, on whatever mesh the suite runs at (CI: 1/2/4)."""
    from repro.core import Session
    plan, queries = served_setup
    sess = Session(plan, backend=backend)
    direct = [sess.execute(q) for q in queries]
    with sess.serve(max_batch=4, max_delay_ms=2.0) as door:
        futs = [door.submit(q, deadline_s=300.0) for q in queries]
        served = [f.result(timeout=300.0) for f in futs]
    for q, a, b in zip(queries, direct, served):
        va, sa = _answer_set(a)
        vb, sb = _answer_set(b)
        assert va == vb, f"{backend}: variable sets diverged on {q.edges}"
        assert sa == sb, f"{backend}: answer set diverged on {q.edges}"


def test_routed_serving_buckets_still_batch_exactly(served_setup):
    """Serving over the *routed* SPMD engine: the door's bucket key
    gains the engine's route token.  The token is a pure function of
    the normalized shape, so the refinement never splits a same-shape
    bucket -- requests still coalesce into one dispatch per shape,
    ``batch_shape_hits`` stays exact, and served answers equal direct
    routed execution."""
    from repro.core import Session
    from repro.serve.batcher import shape_key
    plan, queries = served_setup
    qs = list(queries) * 2
    direct_sess = Session(plan, backend="spmd")
    direct = [direct_sess.execute(q) for q in qs]
    sess = Session(plan, backend="spmd")
    door = sess.serve(max_batch=len(qs) + 1, max_delay_ms=10_000.0,
                      max_queue=len(qs) + 1)
    if sess.num_sites > 1:
        assert door.batcher.route_key is not None
    futs = [door.submit(q, deadline_s=300.0) for q in qs]
    door.close(drain=True)            # manual mode: drains synchronously
    served = [f.result(timeout=5.0) for f in futs]
    for q, a, b in zip(qs, direct, served):
        assert _answer_set(a) == _answer_set(b), f"diverged on {q.edges}"
    # the route token never split a shape's bucket ...
    buckets = {(shape_key(q), sess.route_key(q)) for q in qs}
    assert len(buckets) == len({shape_key(q) for q in qs})
    # ... so each shape ran as ONE engine dispatch and every later
    # member reused the compiled run
    hits = sess.stats().extra["batch_shape_hits"]
    assert hits == len(qs) - len(buckets)


def test_session_serve_knob_validation(served_setup):
    from repro.core import Session
    plan, _ = served_setup
    sess = Session(plan, backend="local")
    with pytest.raises(ValueError):
        sess.serve(FrontDoorConfig(), max_queue=4)   # both given
    door = sess.serve(max_queue=4)
    assert door.config.max_queue == 4
    assert door.metrics is sess.metrics
