"""The readers of the program's own spans and front-door counters
(``queue_wait_ms``, ``match_ms_per_run``, ``engine_host_ms_per_query``)
on span trees built by the program's tracer, and on a whole traced run
at test size."""
import pytest

import small
from harness import deploy, registry
from harness.cell import Run
from repro.obs.trace import Tracer


class Clock:
    """A settable clock for the tracer: spans open and close at the
    times a test sets."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def served(clock, tracer, start, matches, end):
    """One ``serve_batch`` holding one ``query`` from ``start`` to
    ``end``, with a ``match`` span over each ``(a, b)`` of ``matches``
    and a ``dedup`` span after the last."""
    clock.t = start
    with tracer.span("serve_batch"):
        with tracer.span("query"):
            for a, b in matches:
                clock.t = a
                with tracer.span("match"):
                    clock.t = b
            with tracer.span("dedup"):
                clock.t = end
    return tracer.store.spans()[-1]


def make_run(spans, door_before=None, door_after=None):
    door_before = door_before or {"completed": 0, "batches": 0}
    door_after = door_after or {"completed": 0, "batches": 0}
    return Run("cell", 1, 10.0, 1.0, (0.0, 10.0), [], [], door_before,
               door_after, 0, 0, spans, None)


def read(name, run):
    return registry.reader(name)(run)


@pytest.fixture
def spans():
    """Three queries opening in a 10 s window (one runs the device
    twice, one reuses a group's run), and one opening after it."""
    clock = Clock()
    tracer = Tracer(enabled=True, clock=clock, mirror=None)
    served(clock, tracer, 1.0, [(1.5, 2.5)], 3.0)
    served(clock, tracer, 4.0, [(4.0, 4.5), (5.0, 6.0)], 6.5)
    served(clock, tracer, 7.0, [], 7.25)
    served(clock, tracer, 10.5, [(10.5, 12.5)], 13.0)
    return tracer.store.spans()


def test_match_ms_per_run(spans):
    # runs opening in the window: 1 s, 0.5 s, 1 s
    assert read("match_ms_per_run", make_run(spans)) == \
        pytest.approx(1e3 * 2.5 / 3)


def test_engine_host_ms_per_query(spans):
    # query self times: 2 - 1, 2.5 - 1.5, 0.25 (reused, no match)
    assert read("engine_host_ms_per_query", make_run(spans)) == \
        pytest.approx(1e3 * 2.25 / 3)
    # beside the span the accepted reader times whole
    assert read("engine_ms_per_query", make_run(spans)) == \
        pytest.approx(1e3 * 4.75 / 3)


def test_queue_wait_ms():
    run = make_run([], {"dispatched": 10, "queue_wait_s": 5.0},
                   {"dispatched": 14, "queue_wait_s": 13.0})
    assert read("queue_wait_ms", run) == pytest.approx(2000.0)


def test_nothing_to_read_without_the_program_s_spans_or_counters(spans):
    """A run without spans, a program whose ``query`` spans hold no
    ``match`` spans, and a front door without the counters or with no
    dispatch in the window give nothing, and raise nothing."""
    for name in ("match_ms_per_run", "engine_host_ms_per_query"):
        assert read(name, make_run(None)) is None
    clock = Clock()
    tracer = Tracer(enabled=True, clock=clock, mirror=None)
    served(clock, tracer, 1.0, [], 2.0)
    unsplit = make_run(tracer.store.spans())
    assert read("match_ms_per_run", unsplit) is None
    assert read("engine_host_ms_per_query", unsplit) is None
    assert read("queue_wait_ms", make_run(spans)) is None
    idle = {"dispatched": 3, "queue_wait_s": 1.0}
    assert read("queue_wait_ms", make_run(spans, idle, dict(idle))) is None


def test_a_traced_run_reports_them(tmp_path_factory, monkeypatch):
    """A whole traced run at test size on the CPU: the program's span
    and counter names are the ones the readers read."""
    monkeypatch.setattr(deploy, "CACHE",
                        tmp_path_factory.getbasetemp() / "bench-cache")
    line = small.execute(small.cell("lubm20-1chip.c16"), seed=2147483999,
                         traced=True)
    assert line["correct"]
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["queue_wait_ms"] > 0
    assert 0 < m["match_ms_per_run"]
    assert 0 < m["engine_host_ms_per_query"] < m["engine_ms_per_query"]
