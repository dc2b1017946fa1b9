"""Each metric reader on a small synthetic run and trace, and the trace
reduction on a trace recorded on the CPU."""
import math
from types import SimpleNamespace

import pytest

from harness import profile, registry, report
from harness.cell import Run
from harness.closed_loop import Request


#: operation texts as a TPU v5e trace names them
FUSION = ("%fusion.123 = s32[2097152]{0:T(1024)S(1)} fusion(s32[2097152] "
          "%get-tuple-element.655), kind=kCustom, calls=%fused_computation.3")
KERNEL = ("%per_site.1 = s32[4096,1,512]{2,1,0:T(1,128)S(1)} custom-call("
          "s32[4096]{0:T(1024)S(1)} %get-tuple-element.167), "
          'custom_call_target="tpu_custom_call", frontend_attributes='
          "{kernel_metadata={}}")
GATHER = "%all-gather.3 = s32[8388608,3]{1,0} all-gather(s32[2097152,3] %x)"
CONSUMER = "%copy.9 = s32[8388608,3]{1,0} copy(s32[8388608,3] %all-gather.3)"


def op(text, start, end, line="XLA Ops"):
    return profile.DeviceOp(text, start, end, line)


def span(name, start, end, children=()):
    sp = SimpleNamespace(name=name, start=start, end=end,
                         children=list(children))
    sp.walk = lambda: [sp] + [c for ch in sp.children for c in ch.walk()]
    return sp


@pytest.fixture
def run():
    """A 10 s window: four requests and one of the pre-roll, two
    devices with known busy time, one dispatch of three queries and one
    of one."""
    reqs = []
    for i, (sub, lat, err) in enumerate([(-2.0, 2.5, None), (0.0, 1.0, None),
                                         (1.0, 2.0, None), (2.0, 3.0, None),
                                         (9.5, 4.0, None)]):
        r = Request(i, 0, [], sub, sub + lat)
        r.error = err
        reqs.append(r)
    trace = profile.Trace({
        "/device:TPU:0": [op(FUSION, 1.0, 3.0), op(KERNEL, 3.0, 4.0),
                          op(GATHER, 5.0, 6.0), op(CONSUMER, 5.5, 7.0)],
        "/device:TPU:1": [op(KERNEL, 2.0, 3.0)]}, (0.0, 10.0),
        modules={"/device:TPU:0": [op("jit_per_site(42)", 0.9, 4.1,
                                      "XLA Modules")]})
    spans = [span("serve_batch", 0.8, 4.2, [span("query", 1.0, 2.0),
                                            span("query", 2.0, 4.0)]),
             span("serve_batch", 8.0, 11.0, [span("query", 9.0, 10.5)])]
    return Run("cell", 2, 10.0, 42.0, (0.0, 10.0), reqs,
               [True, True, True, False, True],
               {"completed": 10, "batches": 5},
               {"completed": 14, "batches": 7}, 0, 123, spans, trace)


def read(name, run):
    return registry.reader(name)(run)


def test_end_to_end_readers(run):
    # answered correctly and done inside the window: the pre-roll's
    # request and window requests 0 and 1
    assert read("qps", run) == pytest.approx(0.3)
    # latencies of the window's requests 1000, 2000, 3000, 4000 ms:
    # nearest rank
    assert read("p50_ms", run) == pytest.approx(2000.0)
    assert read("p90_ms", run) == pytest.approx(4000.0)
    assert read("setup_s", run) == 42.0


def test_failed_request_lies_beyond_every_limit(run):
    run.requests[1].error = "ShedError: shed"
    run.requests[1].done = 0.001
    assert read("p90_ms", run) == pytest.approx(4000.0)
    assert read("p50_ms", run) == pytest.approx(3000.0)


def test_program_readers(run):
    assert read("queries_per_dispatch", run) == pytest.approx(2.0)
    # query spans opening in the window: 1 s, 2 s, 1.5 s
    assert read("engine_ms_per_query", run) == pytest.approx(1500.0)
    assert read("window_compiles", run) == 0


def test_device_readers(run):
    # device 0 busy 1-4 and 5-7: 5 of 10 s; device 1 busy 1 s
    assert run.trace.busiest() == "/device:TPU:0"
    assert read("device_idle", run) == pytest.approx(50.0)
    # kernels: 1 s on device 0 and 1 s on device 1, over 6 s busy
    assert read("join_kernel_share", run) == pytest.approx(100 * 2 / 6)
    # collectives on the busiest device: 1 s of 5 s busy
    assert read("collective_share", run) == pytest.approx(20.0)


def test_device_readers_find_nothing_without_a_trace(run):
    run.trace, run.spans = None, None
    for name in ("device_idle", "join_kernel_share", "collective_share",
                 "engine_ms_per_query"):
        assert read(name, run) is None
    run.trace = profile.Trace({"/device:TPU:0": [op(FUSION, 1, 2),
                                                 op(CONSUMER, 2, 3)]},
                              (0.0, 10.0))
    assert read("join_kernel_share", run) is None
    assert read("collective_share", run) is None


def test_breakdown(run):
    bd = report.breakdown(run)
    names = dict(bd["device_ops"])
    assert names["jit_per_site(42)/fusion.123"] == pytest.approx(2.0)
    assert names["jit_per_site(42)/per_site.1"] == pytest.approx(1.0)
    assert names["per_site.1"] == pytest.approx(1.0)
    assert names["all-gather.3"] == pytest.approx(1.0)
    gaps = bd["idle_gaps"]
    assert gaps[0] == ["host: in serve_batch span, outside its children",
                       pytest.approx(3.0)]
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps),
                                          reverse=True)
    assert gaps[-1][0] == "host: outside the program's spans"


def test_async_collective_counts_where_the_device_is_busy(run):
    dev = "/device:TPU:0"
    run.trace.async_ops[dev] = [op("%all-gather-start.1 = (s32[4]) "
                                   "all-gather-start(s32[1] %y)", 3.5, 9.0,
                                   "Async XLA Ops")]
    # in flight 3.5-9; busy 1-4 and 5-7: overlap 0.5 + 2 = 2.5 of 5 s
    assert read("collective_share", run) == pytest.approx(50.0)


def test_union_and_gaps():
    iv = [(1, 3), (2, 4), (6, 7), (9, 12)]
    assert profile.union_length(iv, 0, 10) == pytest.approx(5.0)
    assert profile.gaps(iv, 0, 10) == [(0, 1), (4, 6), (7, 9)]
    assert profile.union_length([], 0, 10) == 0.0


def test_recorded_cpu_trace_anchors_the_window(tmp_path):
    """A trace recorded here has the window annotation but no TPU
    plane: the reduction finds the anchor and no device operations."""
    import time

    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(profile.WINDOW):
        w0 = time.perf_counter()
        f(x).block_until_ready()
        w1 = time.perf_counter()
    jax.profiler.stop_trace()
    trace = profile.read(str(tmp_path), w0, w1)
    assert trace.ops == {} and math.isclose(trace.window_s, w1 - w0)
