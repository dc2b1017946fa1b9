"""The LUBM-with-entailment cell at test size on the CPU, a fault planted
in its cycle-close probe, and the readers of the two metrics it adds
(``pair_semijoin_share``, ``cycle_match_ms_per_run``) on synthetic
runs."""
import pytest

import small
from harness import deploy, registry
from harness.cell import Run
from harness.profile import DeviceOp, Trace
from repro.obs.trace import Tracer

CELL = "lubm-rdfs-1chip.c16"


@pytest.fixture(autouse=True)
def cache(tmp_path_factory, monkeypatch):
    monkeypatch.setattr(deploy, "CACHE",
                        tmp_path_factory.getbasetemp() / "bench-cache")


def test_the_cell_runs_correct_and_reports_its_cycle_runs():
    line = small.execute(small.cell(CELL), seed=2**31 + 15, traced=True)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 14 and line["failed"] == 0
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["cycle_match_ms_per_run"] > 0
    assert m["window_compiles"] == 0
    # no profiler device trace on the CPU: the kernel share reads nothing
    assert "pair_semijoin_share" not in m


def test_the_cell_runs_correct_with_hub_constants_on_the_device(
        monkeypatch):
    """At test size a class averages 239 typings, short of the engine's
    hub rule; with the rule lowered to 200 the classes (and the two
    departments, 444 members each) are matched on the device, as the
    classes are at LUBM(20,0)."""
    from repro.core import spmd
    monkeypatch.setattr(spmd, "HUB_EDGES_PER_VALUE", 200)
    line = small.execute(small.cell(CELL), seed=2**31 + 16)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 14 and line["failed"] == 0


def test_a_cycle_close_that_keeps_every_row_is_not_correct(monkeypatch):
    """The cycle-close probe keeps every valid row: Q2 and Q9 answer
    open paths as triangles."""
    import jax.numpy as jnp
    from repro.core import spmd
    monkeypatch.setattr(spmd, "_probe_pair_member",
                        lambda q_s, q_o, t_s, t_o, paths=None:
                        jnp.ones(q_s.shape, bool))
    line = small.execute(small.cell(CELL), seed=21)
    assert not line["correct"]
    assert line["checks"]["not_exact"]["value"] > 0


HLO = '%{} = s32[4,1,512]{{2,1,0}} custom-call(...), ' \
    'custom_call_target="tpu_custom_call"'


def traced_run(ops):
    """A run whose trace holds ``ops`` (name, start, end) on one device,
    and a second, idle device."""
    devs = {"/device:TPU:0": [DeviceOp(t, a, b) for t, a, b in ops],
            "/device:TPU:1": [DeviceOp("%fusion.1 = f32[] fusion()", 0, 1)]}
    return Run(CELL, 1, 10.0, 1.0, (0.0, 10.0), [], [], {}, {}, 0, 0,
               None, Trace(devs, (0.0, 10.0)))


def test_pair_semijoin_share():
    run = traced_run([(HLO.format("pair_semijoin.3"), 1.0, 2.0),
                      (HLO.format("pair_semijoin"), 1.5, 2.5),
                      (HLO.format("join_count.1"), 3.0, 5.0),
                      ("%while.33 = (s32[]) while(...)", 5.0, 6.0),
                      ("%pair_semijoin_copy = f32[] fusion()", 6.0, 7.0)])
    # pair_semijoin covers 1.5 s of the busy 5.5 s ([1, 2.5] and [3, 7])
    assert registry.reader("pair_semijoin_share")(run) \
        == pytest.approx(100 * 1.5 / 5.5)
    without = traced_run([(HLO.format("join_count.1"), 3.0, 5.0)])
    assert registry.reader("pair_semijoin_share")(without) is None
    assert registry.reader("pair_semijoin_share")(
        Run(CELL, 1, 10.0, 1.0, (0.0, 10.0), [], [], {}, {}, 0, 0,
            None, None)) is None


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def span_run(matches):
    """One ``query`` span per ``(start, end, attrs)``, holding one
    ``match`` span with those attributes."""
    clock = Clock()
    tracer = Tracer(enabled=True, clock=clock, mirror=None)
    for a, b, attrs in matches:
        clock.t = a
        with tracer.span("query"):
            with tracer.span("match", **attrs):
                clock.t = b
    return Run(CELL, 1, 10.0, 1.0, (0.0, 10.0), [], [], {}, {}, 0, 0,
               tracer.store.spans(), None)


def test_cycle_match_ms_per_run():
    run = span_run([(1.0, 1.5, {"cycles": 1, "edges": 6}),
                    (2.0, 4.0, {"cycles": 0, "edges": 2}),
                    (5.0, 6.0, {"cycles": 2, "edges": 5}),
                    (11.0, 15.0, {"cycles": 1, "edges": 6})])
    # the cyclic runs opening in the window: 0.5 s and 1 s
    assert registry.reader("cycle_match_ms_per_run")(run) \
        == pytest.approx(750.0)
    # a program whose match spans carry no cycles reads nothing
    parent = span_run([(1.0, 2.0, {"capacity": 8})])
    assert registry.reader("cycle_match_ms_per_run")(parent) is None
    assert registry.reader("cycle_match_ms_per_run")(
        span_run([(1.0, 2.0, {"cycles": 0})])) is None
