"""Whole runs on the CPU at test size, with the chip check skipped: a
sound run comes out correct, and the control and each fault planted
under the timed path come out not correct."""
import pytest

import control
import small
from harness import deploy

ONE, MESH = "lubm20-1chip.c16", "lubm20-2x2.c16"


@pytest.fixture(autouse=True)
def cache(tmp_path_factory, monkeypatch):
    """Graphs and plans of the test-size cells, built once per session."""
    monkeypatch.setattr(deploy, "CACHE",
                        tmp_path_factory.getbasetemp() / "bench-cache")


def altered_answer(monkeypatch):
    """The engine alters one value of each non-empty answer it makes."""
    from repro.core.spmd import SpmdEngine
    orig = SpmdEngine._execute

    def broken(self, query):
        res = orig(self, query)
        if res.num_rows:
            col = next(iter(res.bindings))
            res.bindings[col] = res.bindings[col].copy()
            res.bindings[col][0] += 1
        return res
    monkeypatch.setattr(SpmdEngine, "_execute", broken)


def half_batch(monkeypatch):
    """The engine runs the first half of each batch and hands its
    answers round to the rest."""
    from repro.core.spmd import SpmdEngine
    orig = SpmdEngine._execute_batch

    def broken(self, batch):
        done = orig(self, batch[:(len(batch) + 1) // 2])
        return [done[i % len(done)] for i in range(len(batch))]
    monkeypatch.setattr(SpmdEngine, "_execute_batch", broken)


def stale_state(monkeypatch):
    """The engine answers each query with the answer of the previous
    query of its shape: its state does not move on."""
    from repro.core.spmd import SpmdEngine
    orig = SpmdEngine._execute
    last = {}

    def broken(self, query):
        res = orig(self, query)
        key = query.normalize().edges
        prev, last[key] = last.get(key), res
        return prev if prev is not None else res
    monkeypatch.setattr(SpmdEngine, "_execute", broken)


def no_exchange(monkeypatch):
    """No exchange between chips: every join step skips its collective,
    and the host reads only the first chip's block of the final gather,
    as if each chip's rows had stayed on it."""
    from repro.core import spmd
    plan_comm, run_exact = spmd.plan_step_comm, spmd.SpmdEngine._run_exact

    def no_step_exchange(store, pattern, enabled=True, route=None):
        return tuple(spmd.StepComm("skip", sc.prop, 0, sc.edge_rows)
                     for sc in plan_comm(store, pattern, enabled, route))

    def first_chip_only(self, norm):
        bind, valid, caps, attempts = run_exact(self, norm)
        valid = valid.copy()
        valid[caps[-1]:] = False
        return bind, valid, caps, attempts
    monkeypatch.setattr(spmd, "plan_step_comm", no_step_exchange)
    monkeypatch.setattr(spmd.SpmdEngine, "_run_exact", first_chip_only)


@pytest.mark.parametrize("name", [ONE, MESH])
def test_sound_run_is_correct(name):
    line = small.execute(small.cell(name), seed=2**31 + 5)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 20 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"qps", "p50_ms", "p90_ms", "setup_s"}


def test_a_new_mix_is_data_alone():
    """One closed-loop client (the ``serial`` mix of the open questions)
    is the ``lubm-c16`` file with another client count: no code."""
    spec = small.cell(ONE)
    spec["mix"] = dict(spec["mix"], name="serial", clients=1)
    line = small.execute(spec, seed=4)
    assert line["correct"] and line["attempted"] > 5


@pytest.mark.parametrize("name, fault", [
    (ONE, altered_answer), (ONE, half_batch), (ONE, stale_state),
    (MESH, altered_answer), (MESH, half_batch), (MESH, stale_state),
    (MESH, no_exchange)])
def test_fault_is_not_correct(monkeypatch, name, fault):
    fault(monkeypatch)
    line = small.execute(small.cell(name), seed=11)
    assert not line["correct"]
    assert line["checks"]["not_exact"]["value"] > 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_is_not_correct(monkeypatch, seed):
    control.install(monkeypatch)
    line = small.execute(small.cell(ONE), seed=seed)
    assert not line["correct"]
    assert line["checks"]["not_exact"]["value"] > 0
