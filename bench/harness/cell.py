"""One run of one cell, in the order the benchmark fixes:

1. load (or generate) the graph;
2. load the plan, or build it and save it;
3. build the session and the front door;
4. warm up exactly the mix's shapes at their capacity tier;
5. measure for ``seconds``;
6. compare every answer of the window with the plain reference;
7. hand back the result line.

Everything before the window is set-up (``setup_s``); the comparison
runs after the window, with the program's state released, and is not
counted in either.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

from . import check, closed_loop, deploy, device, profile, reference, report
from .compile_meter import CompileMeter
from .traffic import QueryStream

#: seed of the warm-up queries (one per shape; never measured)
WARMUP_SEED = 0
#: how long the first answer of each shape may take (it may compile)
WARMUP_TIMEOUT_S = 1800.0


@dataclasses.dataclass
class Run:
    """What a run measured: the metric readers' input."""
    cell: str
    chips: int
    seconds: float
    setup_s: float
    window: tuple                     # (w0, w1) on perf_counter
    requests: List[closed_loop.Request]        # pre-roll and window
    answered_ok: List[bool]
    door_before: Dict[str, float]
    door_after: Dict[str, float]
    compiles_window: int
    memory_peak_bytes: int
    spans: Optional[List[Any]] = None          # program root spans
    trace: Optional[profile.Trace] = None      # device operations

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def in_window(self) -> List[closed_loop.Request]:
        """The requests submitted in the window."""
        return [r for r in self.requests if r.submitted >= self.window[0]]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run(cell: Dict, jax, devices: List, meter: CompileMeter, t_start: float,
        seed: int, seconds: float, traced: bool) -> Run:
    """Steps 1 to 5 of one run: the measurements, with the program's
    state released at the end."""
    from repro.core import QueryGraph
    from repro.obs.trace import Tracer
    config, mix = cell["config"], cell["mix"]
    if mix["loop"] != "closed":
        raise ValueError(f"mix {mix['name']!r}: loop {mix['loop']!r} is not "
                         f"driven by this harness")
    s, p, o, nv = deploy.triples(config)
    graph = deploy.program_graph(config, s, p, o, nv)
    log(f"graph: {graph.num_edges} triples, {nv} vertex ids "
        f"({time.perf_counter() - t_start:.3f} s since start)")
    plan = deploy.plan(config, graph)
    log(f"plan: {plan.strategy} over {plan.num_sites} sites "
        f"({time.perf_counter() - t_start:.3f} s since start)")
    tracer = Tracer(enabled=True, capacity=1 << 16) if traced else None
    session = deploy.session(config, plan, devices, tracer)
    door = deploy.front_door(config, session).start()
    prop_id = deploy.property_ids(config)
    named = deploy.named_vertices(config)
    c0 = meter.snapshot()
    warm = QueryStream(mix, prop_id, named, s, p, o, WARMUP_SEED)
    futures = [door.submit(QueryGraph.make(warm.query(i, 0)),
                           deadline_s=WARMUP_TIMEOUT_S)
               for i in range(len(mix["shapes"]))]
    for f in futures:
        f.result(timeout=WARMUP_TIMEOUT_S)
    c1 = meter.snapshot()
    log(f"warm-up: {len(futures)} shapes, {c1[0] - c0[0]} backend compiles "
        f"({c1[1] - c0[1]:.3f} s), {c1[2] - c0[2]} persistent-cache hits, "
        f"capacity retries {session.stats().extra['capacity_retries']:.0f}")
    if tracer is not None:
        tracer.store.clear()

    stream = QueryStream(mix, prop_id, named, s, p, o, seed)
    marks: Dict[str, Any] = {}
    trace_dir = str(deploy.CACHE / "profile" / cell["cell"]["name"])

    def on_start() -> None:
        marks["door"] = door.stats()
        marks["compiles"] = meter.snapshot()[0]
        marks["setup_s"] = time.perf_counter() - t_start
        if traced:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            marks["window"] = jax.profiler.TraceAnnotation(profile.WINDOW)
            marks["window"].__enter__()

    def on_end() -> None:
        if traced:
            marks["window"].__exit__(None, None, None)
            jax.profiler.stop_trace()
        marks["door_end"] = door.stats()
        marks["compiles_end"] = meter.snapshot()[0]

    w0, w1, requests = closed_loop.run(
        door, stream, QueryGraph.make, int(mix["clients"]),
        float(mix["think_ms"]) / 1e3, float(mix["preroll_s"]), seconds,
        on_start, on_end)
    door.close()
    peak = device.memory_peak_bytes(devices)
    del door, session, plan, graph
    gc.collect()
    spans = tracer.store.spans() if tracer is not None else None
    trace = None
    if traced:
        trace = profile.read(trace_dir, w0, w1)
        shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"window: {sum(r.submitted >= w0 for r in requests)} requests "
        f"({len(requests)} with the pre-roll) in {w1 - w0:.3f} s; compiles "
        f"in the window {marks['compiles_end'] - marks['compiles']}")
    return Run(cell["cell"]["name"], len(devices), seconds,
               marks["setup_s"], (w0, w1), requests, [],
               marks["door"], marks["door_end"],
               marks["compiles_end"] - marks["compiles"], peak, spans, trace)


def judge(cell: Dict, result: Run) -> Dict:
    """Step 6: every answer of the window against the reference.
    Fills ``result.answered_ok``; returns the result line's ``checks``
    with the verdict under ``"correct"``."""
    t0 = time.perf_counter()
    s, p, o, _nv = deploy.triples(cell["config"])
    index = reference.TripleIndex(s, p, o)
    counts, ok = check.compare(index, result.requests)
    result.answered_ok = ok
    correct, checks = check.verdict(counts)
    log(f"reference: {len(ok)} requests compared in "
        f"{time.perf_counter() - t0:.3f} s: {counts['wrong']} wrong, "
        f"{counts['unanswered']} unanswered, {counts['failed']} failed")
    return {"correct": correct, "checks": checks}


def execute(cell: Dict, jax, devices: List, t_start: float, seed: int,
            seconds: float, traced: bool) -> Dict:
    """Steps 1 to 7 on ``devices``, whose kind the caller has checked:
    the result line."""
    meter = CompileMeter(jax)
    result = run(cell, jax, devices, meter, t_start, seed, seconds, traced)
    verdict = judge(cell, result)
    return report.line(cell, result, verdict, devices, traced)
