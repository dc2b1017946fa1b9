"""Compile the chip path for a described TPU v5e, without a chip.

The join kernels and the SPMD matcher are lowered and compiled for a
``v5e:2x2`` topology description, so a kernel Mosaic refuses (block
shapes, vector gathers, removed Pallas APIs) fails here on the CPU
instead of at the first query on a chip.  Interpret mode validates a
kernel's arithmetic, never whether it lowers; these tests are the
lowering check.
"""
import importlib.util
import itertools
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import generate_watdiv
from repro.core import spmd as S
from repro.core.query import QueryGraph
from repro.core.workload import make_shape_queries

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def v5e():
    """A described v5e:2x2 topology (no device needed), with the
    persistent compile cache off so every compile really runs."""
    from jax.experimental import topologies
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", before)


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Kernel calls build the compiled (not interpreted) kernel, and the
    match loop takes the kernel path."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setenv("REPRO_SPMD_PALLAS", "1")


def _smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _abstract(args, sharding):
    return [jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                 sharding=sharding) for a in args]


@pytest.mark.parametrize("name", ["semijoin", "join_count", "pair_semijoin",
                                  "dedup_rows", "fused_join"])
def test_join_kernel_compiles_for_v5e(v5e, compiled_kernels, name):
    """Each join kernel at the shapes ``chip_smoke.py`` runs."""
    op, args = _smoke().kernel_cases(0)[name]
    one = jax.sharding.SingleDeviceSharding(v5e.devices[0])
    text = jax.jit(op).lower(*_abstract(args, one)).compile().as_text()
    assert "tpu_custom_call" in text


def _store_and_args(num_sites, mesh):
    g = generate_watdiv(3000, seed=3)
    sites = [np.arange(j, g.num_edges, num_sites) for j in range(num_sites)]
    store = S.SiteStore.build(g, sites)
    sharded = NamedSharding(mesh, P("sites", None))
    return store, _abstract(S._matcher_args(store, True), sharded)


def _compile_matcher(mesh, store, args, pattern, capacity=1024):
    paths = {}
    fn = S.make_spmd_matcher(
        mesh, "sites", pattern, capacity,
        comm=S.plan_step_comm(store, pattern), use_csr=True,
        prop_windows={e.prop: store.prop_window(e.prop)
                      for e in pattern.edges},
        join_paths=paths)
    return fn.lower(*args).compile().as_text(), paths


@pytest.mark.parametrize("shape", ["star", "chain", "cycle"])
def test_spmd_matcher_compiles_on_one_v5e(v5e, compiled_kernels, shape):
    """The whole matcher program of one query shape on a 1-device mesh:
    every join step traces a kernel, none the jnp composition."""
    mesh = Mesh(np.asarray(v5e.devices[:1]), ("sites",))
    store, args = _store_and_args(1, mesh)
    props = itertools.cycle([0, 1, 0])
    pattern = make_shape_queries(lambda: next(props), k=3)[shape]
    text, paths = _compile_matcher(mesh, store, args, pattern)
    assert "tpu_custom_call" in text
    assert paths.get("join_count_kernel", 0) >= 1
    assert paths.get("pair_semijoin_kernel", 0) == (shape == "cycle")
    assert not any(v for k, v in paths.items() if k.endswith("_jnp"))


def test_spmd_matcher_compiles_on_v5e_2x2_mesh(v5e, compiled_kernels,
                                                monkeypatch):
    """A chain over properties spread across all four devices, with the
    kernels a TPU turns on by default: its join step ships between
    devices, so the program holds the blocked kernels and the
    all-gather collectives, and the gathered rows take the jnp dedup +
    expand composition."""
    monkeypatch.delenv("REPRO_SPMD_PALLAS")
    mesh = Mesh(np.asarray(v5e.devices), ("sites",))
    store, args = _store_and_args(4, mesh)
    pattern = QueryGraph.make([(-1, -2, 0), (-2, -3, 1)])
    assert [sc.mode for sc in S.plan_step_comm(store, pattern)] \
        == ["dynamic"]
    text, paths = _compile_matcher(mesh, store, args, pattern,
                                   capacity=65536)
    assert "tpu_custom_call" in text
    assert "all-gather" in text
    assert paths["jnp_join"] == 1 and paths["lexsort_dedup"] == 1
    assert paths["join_count_kernel"] == 2
    assert "fused_join_kernel" not in paths
    assert "hash_dedup_kernel" not in paths
