"""Compile-only checks: every Pallas kernel of the serving path, lowered and
compiled by Mosaic for a described (not attached) TPU v5e chip.

Interpret mode cannot see tiling, layout or VMEM refusals; the TPU compiler
installed alongside jax can, without a chip. Nothing runs here, so these
tests say nothing about results or speed. Shapes are the paper's widths
(d = 96 l2, 200 ip, 512 cos) at the engine's lane count and candidate
windows, plus the sharded path's vmapped merge and single-query greedy, a
lane's start state over the graph's entry candidates, and on a described
2x2 mesh the sharded search dispatch and the replicated diversify stage.

The topology is described inside a module fixture (never at import), so
under several test workers only the worker given this file loads the TPU
compiler library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

LANES = 8
K_MAX = 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes, sharding):
    # kernels called without an explicit rung would take the CPU's jnp
    # oracle here: select the Pallas rung, as the chip's "auto" does
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    ops.set_default_impl("pallas")
    try:
        compiled = jax.jit(fn).lower(*args).compile()
    finally:
        ops.set_default_impl(None)
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


F32, I32, I8 = jnp.float32, jnp.int32, jnp.int8


def _fused_round(metric, d, W):
    fn = lambda v, i, s, K, e: ops.fused_round_batch(  # noqa: E731
        v, i, s, K, e, K_MAX, metric, impl="pallas")
    return fn, [((16384, d), F32), ((LANES, W), I32), ((LANES, W), F32),
                ((LANES,), I32), ((LANES,), F32)]


def _greedy_batch(W):
    fn = lambda s, a: ops.greedy_diversify_batch(  # noqa: E731
        s, a, K_MAX, impl="pallas")
    return fn, [((LANES, W), F32), ((LANES, W, W), jnp.bool_)]


def _greedy_single(K):
    fn = lambda s, a, v: ops.greedy_diversify(  # noqa: E731
        s, a, 10, valid=v, impl="pallas")
    return fn, [((K,), F32), ((K, K), jnp.bool_), ((K,), jnp.bool_)]


def _batch_similarity(metric, d, n):
    fn = lambda q, x: ops.batch_similarity_many(  # noqa: E731
        q, x, metric, impl="pallas")
    return fn, [((LANES, d), F32), ((n, d), F32)]


def _adjacency(metric, d, K):
    fn = lambda x, e, v: ops.pairwise_adjacency(  # noqa: E731
        x, e, metric, v, impl="pallas")
    return fn, [((K, d), F32), ((), F32), ((K,), jnp.bool_)]


def _sharded_topk_merge(L):
    # the tournament merge's call shape: vmapped over the query batch
    fn = jax.vmap(lambda ia, sa, ib, sb: ops.topk_merge(  # noqa: E731
        ia, sa, ib, sb, impl="pallas"))
    return fn, [((LANES, L), I32), ((LANES, L), F32),
                ((LANES, L), I32), ((LANES, L), F32)]


def _int8_dot(d, n):
    from repro.kernels.int8_similarity import int8_dot_pallas
    return int8_dot_pallas, [((LANES, d), I8), ((n, d), I8)]


def _pq_lut_sum(m, n):
    from repro.kernels.pq_lut_similarity import pq_lut_sum_pallas
    return pq_lut_sum_pallas, [((LANES, m, 256), F32), ((n, m), jnp.uint8)]


def _lane_init(metric, d, n, E):
    # a lane's start state: the best of E entry candidates, then the queue
    from repro.core import lane_state
    from repro.core.graph import FlatGraph

    def fn(v, nb, up, e, qs):
        graph = FlatGraph(v, nb, up, e, metric)
        return lane_state.init_lanes(graph, qs, 1024).queue.ids

    return fn, [((n, d), F32), ((n, 32), I32), ((0, n, 1), I32), ((E,), I32),
                ((LANES, d), F32)]


CASES = {
    "fused_round-l2-d96-W256": lambda: _fused_round("l2", 96, 256),
    "fused_round-ip-d200-W1024": lambda: _fused_round("ip", 200, 1024),
    "fused_round-cos-d512-W2048": lambda: _fused_round("cos", 512, 2048),
    "greedy_batch-W2048": lambda: _greedy_batch(2048),
    "greedy_single-K256": lambda: _greedy_single(256),
    "batch_similarity-l2-d96-n65536": lambda: _batch_similarity("l2", 96,
                                                                65536),
    "pairwise_adjacency-cos-d512-K1024": lambda: _adjacency("cos", 512, 1024),
    "topk_merge-sharded-L64": lambda: _sharded_topk_merge(64),
    "int8_dot-d200-n16384": lambda: _int8_dot(200, 16384),
    "pq_lut_sum-m24-n16384": lambda: _pq_lut_sum(24, 16384),
    "lane_init-cos-d512-E1024": lambda: _lane_init("cos", 512, 16384, 1024),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    fn, shapes = CASES[case]()
    _compile(fn, *shapes, sharding=one_chip)


@pytest.mark.parametrize("method", ["div_astar", "greedy"])
def test_sharded_diversify_compiles_for_v5e_2x2(method, topo):
    """The sharded path's replicated diversify stage (adjacency + greedy
    kernels) over candidates replicated on a 4-chip mesh: Mosaic kernels
    cannot be partitioned, so the stage must compile as a shard_map."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.sharded_search.search import _replicated_diversify

    mesh = Mesh(topo.devices, ("data",))
    fn = _replicated_diversify(mesh, False, "l2", 10, 64, method, 1000)
    _compile(fn, ((32768, 96), F32), ((LANES, 64), I32),
             ((LANES, 64), F32), ((LANES,), F32),
             sharding=NamedSharding(mesh, P()))


def test_sharded_search_dispatch_compiles_for_v5e_2x2(topo):
    """The sharded engine's resume dispatch on a 4-chip mesh: shard-local
    beams seeded at the best of each shard's entry candidates, the beam
    loop's similarity kernel and the tournament merge, as one shard_map."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.sharded_search.search import ShardedIndex, _resume_dispatch_fn

    S, ns, d, B, C, g, E = 4, 8192, 96, LANES, 1024, LANES, 1024
    sds = jax.ShapeDtypeStruct
    mesh = Mesh(topo.devices, ("data",))
    shard, repl = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    index = ShardedIndex(vectors=sds((S, ns, d), F32),
                         neighbors=sds((S, ns, 32), I32),
                         entries=sds((S, E), I32), bases=sds((S,), I32),
                         metric="l2")
    ops.set_default_impl("pallas")
    try:
        fn = _resume_dispatch_fn(index, mesh, "data", 64, C, "tournament")
        args = [sds(s, dt, sharding=shard) for s, dt in (
            ((S, ns, d), F32), ((S, ns, 32), I32), ((S, E), I32), ((S,), I32),
            ((S, B, C), I32), ((S, B, C), F32), ((S, B, C), jnp.bool_),
            ((S, B, ns), jnp.bool_), ((S, B), I32))]
        args += [sds(s, dt, sharding=repl) for s, dt in (
            ((g, d), F32), ((g,), I32), ((g,), jnp.bool_), ((), I32),
            ((), I32))]
        compiled = fn.lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
    finally:
        ops.set_default_impl(None)
