"""Elastic resharding properties (contract 16), host-side.

Everything here runs on a single device: ``reshard_tree``/``reshard_index``
accept a bare ``shards=`` count and ``migrate_sharded_state`` is pure host
numpy when no mesh is given, so the bit-exactness properties of the scale
path are checked without a multi-device mesh. The in-flight straddle runs
(lanes migrated mid-ladder across a real grow/shrink) live in
``tests/dist_scripts/elastic_scale_check.py`` with forced host devices.
"""
import types

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.distributed.elastic import plan, reshard_tree
from repro.sharded_search.search import (ShardedSearchState,
                                         build_sharded_index,
                                         migrate_sharded_state,
                                         reshard_index)

_INDEX_FIELDS = ("vectors", "neighbors", "entries", "bases", "codes",
                 "scales", "codebooks")


def _assert_index_equal(a, b):
    assert (a.metric, a.scheme, a.scale_rows) == (b.metric, b.scheme,
                                                  b.scale_rows)
    for f in _INDEX_FIELDS:
        x, y = getattr(a, f), getattr(b, f)
        assert (x is None) == (y is None), f
        if x is not None:
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=f)


def _corpus(seed, n=128, d=8):
    return np.random.default_rng(seed).normal(size=(n, d)).astype(np.float32)


def _rand_state(rng, p, B, C, ns) -> ShardedSearchState:
    """A synthetic in-flight state obeying the queue conventions: per
    (shard, lane) queue canonically sorted (score desc, global id asc),
    empty slots (-1, -inf, True)."""
    ids = np.full((p, B, C), -1, np.int32)
    scores = np.full((p, B, C), -np.inf, np.float32)
    stable = np.ones((p, B, C), bool)
    for s in range(p):
        for b in range(B):
            m = int(rng.integers(0, min(C, ns) + 1))
            loc = rng.choice(ns, size=m, replace=False)
            sc = rng.normal(size=m).astype(np.float32)
            order = np.lexsort((loc + s * ns, -sc))
            ids[s, b, :m] = loc[order].astype(np.int32)
            scores[s, b, :m] = sc[order]
            stable[s, b, :m] = rng.random(m) < 0.5
    return ShardedSearchState(
        ids=ids, scores=scores, stable=stable,
        visited=rng.random((p, B, ns)) < 0.3,
        steps=rng.integers(0, 50, size=(p, B)).astype(np.int32))


# -- reshard_tree / reshard_index ------------------------------------------


@given(st.integers(0, 10_000))
@settings(max_examples=8, deadline=None)
def test_reshard_tree_index_roundtrip_bit_identical(seed):
    """4 -> 8 -> 4 restores every array field of the corpus exactly, for
    float, int8, and pq corpora alike (global ids never move; graphs are
    rebuilt deterministically from the same rows)."""
    x = _corpus(seed)
    for quantized in (None, "int8", "pq"):
        idx4 = build_sharded_index(x, 4, "l2", M=4, quantized=quantized,
                                   scale_rows=2, pq_m=4)
        av = x if quantized else None
        idx8 = reshard_tree(idx4, shards=8, all_vectors=av)
        assert idx8.num_shards == 8 and idx8.shard_size == 16
        back = reshard_tree(idx8, shards=4, all_vectors=av)
        _assert_index_equal(idx4, back)


@given(st.integers(0, 10_000))
@settings(max_examples=8, deadline=None)
def test_reshard_quantized_codes_scales_exact(seed):
    """Quantized reshard is a pure re-blocking: the flattened code rows and
    scale blocks are bytewise-identical — no requantization ever happens on
    a scale event."""
    x = _corpus(seed)
    i8 = build_sharded_index(x, 4, "l2", M=4, quantized="int8", scale_rows=2)
    i8r = reshard_index(i8, 8, x)
    np.testing.assert_array_equal(
        np.asarray(i8.codes).reshape(len(x), -1),
        np.asarray(i8r.codes).reshape(len(x), -1))
    np.testing.assert_array_equal(np.asarray(i8.scales).reshape(-1),
                                  np.asarray(i8r.scales).reshape(-1))
    pq = build_sharded_index(x, 4, "l2", M=4, quantized="pq", pq_m=4)
    pqr = reshard_index(pq, 2, x)
    np.testing.assert_array_equal(
        np.asarray(pq.codes).reshape(len(x), -1),
        np.asarray(pqr.codes).reshape(len(x), -1))
    np.testing.assert_array_equal(np.asarray(pq.codebooks),
                                  np.asarray(pqr.codebooks))


def test_reshard_index_validation():
    x = _corpus(0, n=64)
    idx = build_sharded_index(x, 4, "l2", M=4)
    with pytest.raises(ValueError):
        reshard_index(idx, 3, x)                    # not a power of two
    with pytest.raises(ValueError):
        reshard_index(idx, 128, x)                  # rows don't divide
    i8 = build_sharded_index(x, 4, "l2", M=4, quantized="int8",
                             scale_rows=16)
    with pytest.raises(ValueError):
        reshard_index(i8, 8, x)                     # scale blocks would split
    with pytest.raises(ValueError):
        reshard_index(i8, 2, None)                  # quantized needs floats
    assert reshard_index(idx, 4, x) is idx          # same count: no-op
    with pytest.raises(ValueError):
        reshard_tree(idx)                           # needs mesh or shards=


# -- plan ------------------------------------------------------------------


def _mesh_stub(sizes: dict):
    return types.SimpleNamespace(
        axis_names=tuple(sizes), devices=np.zeros(tuple(sizes.values())))


@given(st.integers(0, 3), st.integers(0, 3), st.integers(0, 2),
       st.integers(0, 2))
@settings(max_examples=30, deadline=None)
def test_plan_inverses(d0, d1, m0, m1):
    a = _mesh_stub({"data": 2 ** d0, "model": 2 ** m0})
    b = _mesh_stub({"data": 2 ** d1, "model": 2 ** m1})
    fwd, rev = plan(a, b), plan(b, a)
    assert fwd["old"] == rev["new"] and fwd["new"] == rev["old"]
    assert fwd["dp_change"] == 2.0 ** (d1 - d0)
    assert fwd["tp_change"] == 2.0 ** (m1 - m0)
    for ax, r in fwd["axis_changes"].items():
        assert rev["axis_changes"][ax] == pytest.approx(1.0 / r)


# -- migrate_sharded_state -------------------------------------------------


@given(st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_migrate_state_roundtrip_bit_identical(seed):
    """Grow 4 -> 8 then shrink back restores the state exactly: queues
    re-bucket by global id and re-sort canonically, visited bits follow
    their rows, per-lane step totals ride the split/merge."""
    rng = np.random.default_rng(seed)
    st4 = _rand_state(rng, p=4, B=3, C=8, ns=32)
    st8 = migrate_sharded_state(st4, 8)
    back = migrate_sharded_state(st8, 4)
    for name in ShardedSearchState._fields:
        np.testing.assert_array_equal(np.asarray(getattr(st4, name)),
                                      np.asarray(getattr(back, name)),
                                      err_msg=name)
    # the lane's cumulative budget baseline is shard-summed expansions —
    # preserved through both directions, so resume_search's relative
    # max_steps stays exact for migrated lanes
    tot = np.asarray(st4.steps).sum(axis=0)
    np.testing.assert_array_equal(np.asarray(st8.steps).sum(axis=0), tot)
    np.testing.assert_array_equal(np.asarray(back.steps).sum(axis=0), tot)


@given(st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_migrate_state_preserves_entries_and_visited(seed):
    """Every (global id, score, stable) queue entry and every visited
    global row survives migration verbatim, per lane."""
    rng = np.random.default_rng(seed)
    p, ns = 4, 32
    state = _rand_state(rng, p=p, B=2, C=8, ns=ns)
    for p_new in (8, 2):
        # a shrink merges queues: size the target like callers do
        cap = 8 * max(1, p // p_new)
        out = migrate_sharded_state(state, p_new, capacity=cap)
        ns_new = p * ns // p_new
        for b in range(2):
            def entries(ids, sc, stbl, width):
                es = set()
                for s in range(ids.shape[0]):
                    for c in range(ids.shape[2]):
                        i = int(ids[s, b, c])
                        if i >= 0:
                            es.add((i + s * width, float(sc[s, b, c]),
                                    bool(stbl[s, b, c])))
                return es
            assert (entries(np.asarray(state.ids), np.asarray(state.scores),
                            np.asarray(state.stable), ns)
                    == entries(np.asarray(out.ids), np.asarray(out.scores),
                               np.asarray(out.stable), ns_new))
            old_v = np.asarray(state.visited)[:, b, :].reshape(-1)
            new_v = np.asarray(out.visited)[:, b, :].reshape(-1)
            np.testing.assert_array_equal(old_v, new_v)


@given(st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_migrate_state_lane_scaling(seed):
    """Serving capacity follows the mesh: ``num_lanes`` pads new lanes
    empty on a grow and keeps the surviving prefix verbatim on a shrink
    (the engine only ever drops LANE_FREE tails)."""
    rng = np.random.default_rng(seed)
    state = _rand_state(rng, p=2, B=2, C=8, ns=32)
    wide = migrate_sharded_state(state, 4, num_lanes=4)
    assert np.asarray(wide.ids).shape[1] == 4
    # appended lanes are empty/unseeded
    np.testing.assert_array_equal(np.asarray(wide.ids)[:, 2:], -1)
    assert not np.asarray(wide.visited)[:, 2:].any()
    np.testing.assert_array_equal(np.asarray(wide.steps)[:, 2:], 0)
    # surviving lanes round-trip bit-identically through the lane shrink
    back = migrate_sharded_state(wide, 2, capacity=8, num_lanes=2)
    for name in ShardedSearchState._fields:
        np.testing.assert_array_equal(np.asarray(getattr(state, name)),
                                      np.asarray(getattr(back, name)),
                                      err_msg=name)


def test_migrate_state_capacity_overflow_raises():
    """A shrink that would merge more candidates than the target queue
    holds must refuse loudly (silent truncation would void the widening
    contract), and succeeds once the capacity is sized up."""
    rng = np.random.default_rng(0)
    p, B, C, ns = 4, 2, 8, 64
    ids = np.zeros((p, B, C), np.int32)
    scores = np.zeros((p, B, C), np.float32)
    for s in range(p):
        for b in range(B):
            loc = rng.choice(ns, size=C, replace=False)
            sc = rng.normal(size=C).astype(np.float32)
            order = np.lexsort((loc + s * ns, -sc))
            ids[s, b] = loc[order]
            scores[s, b] = sc[order]
    full = ShardedSearchState(
        ids=ids, scores=scores, stable=np.ones((p, B, C), bool),
        visited=np.zeros((p, B, ns), bool),
        steps=np.zeros((p, B), np.int32))
    with pytest.raises(ValueError, match="capacity"):
        migrate_sharded_state(full, 2)
    out = migrate_sharded_state(full, 2, capacity=16)
    assert out.ids.shape == (2, 2, 16)


# -- protocol / facade gates ----------------------------------------------


def test_rescalable_protocol_detection():
    """The scheduler's elastic trigger checks the declared
    RescalableBackend capability: a single-host ProgressiveEngine (wrapped
    or not) must NOT declare it, and asking for elastic= over one is a loud
    constructor error."""
    from repro.core.backend import is_rescalable
    from repro.core.batch_progressive import ProgressiveEngine
    from repro.index.flat import build_knn_graph
    from repro.index.mutable import MutableBackend, MutableIndex
    from repro.serve.scheduler import LaneScheduler

    x = _corpus(1, n=64)
    eng = ProgressiveEngine(build_knn_graph(x, metric="l2", M=4), 2,
                            max_k=4)
    assert not is_rescalable(eng)
    mi = MutableIndex(x, "l2", M=4)
    wrapped = MutableBackend(ProgressiveEngine(mi.graph, 2, max_k=4), mi)
    assert not is_rescalable(wrapped)
    with pytest.raises(ValueError, match="elastic"):
        LaneScheduler(backend=wrapped, prewarm=False, elastic=True)


def test_db_elastic_single_device_raises():
    """elastic= needs >= 2 visible devices (there is nothing to scale
    between on one); shards='auto' alone resolves to the device count."""
    import jax

    from repro.db import DiverseVectorDB

    x = _corpus(2, n=64)
    if jax.device_count() >= 2:
        pytest.skip("requires a single-device process")
    with pytest.raises(ValueError, match="devices"):
        DiverseVectorDB(x, "l2", shards="auto", elastic=True, prewarm=False)
    db = DiverseVectorDB(x, "l2", shards="auto", M=4, num_lanes=2,
                         max_k=4, prewarm=False)
    assert db.backend.num_shards == 1
    assert db.backend.rescale_options() == (1,)
    r = db.search(x[3], k=3, eps=2.0)
    assert r.stats.certified
