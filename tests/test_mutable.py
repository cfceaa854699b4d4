"""Online mutable index (contract 15): delta flat-oracle parity, deletion
bitmap filtering, write backpressure, certificate soundness after writes,
and the epoch-swap straddle with in-flight multi-round lanes."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import theorems
from repro.db import DiverseVectorDB, Query
from repro.index.mutable import DeltaFull, MutableIndex
from repro.kernels import ops as kops
from repro.serve.scheduler import RequestDeferred, SchedulerSaturated


@pytest.mark.parametrize("quantized", [None, "int8"])
def test_delta_bitmatches_flat_oracle(clustered_data, quantized):
    """Delta-segment scoring is a flat scan through kernels/ops: ids and
    scores bit-match ``batch_similarity`` over exactly the live tail rows
    (the int8 corpus still returns exact float scores — contract 13)."""
    x = clustered_data
    idx = MutableIndex(x, "l2", M=8, delta_capacity=64, background=False,
                       quantized=quantized)
    rng = np.random.default_rng(1)
    new = rng.normal(size=(7, x.shape[1])).astype(np.float32)
    ids = idx.upsert(new)
    assert np.array_equal(ids, np.arange(len(x), len(x) + 7))
    q = x[0] + np.float32(0.01)
    d_ids, d_sc = idx.score_delta(q)
    assert np.array_equal(d_ids, ids)
    ref = np.asarray(kops.batch_similarity(
        jnp.asarray(q), jnp.asarray(new), "l2"), np.float32)
    np.testing.assert_array_equal(d_sc, ref)
    # a deleted delta row leaves the live scan; the rest stay bit-equal
    idx.delete(ids[2:3])
    d_ids2, d_sc2 = idx.score_delta(q)
    keep = np.arange(7) != 2
    assert np.array_equal(d_ids2, ids[keep])
    np.testing.assert_array_equal(d_sc2, ref[keep])


def test_delete_validates_and_counts(clustered_data):
    idx = MutableIndex(clustered_data, "l2", M=8, background=False)
    with pytest.raises(KeyError):
        idx.delete([len(clustered_data)])
    with pytest.raises(KeyError):
        idx.delete([-1])
    assert idx.delete([3, 5]) == 2
    assert idx.delete([5, 7]) == 1      # 5 already tombstoned
    assert idx.live_count == len(clustered_data) - 3
    assert idx.deleted[[3, 5, 7]].all()


def test_delta_full_backpressure(clustered_data):
    """Past four delta capacities with the rebuild not yet swapped in,
    upsert raises ``DeltaFull`` instead of growing without bound."""
    idx = MutableIndex(clustered_data, "l2", M=8, delta_capacity=4,
                       background=False)
    rng = np.random.default_rng(2)
    for _ in range(16):
        idx.upsert(rng.normal(size=(1, clustered_data.shape[1]))
                   .astype(np.float32))
    assert idx.delta_count == 16
    with pytest.raises(DeltaFull):
        idx.upsert(rng.normal(size=(1, clustered_data.shape[1]))
                   .astype(np.float32))
    # the rebuild auto-requested at the first capacity crossing (n=604) is
    # ready; installing it keeps only the 12 rows written after that
    # snapshot in the delta
    assert idx.swap_ready()
    idx.install_swap()
    assert idx.delta_count == 12 and idx.epoch == 1
    idx.upsert(rng.normal(size=(1, clustered_data.shape[1]))
               .astype(np.float32))   # accepts writes again


def _submit(db, q, reqs):
    while True:
        try:
            reqs.append(db.scheduler.submit(q))
            return
        except (SchedulerSaturated, RequestDeferred):
            db.scheduler.pump()


def _poll(db, reqs, metas, frontiers):
    """Capture each completed request's harvest-time snapshot tag and
    merged frontier (per-lane slots are stable until the next harvest on
    that lane, so polling after every pump sees them first)."""
    for r in reqs:
        if (r.result is not None and r.lane is not None
                and id(r) not in metas):
            metas[id(r)] = db.backend.last_meta[r.lane]
            frontiers[id(r)] = db.backend.last_candidates[r.lane]


def test_epoch_swap_straddle_flat(clustered_data):
    """Contract 15 on the single-host engine: upserts/deletes interleave
    with in-flight multi-round lanes; the delta fills mid-run and the
    rebuilt graph swaps in between rounds. Every result must be valid
    against exactly one corpus version — served ids inside that version's
    row range, never tombstoned there — and every certified lane must pass
    an independent Theorem-2 recheck of its merged frontier."""
    x = clustered_data
    rng = np.random.default_rng(3)
    db = DiverseVectorDB(x, "l2", M=8, num_lanes=3, max_k=8, default_ef=12,
                         delta_capacity=8, background_rebuild=False,
                         prewarm=False)
    qs = (x[rng.integers(0, len(x), 12)]
          + 0.05 * rng.normal(size=(12, x.shape[1]))).astype(np.float32)
    # version -> (n_total, deleted bitmap) after every write we perform —
    # the only events that change the live set (swaps bump version only)
    snaps = {}

    def snap():
        snaps[db.index.version] = (db.index.n_total,
                                   db.index.deleted.copy())

    snap()
    reqs, metas, frontiers = [], {}, {}
    deleted_ever = set()
    for i in range(6):
        _submit(db, Query(qs[i], k=5, eps=0.0, ef=12), reqs)
    db.scheduler.pump()
    _poll(db, reqs, metas, frontiers)
    # writes land while lanes are mid-flight / requests are queued
    assert db.scheduler.inflight or db.scheduler.pending
    db.upsert(qs[:3] + np.float32(0.01))
    snap()
    deleted_ever.update((17, 23))
    db.delete([17, 23])
    snap()
    for i in range(6, 9):
        _submit(db, Query(qs[i], k=5, eps=0.0, ef=12), reqs)
    db.scheduler.pump()
    _poll(db, reqs, metas, frontiers)
    db.upsert(rng.normal(size=(6, x.shape[1]))
              .astype(np.float32))          # crosses capacity -> rebuild
    snap()
    assert db.index.swap_ready()            # inline rebuild is ready
    for i in range(9, 12):
        _submit(db, Query(qs[i], k=5, eps=0.0, ef=12), reqs)
    while any(r.result is None for r in reqs):
        db.scheduler.pump()
        _poll(db, reqs, metas, frontiers)
    assert db.backend.swaps == 1 and db.index.epoch == 1
    epochs = set()
    for r in reqs:
        meta = metas[id(r)]
        epochs.add(meta["epoch"])
        v = max(ver for ver in snaps if ver <= meta["version"])
        n_at, dele_at = snaps[v]
        ids = np.asarray(r.result.ids)
        ids = ids[ids >= 0]
        assert ids.size and (ids < n_at).all(), \
            f"result holds rows from a newer version than its tag {meta}"
        assert not dele_at[ids].any(), \
            f"tombstoned id served (version {v})"
        assert not deleted_ever.intersection(ids.tolist())
        if r.result.stats.certified:
            m_ids, m_sc = frontiers[id(r)][0], frontiers[id(r)][1]
            ok, sel = theorems.theorem2_recheck(
                db.index.float_view()[:n_at], "l2", m_ids, m_sc, 0.0, 5)
            assert ok and np.array_equal(
                np.asarray(sel), np.asarray(r.result.ids))
    assert epochs == {0, 1}, f"results straddle the swap: {epochs}"
    assert any(r.result.stats.certified for r in reqs)
    # post-swap service is clean: fresh searches certify on epoch 1
    r = db.search(Query(qs[0], k=5, eps=0.0, ef=12))
    assert 600 in r.ids.tolist()            # upserted near-dup of qs[0]


def test_swap_preserves_signature_budget(clustered_data):
    """The epoch swap re-notes compile signatures on the carried-over log
    instead of resetting it (compile-budget accounting survives swaps)."""
    db = DiverseVectorDB(clustered_data, "l2", M=8, num_lanes=2, max_k=8,
                         default_ef=12, delta_capacity=4,
                         background_rebuild=False, prewarm=False)
    db.search(clustered_data[0], k=3, eps=0.0)
    before = len(db.backend.signature_log.counts)
    db.upsert(np.zeros((4, clustered_data.shape[1]), np.float32))
    assert db.rebuild(wait=True) or db.backend.swaps  # swap installed
    log = db.backend.signature_log
    assert len(log.counts) >= before                  # log carried across
    assert any(sig[0] == "swap" for sig in log.counts)
    assert db.index.epoch >= 1


def test_certificates_reaudited_against_live_corpus(clustered_data):
    """After a write, a harvested certificate is only kept if the merged
    frontier (graph candidates + delta, bitmap-filtered) re-certifies via
    Theorem 2 — and the served set equals the audit's selection."""
    x = clustered_data
    db = DiverseVectorDB(x, "l2", M=8, num_lanes=2, max_k=8, default_ef=12,
                         prewarm=False)
    q = (x[7] + 0.02 * np.random.default_rng(5).normal(size=x.shape[1])
         ).astype(np.float32)
    base = db.search(Query(q, k=4, eps=0.0, ef=12))
    # upsert two near-duplicates of the query: they dominate the top of
    # the merged frontier, so the served set must include them
    new_ids = db.upsert(np.stack([q, q]) + np.float32(1e-3))
    res = db.search(Query(q, k=4, eps=0.0, ef=12))
    assert int(new_ids[0]) in res.ids.tolist()
    lane = None
    for ln, fr in enumerate(db.backend.last_candidates):
        if fr is not None and np.isin(res.ids, fr[0]).all():
            lane = ln
    assert lane is not None
    m_ids, m_sc = db.backend.last_candidates[lane][:2]
    ok, sel = theorems.theorem2_recheck(
        db.index.float_view(), "l2", m_ids, m_sc, 0.0, 4)
    assert ok == res.stats.certified
    if ok:
        assert np.array_equal(np.asarray(sel), np.asarray(res.ids))
    # the write changed the served set (the near-dup outranks base's top)
    assert int(new_ids[0]) not in np.asarray(base.ids).tolist()


@pytest.mark.parametrize("background", [True, False])
def test_failed_rebuild_is_reraised(clustered_data, monkeypatch, background):
    """A rebuild that raises is not lost: the error surfaces from
    ``wait_rebuild`` (or at once, in the foreground), no swap is left
    pending, and the next rebuild starts clean."""
    idx = MutableIndex(clustered_data, "l2", M=8, background=background)

    def boom(snap, dead=None):
        raise MemoryError("build failed")

    monkeypatch.setattr(idx, "_build", boom)
    if background:
        assert idx.request_rebuild()
        with pytest.raises(RuntimeError, match="rebuild failed") as err:
            idx.wait_rebuild()
    else:
        with pytest.raises(RuntimeError, match="rebuild failed") as err:
            idx.request_rebuild()
    assert isinstance(err.value.__cause__, MemoryError)
    assert not idx.swap_ready()
    monkeypatch.undo()
    assert idx.request_rebuild()
    idx.wait_rebuild()
    assert idx.swap_ready()


@pytest.mark.parametrize("builder", ["knng", "hnsw"])
def test_rebuild_unlinks_deleted_rows(clustered_data, builder):
    """A rebuild keeps tombstoned rows in place (ids stay positional) but
    links only live rows: no edge leads to or from a deleted row, at any
    level, and the entry point is live — so no search spends frontier
    slots on a deleted point."""
    idx = MutableIndex(clustered_data, "l2", M=8, builder=builder,
                       background=False)
    dead = np.array([3, 5, 7, 100, 599])
    idx.delete(dead)
    assert idx.request_rebuild()
    idx.install_swap()
    g = idx.graph
    nbrs, upper = np.asarray(g.neighbors), np.asarray(g.upper)
    assert (nbrs[dead] < 0).all() and not np.isin(nbrs, dead).any()
    assert (upper[:, dead] < 0).all() and not np.isin(upper, dead).any()
    assert not np.isin(np.asarray(g.entry), dead).any()
    np.testing.assert_array_equal(np.asarray(g.vectors), clustered_data)
    live = np.setdiff1d(np.arange(len(clustered_data)), dead)
    assert (nbrs[live] >= 0).any(axis=1).all()


def _linked(graph, ids) -> bool:
    nbrs, upper = np.asarray(graph.neighbors), np.asarray(graph.upper)
    return bool((nbrs[ids] >= 0).any() or np.isin(nbrs, ids).any()
                or np.isin(upper, ids).any())


@pytest.mark.parametrize("background", [True, False])
def test_delete_after_auto_rebuild_is_honoured(clustered_data, background):
    """Upserts that fill the delta start a rebuild at once; a delete that
    lands after that snapshot makes the build stale. The stale structure
    is never installed: ``rebuild(wait=True)`` builds again over the
    current rows, so the swapped-in graph links no deleted row."""
    x = clustered_data
    db = DiverseVectorDB(x, "l2", M=8, delta_capacity=16, num_lanes=2,
                         prewarm=False, background_rebuild=background)
    rng = np.random.default_rng(5)
    db.upsert(x[:16] + rng.normal(size=(16, x.shape[1])).astype(np.float32)
              * np.float32(0.01))          # fills the delta: rebuild starts
    dead = np.array([2, 40, 333])
    db.delete(dead)
    assert db.rebuild(wait=True)
    g = db.index.graph
    assert g.size == len(x) + 16 and not _linked(g, dead)
    assert db.index.delta_count == 0 and not db.index.swap_ready()
    res = db.search(x[40], k=5, eps=0.5)
    assert not np.isin(np.asarray(res.ids), dead).any()


def test_delete_of_unlinked_rows_keeps_pending_build(clustered_data):
    """A delete that touches only rows the pending snapshot already holds
    dead (or does not hold) leaves the build in place."""
    x = clustered_data
    idx = MutableIndex(x, "l2", M=8, delta_capacity=4, background=False)
    idx.delete([7])
    assert idx.request_rebuild()
    art = idx._pending[1]
    idx.upsert(x[:2])                      # delta rows: not in the snapshot
    idx.delete([7, len(x)])
    assert idx.swap_ready() and idx._pending[1] is art
    idx.delete([8])                        # linked by the snapshot: stale
    assert idx.swap_ready() and idx._pending[1] is not art
    assert not _linked(idx.install_swap(), [7, 8])


def test_sharded_rebuild_unlinks_deleted_rows(clustered_data):
    """Each shard's rebuilt graph links only its live rows (local ids)."""
    x = clustered_data
    idx = MutableIndex(x, "l2", M=8, shards=2, background=False)
    dead = np.array([3, 299, 300, 599])
    idx.delete(dead)
    assert idx.request_rebuild()
    sh = idx.install_swap()
    ns = sh.neighbors.shape[1]
    for s in range(2):
        local = dead[(dead >= s * ns) & (dead < (s + 1) * ns)] - s * ns
        nbrs = np.asarray(sh.neighbors[s])
        assert (nbrs[local] < 0).all() and not np.isin(nbrs, local).any()
        assert not np.isin(np.asarray(sh.entries[s]), local).any()
