"""Epochstore tests (ISSUE 11): epoch-stack parity vs the single-buffer
stores, staged-delete regression, compaction HBM reclamation, shard-quota
migration instead of 507, and the kill-mid-migration invariant."""

import tempfile

import numpy as np
import pytest

from weaviate_tpu.engine.epochs import EpochStore
from weaviate_tpu.engine.flat import FlatIndex
from weaviate_tpu.engine.quantized import QuantizedVectorStore
from weaviate_tpu.engine.store import DeviceVectorStore
from weaviate_tpu.runtime import faultline, tracing
from weaviate_tpu.runtime.hbm_ledger import ledger


def _uuids_for_shard(sharding, name, n, seed=0):
    """Deterministic uuids that all ring-route to ``name``."""
    import uuid as uuid_mod

    rng = np.random.default_rng(seed)
    out = []
    while len(out) < n:
        u = str(uuid_mod.UUID(int=int(rng.integers(0, 2 ** 63))))
        if sharding.shard_for(u) == name:
            out.append(u)
    return out


# -- satellite: delete of a host-staged row ----------------------------------

def test_delete_staged_row_tombstones_without_flush(rng):
    """delete() of a doc whose row is still host-staged must tombstone
    the staged row itself (scrub it from the staging buffer), not only
    the device mask — and must NOT pay a full device flush."""
    store = DeviceVectorStore(dim=8)
    vecs = rng.standard_normal((30, 8)).astype(np.float32)
    slots = store.add(vecs)
    assert store._staged_rows == 30
    store.delete(slots[:10])
    # staged rows scrubbed in place, not flushed
    assert store._staged_rows == 20
    assert store.live_count() == 20
    d, i = store.search(vecs[3], k=1)
    assert i[0] != slots[3]
    d, i = store.search(vecs[15], k=1)
    assert i[0] == slots[15]


def test_interleaved_add_delete_flush_agree(rng):
    """The regression matrix: deletes landing before, between, and
    after flushes — live_count and search results must agree with a
    host-side model throughout."""
    store = DeviceVectorStore(dim=8)
    vecs = rng.standard_normal((60, 8)).astype(np.float32)
    live = set()
    s1 = store.add(vecs[:20])
    live |= set(s1.tolist())
    store.delete(s1[:5])          # staged deletes (pre-flush)
    live -= set(s1[:5].tolist())
    store.flush_staged()
    s2 = store.add(vecs[20:40])   # second staged batch
    live |= set(s2.tolist())
    store.delete([s1[7], s2[3]])  # one device-resident, one staged
    live -= {int(s1[7]), int(s2[3])}
    s3 = store.add(vecs[40:])
    live |= set(s3.tolist())
    store.delete(s3[-2:])         # staged again
    live -= set(s3[-2:].tolist())
    assert store.live_count() == len(live)
    d, i = store.search(vecs, k=1)
    for row, slot in enumerate(i[:, 0].tolist()):
        expect_live = row in live
        if expect_live:
            assert slot == row and d[row, 0] < 1e-3
        else:
            assert slot != row
    # the device cross-check agrees with the host counter
    import os

    os.environ["WEAVIATE_TPU_DEBUG_COUNTS"] = "1"
    try:
        assert store.live_count() == len(live)
    finally:
        os.environ.pop("WEAVIATE_TPU_DEBUG_COUNTS")


# -- epoch-stack parity suite -------------------------------------------------

@pytest.mark.parametrize("mask_kind", [None, "shared", "per_query"])
def test_epoch_parity_flat(rng, mask_kind):
    """Search results bit-identical between a 1-buffer store and the
    same corpus split across >=3 epochs with interleaved tombstones,
    across filter forms."""
    dim = 16
    es = EpochStore(dim=dim, epoch_rows=16, capacity=16, chunk_size=16)
    bs = DeviceVectorStore(dim=dim, capacity=64, chunk_size=64)
    vecs = rng.standard_normal((50, dim)).astype(np.float32)
    # interleave adds and tombstones across epoch boundaries
    for lo in range(0, 50, 10):
        s1 = es.add(vecs[lo:lo + 10])
        s2 = bs.add(vecs[lo:lo + 10])
        assert (s1 == s2).all()
        if lo:
            es.delete([lo - 3])
            bs.delete([lo - 3])
    assert es.epoch_count >= 3
    q = rng.standard_normal((4, dim)).astype(np.float32)
    allow = None
    if mask_kind == "shared":
        allow = np.zeros(64, dtype=bool)
        allow[[1, 2, 14, 18, 30, 33, 45, 48]] = True
    elif mask_kind == "per_query":
        allow = np.zeros((4, 64), dtype=bool)
        allow[0, [1, 2, 20]] = True
        allow[1, :] = True
        allow[2, [33, 34, 48]] = True
        allow[3, [5, 6, 40, 41]] = True
    d1, i1 = es.search(q, k=6, allow_mask=allow)
    d2, i2 = bs.search(q, k=6, allow_mask=allow)
    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_allclose(d1, d2, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mask_kind", [None, "per_query"])
@pytest.mark.parametrize("quant", ["bq", "pq4"])
def test_epoch_parity_quantized(rng, quant, mask_kind):
    """Quantized twins: 3-epoch stack vs single store, same codebook,
    same rescore — candidates merge on device, ONE host rescore."""
    dim = 32
    vecs = rng.standard_normal((60, dim)).astype(np.float32)
    if quant == "bq":
        bs = QuantizedVectorStore(dim=dim, quantization="bq",
                                  capacity=64, chunk_size=64)
        es = EpochStore(dim=dim, epoch_rows=16, capacity=16,
                        chunk_size=16, quantization="bq")
    else:
        bs = QuantizedVectorStore(dim=dim, quantization="pq",
                                  pq_centroids=16, capacity=64,
                                  chunk_size=64)
        bs.add(vecs)
        bs.train(vecs)
        es = EpochStore(dim=dim, epoch_rows=16, capacity=16,
                        chunk_size=16, quantization="pq",
                        quant_kwargs=dict(pq_centroids=16,
                                          codebook=bs.codebook))
    if quant == "bq":
        bs.add(vecs)
    es.add(vecs)
    for s in (es, bs):
        s.delete([4, 17, 33, 50])
    assert es.epoch_count >= 3
    q = rng.standard_normal((3, dim)).astype(np.float32)
    allow = None
    if mask_kind == "per_query":
        allow = np.zeros((3, 64), dtype=bool)
        allow[0, [1, 2, 18, 19, 40]] = True
        allow[1, :] = True
        allow[2, [33, 34, 48, 55]] = True
    d1, i1 = es.search(q, k=5, allow_mask=allow)
    d2, i2 = bs.search(q, k=5, allow_mask=allow)
    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_allclose(d1, d2, rtol=1e-5, atol=1e-5)


def test_epoch_parity_survives_compaction(rng):
    """Compacting a tombstone-heavy epoch repacks its rows but global
    slot ids — and therefore search results — must not change."""
    dim = 16
    es = EpochStore(dim=dim, epoch_rows=16, capacity=16, chunk_size=16)
    bs = DeviceVectorStore(dim=dim, capacity=64, chunk_size=64)
    vecs = rng.standard_normal((48, dim)).astype(np.float32)
    es.add(vecs)
    bs.add(vecs)
    dead = [1, 3, 5, 7, 9, 20, 22, 24]
    es.delete(dead)
    bs.delete(dead)
    assert es.maintain()  # epoch 0 (6/16 dead) and 1 (3/16) fold
    assert es.compactions_total >= 1
    q = rng.standard_normal((3, dim)).astype(np.float32)
    d1, i1 = es.search(q, k=8)
    d2, i2 = bs.search(q, k=8)
    np.testing.assert_array_equal(i1, i2)
    # updates still address the same global slots after compaction
    # (slot 2 lives in the COMPACTED epoch 0 — its local row moved)
    es.set_at([2], vecs[:1])
    bs.set_at([2], vecs[:1])
    d1, i1 = es.search(vecs[0], k=2)
    d2, i2 = bs.search(vecs[0], k=2)
    np.testing.assert_array_equal(i1, i2)


def test_flat_index_epoch_backed(rng):
    """FlatIndex(epoch_rows=...) keeps the full VectorIndex contract:
    doc-id mapping, updates, deletes, filters, async batch."""
    idx = FlatIndex(dim=8, epoch_rows=16, capacity=16, chunk_size=16)
    ids = np.arange(100, 140, dtype=np.int64)
    vecs = rng.standard_normal((40, 8)).astype(np.float32)
    idx.add_batch(ids, vecs)
    assert idx.epoch_store is not None
    assert idx.epoch_store.epoch_count >= 2
    got, d = idx.search_by_vector(vecs[7], k=1)
    assert got[0] == 107
    idx.delete(107)
    got, d = idx.search_by_vector(vecs[7], k=1)
    assert got[0] != 107
    # update an existing id in a sealed epoch
    nv = rng.standard_normal(8).astype(np.float32)
    idx.add_batch([105], nv[None, :])
    got, d = idx.search_by_vector(nv, k=1)
    assert got[0] == 105 and d[0] < 1e-3
    # per-query filtered async batch == sync
    q = rng.standard_normal((4, 8)).astype(np.float32)
    allow = [np.array([101, 102]), None, np.array([120, 121]), None]
    sync_ids, sync_d = idx.search_by_vector_batch(q, 3, allow)
    h = idx.search_by_vector_batch_async(q, 3, allow)
    assert h is not None
    assert h.attrs.get("epochs", 0) >= 2
    async_ids, async_d = h.result()
    np.testing.assert_array_equal(sync_ids, async_ids)
    np.testing.assert_allclose(sync_d, async_d, rtol=1e-5)
    # compact keeps doc-id mapping
    idx.compact()
    got, d = idx.search_by_vector(nv, k=1)
    assert got[0] == 105
    # snapshot/restore round trip through the epoch form
    snap = idx.snapshot()
    r = FlatIndex.restore(snap)
    got, d = r.search_by_vector(nv, k=1)
    assert got[0] == 105


# -- satellite: compact() attribution ----------------------------------------

def test_compact_rides_sanctioned_d2h_span(rng):
    """store.compact runs under a ``store.compact`` span whose rebuild
    D2H goes through transfer.d2h (a nested ``transfer.d2h`` span) —
    graftlint G1 stays empty for engine/ because the boundary is the
    audited one."""
    tracing.clear_traces()
    store = DeviceVectorStore(dim=8, capacity=32, chunk_size=32)
    store.add(rng.standard_normal((20, 8)).astype(np.float32))
    store.delete([1, 2, 3])
    with tracing.trace("maintenance", force=True):
        store.compact()
    (t,) = tracing.recent_traces(1)
    names = [s["name"] for s in t["spans"]]
    assert "store.compact" in names
    assert "transfer.d2h" in names
    tracing.clear_traces()


# -- compaction reclaims HBM (acceptance) ------------------------------------

def test_epoch_compaction_reclaims_ledger_bytes(rng):
    from weaviate_tpu.runtime import hbm_ledger

    with hbm_ledger.owner("EpochLedger", "s0"):
        es = EpochStore(dim=32, epoch_rows=64, capacity=64, chunk_size=64)
    vecs = rng.standard_normal((256, 32)).astype(np.float32)
    es.add(vecs)
    es.seal_active()
    before = ledger.shard_bytes("EpochLedger", "s0")
    comps_before = ledger.shard_component_bytes("EpochLedger", "s0")
    assert any("@e" in c for c in comps_before)
    # tombstone most of every sealed epoch, then run the policy
    es.delete(np.arange(0, 256, dtype=np.int64)[
        np.arange(256) % 4 != 0])
    assert es.maintain()
    after = ledger.shard_bytes("EpochLedger", "s0")
    assert after < before, (before, after)
    # the survivors still serve, on their original global slots
    keep = np.arange(0, 256, 4)
    d, i = es.search(vecs[keep[3]], k=1)
    assert i[0] == keep[3]
    # per-epoch gauges exist and tombstones went back to zero
    stats = es.epoch_stats()
    assert all(s["tombstones"] == 0 for s in stats if s["sealed"])


def test_epoch_gauges_exposed(rng):
    from weaviate_tpu.runtime.metrics import registry

    es = EpochStore(dim=8, epoch_rows=8, capacity=8, chunk_size=8)
    es.add(rng.standard_normal((20, 8)).astype(np.float32))
    es.maintain()
    text = registry.expose()
    assert "weaviate_tpu_epoch_count" in text
    assert "weaviate_tpu_epoch_live_rows" in text
    assert "weaviate_tpu_epoch_tombstone_rows" in text


# -- mixed read/write + migration (acceptance) -------------------------------

def _epoch_collection(tmpdir, shards=2, epoch_rows=32, dim=16):
    from weaviate_tpu.db.database import Database
    from weaviate_tpu.schema.config import (CollectionConfig,
                                            ShardingConfig, VectorConfig,
                                            VectorIndexConfig)

    db = Database(data_dir=tmpdir)
    cfg = CollectionConfig(
        name="EpochCol",
        vectors=[VectorConfig(name="", dim=dim,
                              index=VectorIndexConfig(
                                  index_type="flat",
                                  epoch_rows=epoch_rows))],
        sharding=ShardingConfig(desired_count=shards))
    db.create_collection(cfg)
    return db, db.get_collection("EpochCol")


def test_mixed_read_write_reclaims_and_stays_correct(rng):
    """Sustained interleaved put/delete/query: searches stay correct
    throughout, and the background policy's compaction makes ledger
    totals FALL after deletes — HBM is finally reclaimed."""
    with tempfile.TemporaryDirectory() as d:
        db, col = _epoch_collection(d, shards=1, epoch_rows=32)
        try:
            alive = {}
            n = 0
            for round_ in range(6):
                for _ in range(40):
                    v = rng.standard_normal(16).astype(np.float32)
                    u = col.put_object({"n": n}, vector=v)
                    alive[u] = v
                    n += 1
                doomed = list(alive)[::3][:20]
                for u in doomed:
                    col.delete_object(u)
                    del alive[u]
                probe = list(alive)[-1]
                res = col.near_vector(alive[probe], k=3)
                assert res and res[0].uuid == probe
                assert len({r.uuid for r in res}) == len(res)
            peak = ledger.collection_bytes("EpochCol")
            # delete-heavy tail, then the policy cycle reclaims
            for u in list(alive)[::2]:
                col.delete_object(u)
                del alive[u]
            # the registered cycle body, driven synchronously
            assert db.cycles.run_now("epoch-maintenance")
            reclaimed = ledger.collection_bytes("EpochCol")
            assert reclaimed < peak, (peak, reclaimed)
            probe = list(alive)[0]
            res = col.near_vector(alive[probe], k=3)
            assert res and res[0].uuid == probe
        finally:
            db.close()


def test_shard_quota_migration_averts_507(rng):
    """A shard at its HBM quota watermark migrates its coldest sealed
    epoch to the sibling with headroom and the write SUCCEEDS; with no
    headroom anywhere, the typed 507 surfaces."""
    from weaviate_tpu.runtime.memwatch import InsufficientMemoryError
    from weaviate_tpu.runtime.metrics import epoch_migrations

    with tempfile.TemporaryDirectory() as d:
        db, col = _epoch_collection(d, shards=2, epoch_rows=32)
        try:
            fat = "shard-0"
            uuids = _uuids_for_shard(col.sharding, fat, 100)
            for j, u in enumerate(uuids):
                col.put_object({"j": j}, uuid=u,
                               vector=rng.standard_normal(16)
                               .astype(np.float32))
            shard = col.shards[fat]
            for idx in shard.vector_indexes.values():
                idx.epoch_store.seal_active()
            used = ledger.shard_bytes("EpochCol", fat)
            # quota such that the shard is already over the watermark
            shard.shard_hbm_limit = used
            assert shard.over_shard_limit()
            before = epoch_migrations.labels("EpochCol", fat).value
            u_new = _uuids_for_shard(col.sharding, fat, 1, seed=7)[0]
            col.put_object({"fresh": True}, uuid=u_new,
                           vector=rng.standard_normal(16)
                           .astype(np.float32))  # must NOT raise
            assert epoch_migrations.labels("EpochCol", fat).value > before
            assert ledger.shard_bytes("EpochCol", fat) < used
            # every object still served exactly once
            for u in uuids[:10] + [u_new]:
                assert col.get_object(u) is not None
            res = col.near_vector(np.zeros(16, np.float32), k=101)
            assert len(res) == len({r.uuid for r in res})
            # no headroom anywhere -> typed 507
            other = col.shards["shard-1"]
            other.shard_hbm_limit = 1  # hopeless quota
            shard.shard_hbm_limit = max(
                ledger.shard_bytes("EpochCol", fat) // 2, 1)
            with pytest.raises(InsufficientMemoryError):
                col.put_object(
                    {"overflow": True},
                    uuid=_uuids_for_shard(col.sharding, fat, 1, seed=9)[0],
                    vector=rng.standard_normal(16).astype(np.float32))
        finally:
            db.close()


@pytest.mark.parametrize("crash_at", ["epoch.migrate.pre_ingest",
                                      "epoch.migrate.post_ingest",
                                      "epoch.migrate.post_cutover"])
def test_migration_kill_no_loss_no_double_serve(rng, crash_at):
    """Crashpoint-style kill during epoch migration: whichever side of
    the cutover the failure lands on, every doc is served EXACTLY once
    — before and after a restart — and re-running the migration
    completes cleanly."""
    from weaviate_tpu.db.database import Database

    with tempfile.TemporaryDirectory() as d:
        db, col = _epoch_collection(d, shards=2, epoch_rows=16)
        uuids = _uuids_for_shard(col.sharding, "shard-0", 40)
        vecs = {}
        for j, u in enumerate(uuids):
            v = rng.standard_normal(16).astype(np.float32)
            col.put_object({"j": j}, uuid=u, vector=v)
            vecs[u] = v

        def assert_exactly_once(c):
            for u in uuids:
                assert c.get_object(u) is not None, f"lost {u}"
            res = c.near_vector(np.zeros(16, np.float32), k=200)
            served = [r.uuid for r in res if r.uuid in vecs]
            assert len(served) == len(set(served)), "double-served"
            assert len(set(served)) == len(uuids), "search lost docs"

        col.shards["shard-0"].vector_indexes[""].epoch_store.seal_active()
        with faultline.injected(crash_at, "error"):
            with pytest.raises(faultline.FaultInjected):
                col.migrate_epoch("shard-0", dst_name="shard-1")
        assert_exactly_once(col)
        db.close()
        # restart over the same dir: durable state must hold the invariant
        db2 = Database(data_dir=d)
        col2 = db2.get_collection("EpochCol")
        try:
            assert_exactly_once(col2)
            # the policy re-runs and completes the interrupted move
            col2.shards["shard-0"].vector_indexes[""] \
                .epoch_store.seal_active()
            col2.migrate_epoch("shard-0", dst_name="shard-1")
            assert_exactly_once(col2)
            # a delete must reach EVERY copy the crash left behind
            # (the pre-ingest durable markers close the resurrect
            # window a post-ingest kill used to open)
            gone = uuids[5]
            assert col2.delete_object(gone)
            assert col2.get_object(gone) is None
            res = col2.near_vector(np.zeros(16, np.float32), k=200)
            assert gone not in {r.uuid for r in res}
        finally:
            db2.close()


def test_epoch_parity_mesh(rng):
    """Mesh-sharded epochs: per-epoch SPMD scans (epoch-sliced,
    column-sharded allow masks) + replicated slot-map merge — same
    results as the single row-sharded buffer."""
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    from weaviate_tpu.parallel import make_mesh

    mesh = make_mesh(8)
    vecs = rng.standard_normal((120, 32)).astype(np.float32)
    es = EpochStore(dim=32, epoch_rows=48, capacity=32, chunk_size=4,
                    mesh=mesh)
    bs = DeviceVectorStore(dim=32, capacity=128, chunk_size=16, mesh=mesh)
    s1, s2 = es.add(vecs), bs.add(vecs)
    assert (s1 == s2).all()
    es.delete([3, 50, 100])
    bs.delete([3, 50, 100])
    q = rng.standard_normal((3, 32)).astype(np.float32)
    d1, i1 = es.search(q, k=6)
    d2, i2 = bs.search(q, k=6)
    np.testing.assert_array_equal(i1, i2)
    pm = np.zeros((3, 160), dtype=bool)
    pm[0, [1, 2, 60]] = True
    pm[1, :] = True
    pm[2, [100, 101]] = True
    d1, i1 = es.search(q, k=3, allow_mask=pm)
    d2, i2 = bs.search(q, k=3, allow_mask=pm[:, :128])
    np.testing.assert_array_equal(i1, i2)


def test_migration_blocks_concurrent_write_no_loss(rng):
    """A delete/put of a migrating uuid queues behind the move (the
    source lock spans ingest + cutover) instead of landing in the
    un-synchronized window where the cutover would erase it or the
    target's stale copy resurrect it."""
    import threading

    with tempfile.TemporaryDirectory() as d:
        db, col = _epoch_collection(d, shards=2, epoch_rows=16)
        try:
            uuids = _uuids_for_shard(col.sharding, "shard-0", 20)
            for j, u in enumerate(uuids):
                col.put_object({"j": j}, uuid=u,
                               vector=rng.standard_normal(16)
                               .astype(np.float32))
            col.shards["shard-0"].vector_indexes[""] \
                .epoch_store.seal_active()
            victim = uuids[0]
            with faultline.injected("epoch.migrate.post_ingest",
                                    "latency", latency_s=0.4):
                t = threading.Thread(
                    target=col.migrate_epoch,
                    args=("shard-0",), kwargs={"dst_name": "shard-1"})
                t.start()
                import time as _t

                _t.sleep(0.1)  # migration is inside the window now
                assert col.delete_object(victim)  # queues behind cutover
                t.join(10)
            assert col.get_object(victim) is None
            res = col.near_vector(np.zeros(16, np.float32), k=50)
            assert victim not in {r.uuid for r in res}
            # every other object still served exactly once
            others = uuids[1:]
            assert all(col.get_object(u) is not None for u in others)
            assert len({r.uuid for r in res} & set(others)) == len(others)
        finally:
            db.close()


def test_epoch_snapshot_restore_after_early_seal(rng):
    """An early seal (the pre-migration step) leaves the active epoch's
    range mostly unused, so the slot->id table is wider than a
    re-split restore's capacity — restore must keep every entry."""
    idx = FlatIndex(dim=8, epoch_rows=64, capacity=64, chunk_size=64)
    ids = np.arange(10, dtype=np.int64)
    vecs = rng.standard_normal((10, 8)).astype(np.float32)
    idx.add_batch(ids, vecs)
    idx.epoch_store.seal_active()
    idx.add_batch(np.arange(10, 15, dtype=np.int64),
                  rng.standard_normal((5, 8)).astype(np.float32))
    snap = idx.snapshot()
    r = FlatIndex.restore(snap)
    assert len(r) == 15
    got, d = r.search_by_vector(vecs[4], k=1)
    assert got[0] == 4 and d[0] < 1e-3


def test_epoch_compress_keeps_results(rng):
    """Runtime compression of an epoch-backed index keeps slot layout
    and serves the same neighbors (rescored exactly)."""
    idx = FlatIndex(dim=16, epoch_rows=16, capacity=16, chunk_size=16)
    ids = np.arange(50, dtype=np.int64)
    vecs = rng.standard_normal((50, 16)).astype(np.float32)
    idx.add_batch(ids, vecs)
    idx.delete(7, 30)
    idx.compress(quantization="bq")
    assert idx.compressed
    assert idx.epoch_store is not None and idx.epoch_store.quantization == "bq"
    got, d = idx.search_by_vector(vecs[12], k=1)
    assert got[0] == 12 and d[0] < 1e-3
    got, _ = idx.search_by_vector(vecs[7], k=50)
    assert 7 not in got.tolist()


# -- ISSUE 13: cross-node epoch migration -------------------------------------


class _FakeRemote:
    """Remote shard client double: captures cross-node ingests and
    serves GET/DELETE from the captured store."""

    def __init__(self, fail=False):
        self.fail = fail
        self.calls = []
        self.objects = {}  # (node, shard) -> {uuid: raw}

    def put_objects(self, node, collection, shard, raw_objects):
        from weaviate_tpu.cluster.transport import RpcError
        from weaviate_tpu.storage.objects import StorageObject

        if self.fail:
            raise RpcError(507, "target at watermark")
        self.calls.append(("put", node, collection, shard,
                           len(raw_objects)))
        bucket = self.objects.setdefault((node, shard), {})
        for raw in raw_objects:
            bucket[StorageObject.from_bytes(raw).uuid] = raw

    def get_object(self, node, collection, shard, uuid):
        return self.objects.get((node, shard), {}).get(uuid)

    def delete_object(self, node, collection, shard, uuid):
        return self.objects.get((node, shard), {}).pop(uuid, None) \
            is not None


def _cross_node_collection(tmpdir, remote, local_hbm=None):
    from weaviate_tpu.db.collection import Collection
    from weaviate_tpu.db.sharding import ShardingState
    from weaviate_tpu.schema.config import (CollectionConfig,
                                            ShardingConfig, VectorConfig,
                                            VectorIndexConfig)

    state = ShardingState(
        shard_names=["shard-0", "shard-1"],
        placement={"shard-0": ["node-a"], "shard-1": ["node-b"]})
    cfg = CollectionConfig(
        name="XNode",
        vectors=[VectorConfig(name="", dim=16,
                              index=VectorIndexConfig(
                                  index_type="flat", epoch_rows=16))],
        sharding=ShardingConfig(desired_count=2))
    col = Collection(
        tmpdir, cfg, sharding_state=state, local_node="node-a",
        remote=remote,
        nodes_provider=lambda: ["node-a", "node-b"],
        node_hbm_provider=lambda: {"node-b": 0})
    return col


def test_cross_node_epoch_migration_durable_cutover(rng):
    """No LOCAL sibling has headroom (the only sibling lives on
    node-b): migrate_epoch ships the coldest sealed epoch over the
    shard RPC behind the same durable-marker cutover — reads follow the
    marker to the remote copy, deletes clean both sides, and the
    epoch's HBM releases locally."""
    with tempfile.TemporaryDirectory() as d:
        remote = _FakeRemote()
        col = _cross_node_collection(d, remote)
        try:
            uuids = _uuids_for_shard(col.sharding, "shard-0", 24)
            for j, u in enumerate(uuids):
                col.put_object({"j": j}, uuid=u,
                               vector=rng.standard_normal(16)
                               .astype(np.float32))
            shard = col.shards["shard-0"]
            for idx in shard.vector_indexes.values():
                idx.epoch_store.seal_active()
            before = ledger.shard_bytes("XNode", "shard-0")
            moved = col.migrate_epoch("shard-0")
            assert moved > 0
            assert remote.calls and remote.calls[0][:4] == (
                "put", "node-b", "XNode", "shard-1")
            assert ledger.shard_bytes("XNode", "shard-0") < before
            # marker-routed read reaches the remote copy
            migrated = [u for u in uuids
                        if shard.migrated_to(u) == "shard-1"]
            assert len(migrated) == moved
            for u in migrated[:5]:
                obj = col.get_object(u)
                assert obj is not None and obj.uuid == u
            # delete cleans BOTH sides and drops the marker
            victim = migrated[0]
            assert col.delete_object(victim)
            assert shard.migrated_to(victim) is None
            assert remote.get_object("node-b", "XNode", "shard-1",
                                     victim) is None
        finally:
            col.close()


def test_cross_node_migration_rpc_failure_aborts_markers_kept(rng):
    """An ingest RPC failure (target watermark / lost reply / network
    fault) is AMBIGUOUS — the put may have landed durably before the
    reply was lost — so the abort keeps the routing markers (a marker
    to an absent copy is harmless; a dropped marker to a present copy
    is an undeletable zombie), cuts nothing over, and the source still
    serves every object. A later retry re-marks and completes."""
    with tempfile.TemporaryDirectory() as d:
        remote = _FakeRemote(fail=True)
        col = _cross_node_collection(d, remote)
        try:
            uuids = _uuids_for_shard(col.sharding, "shard-0", 12)
            for j, u in enumerate(uuids):
                col.put_object({"j": j}, uuid=u,
                               vector=rng.standard_normal(16)
                               .astype(np.float32))
            shard = col.shards["shard-0"]
            for idx in shard.vector_indexes.values():
                idx.epoch_store.seal_active()
            assert col.migrate_epoch("shard-0") == 0
            marked = [u for u in uuids
                      if shard.migrated_to(u) == "shard-1"]
            assert marked  # kept, not rolled back
            for u in uuids:  # ring copy still authoritative
                assert col.get_object(u) is not None
            # the network heals: the retry re-marks and completes
            remote.fail = False
            moved = col.migrate_epoch("shard-0")
            assert moved > 0
            for u in uuids:
                assert col.get_object(u) is not None
        finally:
            col.close()
