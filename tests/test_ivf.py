"""IVF ANN index + dynamic flat→IVF upgrade.

Mirrors the reference's recall-gated ANN tests (hnsw/recall_test.go asserts
recall vs brute force) and dynamic upgrade tests (dynamic/index.go:348).
"""

import numpy as np
import pytest

from weaviate_tpu.engine.dynamic import DynamicIndex
from weaviate_tpu.engine.flat import FlatIndex
from weaviate_tpu.engine import ivf as ivf_mod
from weaviate_tpu.engine.ivf import IVFIndex


def _clustered(rng, n, dim, n_clusters=32):
    """Clustered corpus — IVF recall on uniform noise is meaningless."""
    centers = rng.standard_normal((n_clusters, dim)) * 5.0
    assign = rng.integers(0, n_clusters, n)
    return (centers[assign] + rng.standard_normal((n, dim))).astype(np.float32)


def _recall(ann_ids, exact_ids):
    hits = sum(len(set(a.tolist()) & set(e.tolist())) for a, e in
               zip(ann_ids, exact_ids))
    return hits / exact_ids.size


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(3)
    x = _clustered(rng, 6000, 32)
    q = _clustered(rng, 16, 32)
    return x, q


def test_ivf_trains_at_threshold(corpus):
    x, _ = corpus
    idx = IVFIndex(dim=32, train_threshold=2000, delta_threshold=512)
    idx.add_batch(np.arange(1000), x[:1000])
    assert not idx.trained
    idx.add_batch(np.arange(1000, 4000), x[1000:4000])
    assert idx.trained
    assert len(idx) == 4000


@pytest.mark.parametrize("metric,floor", [("l2-squared", 0.9),
                                          ("cosine", 0.85)])
def test_ivf_recall_vs_exact(corpus, metric, floor):
    x, q = corpus
    n = len(x) if metric == "l2-squared" else 3000
    flat = FlatIndex(dim=32, metric=metric)
    flat.add_batch(np.arange(n), x[:n])
    ivf = IVFIndex(dim=32, metric=metric, train_threshold=n // 3,
                   delta_threshold=n // 12, nprobe=8)
    ivf.add_batch(np.arange(n), x[:n])
    assert ivf.trained

    exact_ids, _ = flat.search_by_vector_batch(q, 10)
    ann_ids, ann_d = ivf.search_by_vector_batch(q, 10)
    r = _recall(ann_ids, exact_ids)
    assert r >= floor, f"recall {r} too low"
    # distances ascending
    for row in ann_d:
        assert (np.diff(row[row < 1e37]) >= -1e-4).all()


@pytest.mark.parametrize("nlist", [16, 64])
def test_ivf_full_probe_is_exact(corpus, nlist):
    """nprobe == nlist degenerates to exact brute force."""
    x, q = corpus
    n = 4000
    ivf = IVFIndex(dim=32, train_threshold=2000, nlist=nlist, nprobe=nlist,
                   delta_threshold=512)
    ivf.add_batch(np.arange(n), x[:n])
    flat = FlatIndex(dim=32)
    flat.add_batch(np.arange(n), x[:n])
    exact_ids, _ = flat.search_by_vector_batch(q, 5)
    ann_ids, _ = ivf.search_by_vector_batch(q, 5)
    assert _recall(ann_ids, exact_ids) == 1.0


def test_ivf_delta_is_searchable_before_flush(corpus):
    x, _ = corpus
    ivf = IVFIndex(dim=32, train_threshold=2000, delta_threshold=100_000)
    ivf.add_batch(np.arange(3000), x[:3000])
    assert ivf.trained
    # these stay in the delta buffer (threshold huge)
    probe = x[3000] + 0.001
    ivf.add(99_999, x[3000])
    ids, d = ivf.search_by_vector(probe, 1)
    assert ids[0] == 99_999


def test_ivf_delete_and_update(corpus):
    x, _ = corpus
    n = 3000
    ivf = IVFIndex(dim=32, train_threshold=1000, delta_threshold=256)
    ivf.add_batch(np.arange(n), x[:n])
    ivf.store.flush_delta()
    # delete a list-resident vector: must vanish from results
    q = x[5]
    ids, _ = ivf.search_by_vector(q, 1)
    assert ids[0] == 5
    ivf.delete(5)
    ids, _ = ivf.search_by_vector(q, 3)
    assert 5 not in ids.tolist()
    assert len(ivf) == n - 1
    # update: overwrite doc 7 with a far-away vector
    far = (x[7] + 100.0).astype(np.float32)
    ivf.add(7, far)
    ids, _ = ivf.search_by_vector(far + 0.001, 1)
    assert ids[0] == 7


def test_ivf_allow_list(corpus):
    x, q = corpus
    n = 3000
    ivf = IVFIndex(dim=32, train_threshold=1000, delta_threshold=256,
                   nprobe=16)
    ivf.add_batch(np.arange(n), x[:n])
    allowed = np.arange(0, n, 7)
    ids, d = ivf.search_by_vector(q[0], 10, allow_list=allowed)
    assert len(ids) > 0
    assert all(i % 7 == 0 for i in ids.tolist())


def test_ivf_snapshot_restore(corpus):
    x, q = corpus
    n = 3000
    ivf = IVFIndex(dim=32, train_threshold=1000, delta_threshold=256)
    ivf.add_batch(np.arange(n), x[:n])
    ivf.delete(17)
    snap = ivf.snapshot()
    restored = IVFIndex.restore(snap)
    assert restored.trained
    assert len(restored) == n - 1
    a, _ = ivf.search_by_vector_batch(q, 10)
    b, _ = restored.search_by_vector_batch(q, 10)
    assert _recall(b, a) >= 0.9


def test_dynamic_upgrade(corpus):
    x, q = corpus
    dyn = DynamicIndex(dim=32, threshold=2000, nprobe=16)
    dyn.add_batch(np.arange(1500), x[:1500])
    assert not dyn.upgraded
    ids, _ = dyn.search_by_vector(x[3], 1)
    assert ids[0] == 3
    dyn.add_batch(np.arange(1500, 4000), x[1500:4000])
    assert dyn.upgraded
    assert len(dyn) == 4000
    # still finds its nearest neighbors after migration
    ids, _ = dyn.search_by_vector(x[3] + 0.0001, 1)
    assert ids[0] == 3


def test_dynamic_stays_flat_below_threshold(corpus):
    x, _ = corpus
    dyn = DynamicIndex(dim=32, threshold=10_000)
    dyn.add_batch(np.arange(500), x[:500])
    assert not dyn.upgraded
    assert dyn.index_type == "dynamic"


def test_dynamic_in_collection(tmp_path, corpus):
    from weaviate_tpu.db.database import Database
    from weaviate_tpu.schema.config import CollectionConfig, VectorConfig, VectorIndexConfig

    x, _ = corpus
    db = Database(str(tmp_path))
    cfg = CollectionConfig(
        name="Ann",
        vectors=[VectorConfig(index=VectorIndexConfig(
            index_type="dynamic", flat_to_ann_threshold=2000))],
    )
    col = db.create_collection(cfg)
    col.batch_put([{"properties": {"i": i}, "vector": x[i]}
                   for i in range(2500)])
    res = col.near_vector(x[42] + 0.0001, k=1)
    assert res[0].object.properties["i"] == 42
    shard = next(iter(col.shards.values()))
    assert shard.vector_indexes[""].upgraded
    db.close()


# -- IVF-PQ residency (VERDICT r2 item 4b) -----------------------------------

def _gt10(vecs, q, k=10):
    sq = np.einsum("nd,nd->n", vecs, vecs)
    d = sq[None, :] - 2.0 * (q @ vecs.T)
    part = np.argpartition(d, k, 1)[:, :k]
    pd = np.take_along_axis(d, part, 1)
    return np.take_along_axis(part, np.argsort(pd, 1), 1)


def test_ivf_pq_recall_parity(rng):
    """IVF-PQ (codes in lists + exact rescore) tracks uncompressed IVF
    recall on clustered data."""
    n, d = 6000, 32
    centers = rng.standard_normal((64, d)).astype(np.float32)
    vecs = (centers[rng.integers(0, 64, n)]
            + 0.3 * rng.standard_normal((n, d))).astype(np.float32)
    q = (vecs[rng.integers(0, n, 50)]
         + 0.05 * rng.standard_normal((50, d))).astype(np.float32)
    gt = _gt10(vecs, q)

    plain = IVFIndex(dim=d, train_threshold=4000, delta_threshold=1000)
    pq = IVFIndex(dim=d, train_threshold=4000, delta_threshold=1000,
                  quantization="pq")
    plain.add_batch(np.arange(n), vecs)
    pq.add_batch(np.arange(n), vecs)
    assert plain.trained and pq.trained and pq.compressed

    def recall(idx):
        hits = 0
        for r in range(50):
            ids, _ = idx.search_by_vector(q[r], k=10)
            hits += len(set(ids.tolist()) & set(gt[r].tolist()))
        return hits / 500

    r_plain, r_pq = recall(plain), recall(pq)
    assert r_pq >= r_plain - 0.05, (r_pq, r_plain)
    assert r_pq >= 0.85, r_pq


def test_ivf_pq_lifecycle(rng):
    n, d = 5000, 16
    vecs = rng.standard_normal((n, d)).astype(np.float32)
    idx = IVFIndex(dim=d, train_threshold=3000, delta_threshold=500,
                   quantization="pq")
    idx.add_batch(np.arange(n), vecs)
    ids, dists = idx.search_by_vector(vecs[123], k=3)
    assert ids[0] == 123 and dists[0] < 1e-3  # exact after rescore
    idx.delete(123)
    ids, _ = idx.search_by_vector(vecs[123], k=3)
    assert 123 not in ids.tolist()
    # update re-routes through the exact delta
    idx.add_batch([55], vecs[200][None] + 0.001)
    ids, _ = idx.search_by_vector(vecs[200], k=2)
    assert 55 in ids.tolist()


def test_ivf_pq_snapshot_restore(rng):
    n, d = 4000, 16
    vecs = rng.standard_normal((n, d)).astype(np.float32)
    idx = IVFIndex(dim=d, train_threshold=2000, delta_threshold=500,
                   quantization="pq")
    idx.add_batch(np.arange(n), vecs)
    snap = idx.snapshot()
    back = IVFIndex.restore(snap)
    assert back.compressed
    ids, dists = back.search_by_vector(vecs[77], k=3)
    assert ids[0] == 77 and dists[0] < 1e-3


def test_ivf_runtime_compress(rng):
    """compress() flips a live uncompressed IVF to PQ residency."""
    n, d = 5000, 16
    vecs = rng.standard_normal((n, d)).astype(np.float32)
    idx = IVFIndex(dim=d, train_threshold=3000, delta_threshold=500)
    idx.add_batch(np.arange(n), vecs)
    assert idx.trained and not idx.compressed
    ids_before, _ = idx.search_by_vector(vecs[42], k=10)
    idx.compress("pq")
    assert idx.compressed
    ids_after, dists = idx.search_by_vector(vecs[42], k=10)
    assert ids_after[0] == 42 and dists[0] < 1e-3
    assert len(set(ids_before.tolist()) & set(ids_after.tolist())) >= 7


def test_ivf_pq_masked_candidates_stay_dead(rng):
    """Deleted / allow-filtered docs must never surface through the PQ
    rescore (masked probe rows keep their slot ids in the top-k buffer)."""
    n, d = 5000, 16
    vecs = rng.standard_normal((n, d)).astype(np.float32)
    idx = IVFIndex(dim=d, train_threshold=3000, delta_threshold=500,
                   quantization="pq")
    idx.add_batch(np.arange(n), vecs)
    idx.delete(10)
    ids, _ = idx.search_by_vector(vecs[10], k=10)
    assert 10 not in ids.tolist()
    # tiny allow list (fewer rows than the oversampled candidate count)
    allow = np.asarray([3, 4, 5], dtype=np.int64)
    ids, _ = idx.search_by_vector(vecs[3], k=10, allow_list=allow)
    assert set(ids.tolist()) <= {3, 4, 5}, ids


# -- ISSUE 16: first-class serving path --------------------------------------

def test_ivf_recall_gate_few_lists(rng):
    """recall@10 >= 0.95 vs exact flat while probing <= 5% of lists
    (nprobe=3 of nlist=64 -> 4.7%): the multi-probe + residual layout
    earns its keep only if a tiny probe fraction preserves recall."""
    n, d, k = 8000, 32, 10
    centers = rng.standard_normal((64, d)).astype(np.float32) * 4.0
    vecs = (centers[rng.integers(0, 64, n)]
            + 0.4 * rng.standard_normal((n, d))).astype(np.float32)
    q = (vecs[rng.integers(0, n, 64)]
         + 0.05 * rng.standard_normal((64, d))).astype(np.float32)
    gt = _gt10(vecs, q, k)
    ivf = IVFIndex(dim=d, train_threshold=4000, delta_threshold=1000,
                   nlist=64, nprobe=3)
    ivf.add_batch(np.arange(n), vecs)
    ivf.store.flush_delta()
    h = ivf.store.search_async(q, k)
    assert h.attrs["lists_frac"] <= 0.05, h.attrs
    h.result()
    ids, _ = ivf.search_by_vector_batch(q, k)
    r = _recall(ids, gt)
    assert r >= 0.95, r


@pytest.mark.parametrize("metric", ["l2-squared", "dot", "cosine"])
def test_ivf_filter_parity_across_metrics(rng, metric):
    """Full-probe IVF == exact flat for every metric x {no filter,
    shared allow list, per-query allow lists}, and the parity survives
    compaction WITHOUT a posting-list rebuild."""
    n, d, k = 2500, 24, 8
    vecs = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((6, d)).astype(np.float32)
    ivf = IVFIndex(dim=d, metric=metric, train_threshold=1000,
                   delta_threshold=256, nlist=16, nprobe=16)
    flat = FlatIndex(dim=d, metric=metric)
    ivf.add_batch(np.arange(n), vecs)
    flat.add_batch(np.arange(n), vecs)
    ivf.store.flush_delta()
    assert ivf.supports_batched_filters

    shared = np.arange(0, n, 3)
    per_q = [None if r % 2 else
             np.flatnonzero(rng.random(n) < 0.2).astype(np.int64)
             for r in range(len(q))]

    def check():
        for allow in (None, shared, per_q):
            ei, _ = flat.search_by_vector_batch(q, k, allow)
            ai, _ = ivf.search_by_vector_batch(q, k, allow)
            for r in range(len(q)):
                assert set(ai[r][ai[r] >= 0].tolist()) == \
                    set(ei[r][ei[r] >= 0].tolist()), (metric, allow, r)

    check()
    # tombstone churn + compaction: holes, not rebuilds — parity holds
    for doc in range(0, n, 5):
        ivf.delete(doc)
        flat.delete(doc)
    rebuilds = ivf.store.rebuild_count
    ivf.compact()
    flat.compact()
    assert ivf.store.rebuild_count == rebuilds
    check()


def test_ivf_async_bitexact_vs_sync(rng):
    """search == search_async(...).result() bit-for-bit, plain and
    residual-PQ, with BOTH legs live (list-resident rows + delta)."""
    n, d, k = 4000, 32, 10
    vecs = rng.standard_normal((n + 100, d)).astype(np.float32)
    q = rng.standard_normal((8, d)).astype(np.float32)
    for quant in (None, "pq"):
        ivf = IVFIndex(dim=d, train_threshold=2000, delta_threshold=512,
                       quantization=quant)
        ivf.add_batch(np.arange(n), vecs[:n])
        ivf.store.flush_delta()
        ivf.add_batch(np.arange(n, n + 100), vecs[n:])  # stays in delta
        sd, si = ivf.store.search(q, k)
        ad, ai = ivf.store.search_async(q, k).result()
        assert np.array_equal(si, ai), quant
        assert np.array_equal(sd, ad), quant
        # index-level async twin exists and resolves to the sync result
        h = ivf.search_by_vector_batch_async(q, k)
        assert h is not None
        ids_a, d_a = h.result()
        ids_s, d_s = ivf.search_by_vector_batch(q, k)
        assert np.array_equal(np.asarray(ids_a), np.asarray(ids_s)), quant
        assert np.array_equal(np.asarray(d_a), np.asarray(d_s)), quant


def test_ivf_compact_no_rebuild_and_hole_reuse(rng):
    """compact() never rebuilds the posting lists (rebuild_count flat);
    deletes punch holes that later inserts refill."""
    n, d = 3000, 16
    vecs = rng.standard_normal((n + 300, d)).astype(np.float32)
    idx = IVFIndex(dim=d, train_threshold=1000, delta_threshold=256)
    idx.add_batch(np.arange(n), vecs[:n])
    idx.store.flush_delta()
    built = idx.store.rebuild_count
    for doc in range(600):
        idx.delete(doc)
    idx.compact()
    assert idx.store.rebuild_count == built
    assert len(idx) == n - 600
    ids, _ = idx.search_by_vector(vecs[700], 1)
    assert ids[0] == 700
    ids, _ = idx.search_by_vector(vecs[10], 5)
    assert 10 not in ids.tolist()
    # refill: new rows land in punched holes, still no rebuild
    idx.add_batch(np.arange(n, n + 300), vecs[n:])
    idx.store.flush_delta()
    assert idx.store.rebuild_count == built
    ids, _ = idx.search_by_vector(vecs[n + 7], 1)
    assert ids[0] == n + 7


def test_ivf_maintain_retrains_on_drift(rng):
    """maintain() folds the delta every tick but retrains only once the
    live count crosses retrain_factor x live-at-train."""
    n0, d = 1200, 16
    vecs = rng.standard_normal((5 * n0, d)).astype(np.float32)
    idx = IVFIndex(dim=d, train_threshold=1000, delta_threshold=256)
    idx.add_batch(np.arange(n0), vecs[:n0])
    assert idx.trained
    t0 = idx.store.retrain_count
    idx.maintain()
    assert idx.store.retrain_count == t0  # below the drift gate
    idx.add_batch(np.arange(n0, 5 * n0), vecs[n0:])
    idx.maintain()
    assert idx.store.retrain_count == t0 + 1  # 5x growth -> retrain
    ids, _ = idx.search_by_vector(vecs[3], 1)
    assert ids[0] == 3


@pytest.mark.parametrize("user_nlist,want", [(0, (32, 64, 128)),
                                             (24, (24, 24, 24))])
def test_nlist_follows_the_corpus_over_two_retrains(rng, user_nlist, want):
    """Automatic (0), the number of lists is re-sized at every retrain to
    the corpus it finds (about 2 sqrt(rows)); the first training does not
    pin it. Where the user set it, it stays. Both retrains fall on the
    WRITE path, at the full delta that finds the corpus four times the
    trained one: no maintenance tick is involved."""
    d = 16
    vecs = rng.standard_normal((8960, d)).astype(np.float32)
    idx = IVFIndex(dim=d, nlist=user_nlist, train_threshold=256,
                   delta_threshold=128)
    seen = []
    for start in range(0, 8960, 128):
        idx.add_batch(np.arange(start, start + 128), vecs[start:start + 128])
        state = (idx.store.nlist, idx.store.retrain_count, len(idx))
        if idx.trained and (not seen or seen[-1][:2] != state[:2]):
            seen.append(state)
    # trained at 256 rows, retrained at 1,024 and at 4,096
    assert seen == [(want[0], 0, 256), (want[1], 1, 1024),
                    (want[2], 2, 4096)]
    assert idx.store.retrain_count == 2
    assert idx.store._live_at_train == 4096
    assert idx.store._user_nlist == user_nlist
    back = IVFIndex.restore(idx.snapshot())
    assert back.store._user_nlist == user_nlist
    assert back.store.nlist == want[-1]
    ids, _ = idx.search_by_vector(vecs[8959], 1)
    assert ids[0] == 8959


def test_a_tick_folds_the_delta_only_once_the_writes_have_paused(rng):
    d = 16
    vecs = rng.standard_normal((700, d)).astype(np.float32)
    idx = IVFIndex(dim=d, train_threshold=512, delta_threshold=4096)
    idx.add_batch(np.arange(600), vecs[:600])
    assert idx.trained and not idx.store._delta_slots
    idx.add_batch(np.arange(600, 700), vecs[600:])
    wrote = idx.store._last_write_t
    pause = ivf_mod.TAIL_FOLD_PAUSE_S
    # rows arrived a moment ago: work left, whatever the ticks before saw
    assert idx.maintain(tick=True, now=wrote + 0.1) is True
    assert idx.maintain(tick=True, now=wrote + pause - 0.1) is True
    assert len(idx.store._delta_slots) == 100
    # paused by the clock: folded at the FIRST tick that comes after, also
    # where the scheduler was kept from ticking all the while
    assert idx.maintain(tick=True, now=wrote + pause) is True
    assert not idx.store._delta_slots
    assert idx.maintain(tick=True) is False     # nothing to do: back off
    idx.add_batch([700], vecs[:1])
    assert idx.maintain() is True               # a caller's call folds now
    assert not idx.store._delta_slots


def test_dynamic_upgrade_parity(rng):
    """The threshold-crossing insert swaps flat -> residual-PQ IVF with
    no serving regression: the upgraded index answers with the same
    neighbors (full probe + exact rescore), keeps batched-filter
    support, and takes maintenance ticks."""
    n, d, k = 2600, 24, 5
    vecs = rng.standard_normal((n, d)).astype(np.float32)
    q = (vecs[7] + 0.0001).astype(np.float32)[None, :]
    dyn = DynamicIndex(dim=d, threshold=2000, nlist=16, nprobe=16,
                       upgrade_quantization="pq")
    dyn.add_batch(np.arange(1999), vecs[:1999])
    assert not dyn.upgraded
    ids_flat, _ = dyn.search_by_vector_batch(q, k)
    dyn.add_batch(np.arange(1999, n), vecs[1999:])
    assert dyn.upgraded and dyn.compressed
    assert dyn.supports_batched_filters
    ids_ivf, _ = dyn.search_by_vector_batch(q, k)
    assert ids_ivf[0][0] == 7
    assert len(set(ids_flat[0].tolist()) & set(ids_ivf[0].tolist())) >= 4
    dyn.maintain()  # forwards to the IVF impl without error
    ids2, _ = dyn.search_by_vector_batch(q, k)
    assert ids2[0][0] == 7


def test_ivf_filtered_requests_coalesce(rng):
    """Filtered IVF searches ride ONE bitmask-batched dispatch through
    the QueryBatcher (ISSUE 16 acceptance: the batcher_filtered_batched
    counter moves, nothing routes solo)."""
    import threading
    import time

    from weaviate_tpu.runtime.query_batcher import QueryBatcher

    n, d, k = 1500, 16, 5
    vecs = rng.standard_normal((n, d)).astype(np.float32)
    idx = IVFIndex(dim=d, train_threshold=800, delta_threshold=256,
                   nlist=16, nprobe=16)
    idx.add_batch(np.arange(n), vecs)
    idx.store.flush_delta()
    calls = []
    real = idx.search_by_vector_batch

    def counting(qs, kk, allow=None):
        calls.append({"rows": len(qs),
                      "per_query": isinstance(allow, (list, tuple))})
        return real(qs, kk, allow)

    qb = QueryBatcher(
        counting,
        supports_filter_batching=lambda: idx.supports_batched_filters)
    nreq = 9
    queries = rng.standard_normal((nreq, d)).astype(np.float32)
    allows = [np.flatnonzero(rng.random(n) < 0.4).astype(np.int64)
              for _ in range(nreq)]
    gate = threading.Event()
    first = threading.Event()
    inner = qb._batch_fn

    def slow_first(qs, kk, allow=None):
        if not first.is_set():
            first.set()
            gate.wait(5.0)
        return inner(qs, kk, allow)

    qb._batch_fn = slow_first
    results = [None] * nreq

    def worker(j):
        results[j] = qb.search(queries[j], k, allows[j])

    threads = [threading.Thread(target=worker, args=(j,))
               for j in range(nreq)]
    threads[0].start()
    time.sleep(0.1)
    for t in threads[1:]:
        t.start()
    time.sleep(0.3)
    gate.set()
    for t in threads:
        t.join()
    qb.stop()
    assert qb.filtered_batched >= nreq - 1, qb.filtered_batched
    coalesced = [c for c in calls if c["rows"] > 1]
    assert len(coalesced) == 1 and coalesced[0]["per_query"], calls
    for j in range(nreq):
        ids, _ = results[j]
        ref_i, _ = idx.search_by_vector_batch(
            queries[j][None, :], k, [allows[j]])
        got = np.asarray(ids)
        assert np.array_equal(got[got >= 0], ref_i[0][ref_i[0] >= 0]), j


def test_ivf_host_mirror_ledger_lifecycle(rng):
    """The residual-PQ host f32 mirror is ledger-visible as a HOST-tier
    component (never admission-gated device bytes) and releases when the
    store is dropped."""
    import gc

    from weaviate_tpu.runtime import hbm_ledger

    n, d = 3000, 16
    vecs = rng.standard_normal((n, d)).astype(np.float32)
    col = "IvfMirrorTest"
    with hbm_ledger.owner(collection=col, shard="s0"):
        idx = IVFIndex(dim=d, train_threshold=1000, delta_threshold=256,
                       quantization="pq")
        idx.add_batch(np.arange(n), vecs)
    bd = hbm_ledger.ledger.breakdown()[col]
    assert bd["components"].get("host_mirror", 0) >= n * d * 4
    assert bd["components"].get("list_codes", 0) > 0
    # host tier by contract: mirror bytes never count as device bytes
    mirror_entries = [e for e in hbm_ledger.ledger.top(200)
                      if e["collection"] == col
                      and e["component"] == "host_mirror"]
    assert mirror_entries and all(
        e["placement"] == "host" for e in mirror_entries)
    del idx
    gc.collect()
    bd = hbm_ledger.ledger.breakdown().get(col)
    assert bd is None or bd["components"].get("host_mirror", 0) == 0


def test_kmeans_reseeds_empty_clusters(rng):
    """Empty clusters reseed deterministically from the fullest
    cluster's farthest members; kmeans_fit never returns dead lists."""
    import jax.numpy as jnp

    from weaviate_tpu.ops import kmeans as km

    vecs = rng.standard_normal((256, 8)).astype(np.float32)
    cents = vecs[:4].copy()
    cents[2] = 1e4  # parked far away: nothing assigns to it
    assign = km.kmeans_assign(vecs, cents)
    counts = np.bincount(assign, minlength=4).astype(np.float32)
    assert counts[2] == 0
    out1 = np.asarray(km._reseed_empty(vecs, jnp.asarray(cents), counts,
                                       batch=4096))
    out2 = np.asarray(km._reseed_empty(vecs, jnp.asarray(cents), counts,
                                       batch=4096))
    assert np.array_equal(out1, out2)  # no RNG in the reseed
    # the reseed target is a REAL data point, and it revives the cluster
    assert (out1[2][None] == vecs).all(axis=1).any()
    a2 = km.kmeans_assign(vecs, out1)
    assert (np.bincount(a2, minlength=4) > 0).all()
    # end-to-end: a fit over duplicate-heavy data keeps every centroid live
    blob = np.repeat(rng.standard_normal((6, 8)).astype(np.float32), 50, 0)
    blob += 0.01 * rng.standard_normal(blob.shape).astype(np.float32)
    cents_fit = km.kmeans_fit(blob, k=8, iters=6, seed=0)
    fit_counts = np.bincount(km.kmeans_assign(blob, cents_fit),
                             minlength=8)
    assert (fit_counts > 0).all(), fit_counts
