"""PQ/BQ/SQ conformance and recall tests.

Mirrors the reference's compression tests (compressionhelpers tests +
hnsw/compress_recall_test.go): codebook quality, encode/decode roundtrip,
ADC-equivalence, and end-to-end recall of compressed search with rescore.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from weaviate_tpu.engine.flat import FlatIndex
from weaviate_tpu.engine.quantized import QuantizedVectorStore
from weaviate_tpu.ops import bq as bq_ops
from weaviate_tpu.ops import pq as pq_ops


def clustered_data(rng, n=2000, dim=32, n_clusters=16):
    centers = rng.standard_normal((n_clusters, dim)).astype(np.float32) * 5
    assign = rng.integers(0, n_clusters, n)
    return (centers[assign] + rng.standard_normal((n, dim)).astype(np.float32) * 0.3)


# -- PQ ops ------------------------------------------------------------------

def test_pq_fit_encode_roundtrip(rng):
    x = clustered_data(rng)
    cb = pq_ops.pq_fit(x, m=8, k=16, iters=6)
    assert cb.centroids.shape == (8, 16, 4)
    codes = pq_ops.pq_encode(cb, x)
    assert codes.shape == (2000, 8) and codes.dtype == np.uint8
    # reconstruction error must be far below data scale
    x_hat = np.asarray(pq_ops.pq_reconstruct(jnp.asarray(codes), cb.centroids, 8))
    rel_err = np.linalg.norm(x_hat - x) / np.linalg.norm(x)
    assert rel_err < 0.5


def test_pq_topk_matches_adc_lut(rng):
    """reconstruct-matmul distances == classic per-query LUT ADC distances."""
    x = clustered_data(rng, n=256, dim=16)
    q = rng.standard_normal((2, 16)).astype(np.float32)
    cb = pq_ops.pq_fit(x, m=4, k=8, iters=4)
    codes = pq_ops.pq_encode(cb, x)
    d, i = pq_ops.pq_topk(jnp.asarray(q), jnp.asarray(codes), cb.centroids,
                          k=5, chunk_size=256)
    # numpy LUT-ADC reference (reference product_quantization.go:440)
    cents = np.asarray(cb.centroids)  # [m, k, ds]
    qs = q.reshape(2, 4, 4)
    lut = ((qs[:, :, None, :] - cents[None]) ** 2).sum(-1)  # [B, m, k]
    adc = np.zeros((2, 256), np.float32)
    for b in range(2):
        for n in range(256):
            adc[b, n] = sum(lut[b, m, codes[n, m]] for m in range(4))
    want = np.sort(adc, axis=1)[:, :5]
    np.testing.assert_allclose(np.asarray(d), want, rtol=1e-3, atol=1e-3)


def test_pq_recall_on_clustered_data(rng):
    # wider within-cluster spread + finer segmentation: the un-rescored
    # compressed scan must still rank mostly-correct neighbors (end-to-end
    # recall with rescore is asserted in test_flat_index_compress_runtime)
    centers = rng.standard_normal((16, 64)).astype(np.float32) * 5
    x = (centers[rng.integers(0, 16, 4000)]
         + rng.standard_normal((4000, 64)).astype(np.float32) * 1.5)
    q = x[rng.choice(4000, 20, replace=False)] \
        + rng.standard_normal((20, 64)).astype(np.float32) * 0.3
    cb = pq_ops.pq_fit(x, m=32, k=64, iters=10)
    codes = pq_ops.pq_encode(cb, x)
    d, i = pq_ops.pq_topk(jnp.asarray(q), jnp.asarray(codes), cb.centroids,
                          k=10, chunk_size=500)
    gt = np.argsort(((q[:, None] - x[None]) ** 2).sum(-1), axis=1)[:, :10]
    recall = np.mean([len(set(np.asarray(i)[r]) & set(gt[r])) / 10 for r in range(20)])
    assert recall > 0.45, recall  # un-rescored compressed recall


# -- the look-up and the scan's contract (ISSUE 29) ---------------------------

import pq_reference  # noqa: E402 — the plain numpy quantizer, tests/


@pytest.mark.parametrize("m,ds,k", pq_reference.GEOMETRIES_8BIT)
def test_pq_reconstruct_is_the_table_lookup(rng, m, ds, k):
    """``[N, m] u8 -> [N, d] f32``, bit-equal to ``centroids[s, codes[:, s]]``
    whatever the geometry (the cell's 96 x 1 x 256, the default d/8 x 8 x
    256, fewer centroids, a count that is no power of two)."""
    cent = rng.standard_normal((m, k, ds)).astype(np.float32)
    codes = rng.integers(0, k, (700, m)).astype(np.uint8)
    codes[0], codes[1] = 0, k - 1
    got = np.asarray(pq_ops.pq_reconstruct(jnp.asarray(codes),
                                           jnp.asarray(cent), m))
    assert got.dtype == np.float32
    assert got.tobytes() == pq_reference.reconstruct(cent, codes).tobytes()


@pytest.mark.parametrize("b", [1, 3, 32])
@pytest.mark.parametrize("chunk", [1024, 256], ids=["one-chunk", "four-chunks"])
@pytest.mark.parametrize("masks", ["valid", "valid+allow_bits"])
@pytest.mark.parametrize("metric", ["l2-squared", "dot", "cosine"])
def test_pq_topk_against_the_plain_quantizer(rng, metric, masks, chunk, b):
    """``pq_topk`` on a shared codebook and shared codes against
    tests/pq_reference.py: the same candidates (up to rows within 1e-4 of
    the cut) at the same distances, with dead rows, with a per-query allow
    list, over one chunk and over several, at three batch sizes."""
    from weaviate_tpu.ops.pallas_kernels import pack_allow_bitmask

    n, m, ds, k, n_cand = 1024, 8, 4, 64, 10
    cent = (rng.standard_normal((m, k, ds)) * 0.3).astype(np.float32)
    codes = rng.integers(0, k, (n, m)).astype(np.uint8)
    q = rng.standard_normal((b, m * ds)).astype(np.float32)
    if metric == "cosine":
        q /= np.linalg.norm(q, axis=1, keepdims=True)
    valid = rng.random(n) > 0.1
    allow = None
    if masks == "valid+allow_bits":
        allow = rng.random((b, n)) > 0.5
    d, i = pq_ops.pq_topk(
        jnp.asarray(q), jnp.asarray(codes), jnp.asarray(cent), k=n_cand,
        chunk_size=chunk, metric=metric, valid=jnp.asarray(valid),
        allow_bits=(None if allow is None
                    else jnp.asarray(pack_allow_bitmask(allow))))
    d, i = np.asarray(d), np.asarray(i)
    for r in range(b):
        live = valid if allow is None else valid & allow[r]
        want, dist = pq_reference.candidates(cent, codes, q[r], metric,
                                             n_cand, live)
        got = set(i[r].tolist())
        assert len(got) == n_cand and live[i[r]].all()
        cut = dist[want[-1]]
        for row in got ^ set(want.tolist()):
            assert abs(dist[row] - cut) < 1e-4, (r, row, dist[row], cut)
        np.testing.assert_allclose(d[r], dist[i[r]], rtol=1e-5, atol=1e-4)
        assert (np.diff(d[r]) >= 0).all()


# -- BQ ops ------------------------------------------------------------------

def test_bq_encode_matches_numpy(rng):
    x = rng.standard_normal((16, 70)).astype(np.float32)  # 70 -> 3 words padded
    words = np.asarray(bq_ops.bq_encode(jnp.asarray(x)))
    assert words.shape == (16, 3)
    want_bits = (x >= 0)
    for r in range(16):
        for j in range(70):
            w, b = divmod(j, 32)
            assert bool((words[r, w] >> b) & 1) == want_bits[r, j]


def test_bq_topk_is_hamming(rng):
    x = rng.standard_normal((128, 64)).astype(np.float32)
    q = rng.standard_normal((3, 64)).astype(np.float32)
    xw = bq_ops.bq_encode(jnp.asarray(x))
    qw = bq_ops.bq_encode(jnp.asarray(q))
    d, i = bq_ops.bq_topk(qw, xw, k=5, chunk_size=128)
    ham = bq_ops.bq_hamming_np(np.asarray(qw), np.asarray(xw))
    want = np.sort(ham, axis=1)[:, :5]
    np.testing.assert_array_equal(np.asarray(d), want.astype(np.float32))


# -- quantized store / index -------------------------------------------------

def test_bq_store_search_with_rescore(rng):
    store = QuantizedVectorStore(dim=64, quantization="bq", capacity=512,
                                 chunk_size=512, rescore_limit=8)
    x = rng.standard_normal((300, 64)).astype(np.float32)
    store.add(x)
    d, i = store.search(x[17], k=5)
    assert i[0] == 17 and d[0] < 1e-3  # rescore restores exact self-match
    store.delete([17])
    d, i = store.search(x[17], k=5)
    assert i[0] != 17


def test_pq_store_lifecycle(rng):
    x = clustered_data(rng, n=1000, dim=32)
    store = QuantizedVectorStore(dim=32, quantization="pq", capacity=1024,
                                 chunk_size=1024, pq_segments=8,
                                 pq_centroids=32, rescore_limit=8)
    store.train(x)
    store.add(x)
    d, i = store.search(x[3], k=5)
    assert i[0] == 3 and d[0] < 1e-3


@pytest.mark.parametrize("quantization", ["pq", "sq"])
def test_untrained_pq_store_raises_on_search(rng, quantization):
    store = QuantizedVectorStore(dim=16, quantization=quantization,
                                 pq_centroids=8)
    # adds are allowed before training (vectors accumulate on host)...
    store.add(rng.standard_normal((40, 16)).astype(np.float32))
    # ...but searching without a codebook must fail loudly
    with pytest.raises(RuntimeError):
        store.search(rng.standard_normal(16).astype(np.float32), k=3)
    # train() on current contents unlocks search and encodes the backlog
    store.train()
    d, i = store.search(store.get([7])[0], k=1)
    assert i[0] == 7


@pytest.mark.parametrize("quantization,kwargs", [
    ("pq", dict(pq_segments=8, pq_centroids=64)), ("sq", {})],
    ids=["pq", "sq"])
def test_flat_index_compress_runtime(rng, quantization, kwargs):
    """Reference compress.go semantics: build uncompressed, compress at
    runtime, mapping and recall preserved."""
    x = clustered_data(rng, n=1200, dim=32)
    idx = FlatIndex(dim=32, capacity=2048, chunk_size=2048)
    ids = np.arange(1200) + 10_000
    idx.add_batch(ids, x)
    idx.delete(ids[7])
    assert not idx.compressed
    idx.compress(quantization, rescore_limit=8, **kwargs)
    assert idx.compressed and idx.store.quantization == quantization
    got, d = idx.search_by_vector(x[100], k=5)
    assert got[0] == ids[100] and d[0] < 1e-3
    got, _ = idx.search_by_vector(x[7], k=5)
    assert ids[7] not in got  # tombstone survived compression
    # recall@10 with rescore must be high
    q = clustered_data(rng, n=10, dim=32)
    gt = np.argsort(((q[:, None] - x[None]) ** 2).sum(-1), axis=1)[:, :10]
    hits = 0
    for r in range(10):
        got, _ = idx.search_by_vector(q[r], k=10)
        hits += len(set((got - 10_000).tolist()) & set(gt[r].tolist()))
    assert hits / 100 > 0.85, hits / 100


@pytest.mark.parametrize("quantization", ["bq", "sq"])
def test_quantized_snapshot_restore(rng, quantization):
    x = clustered_data(rng, n=600, dim=32)
    idx = FlatIndex(dim=32, capacity=1024, chunk_size=1024,
                    quantization=quantization, rescore_limit=8)
    idx.add_batch(np.arange(600), x)
    idx.store.train()        # bq: nothing to fit; sq: the rows' range
    snap = idx.snapshot()
    idx2 = FlatIndex.restore(snap)
    assert idx2.compressed and idx2.store.trained
    assert np.array_equal(np.asarray(idx2.store.codes),
                          np.asarray(idx.store.codes))
    got, d = idx2.search_by_vector(x[42], k=3)
    assert got[0] == 42 and d[0] < 1e-3


@pytest.mark.parametrize("legacy", ["fused", "exact"])
@pytest.mark.parametrize("kind", ["bq", "pq", "sq", "epochs"])
def test_a_legacy_snapshot_with_a_selection_restores(rng, kind, legacy):
    """Snapshots written before PR 48 carry the selector a store was
    built with. ``restore()`` reads past the key, whatever its value: the
    twin answers as the store that never had one, and writes none."""
    from weaviate_tpu.engine.epochs import EpochStore

    x = clustered_data(rng, n=600, dim=32)
    if kind == "epochs":
        cls = EpochStore
        store = cls(dim=32, epoch_rows=256, capacity=256, chunk_size=256)
    else:
        cls = QuantizedVectorStore
        store = cls(dim=32, quantization=kind, capacity=1024,
                    chunk_size=1024, pq_segments=8, pq_centroids=16,
                    rescore_limit=8)
        store.train(x)
    store.add(x)
    store.delete([5, 6])
    snap = store.snapshot()
    assert "selection" not in snap
    twin = cls.restore(dict(snap, selection=legacy))
    assert not hasattr(twin, "selection")
    assert "selection" not in twin.snapshot()
    d0, i0 = store.search(x[40:44], k=5)
    d1, i1 = twin.search(x[40:44], k=5)
    assert np.array_equal(i0, i1) and np.array_equal(d0, d1)
    assert list(i0[:, 0]) == [40, 41, 42, 43]


@pytest.mark.parametrize("quantization", ["bq", "sq"])
def test_compress_twice_raises(rng, quantization):
    x = clustered_data(rng, n=300, dim=16)
    idx = FlatIndex(dim=16, capacity=512, chunk_size=512)
    idx.add_batch(np.arange(300), x)
    idx.compress(quantization)
    with pytest.raises(RuntimeError):
        idx.compress(quantization)


def test_pq_twostage_prefix_matches_full_scan():
    """Two-stage PQ (BQ sign prefix stage 1 + gathered ADC stage 2,
    ops/pq.pq_topk_twostage) must reach the same rescored results as the
    exhaustive PQ scan on clustered data."""
    import numpy as np

    from weaviate_tpu.engine.quantized import QuantizedVectorStore

    rng = np.random.default_rng(11)
    centers = rng.standard_normal((40, 256)).astype(np.float32) * 2.0
    xs = (centers[rng.integers(0, 40, 3000)]
          + 0.3 * rng.standard_normal((3000, 256))).astype(np.float32)
    qs = xs[rng.integers(0, 3000, 8)] + 0.05 * rng.standard_normal(
        (8, 256)).astype(np.float32)

    full = QuantizedVectorStore(dim=256, quantization="pq", rescore="host")
    two = QuantizedVectorStore(dim=256, quantization="pq", rescore="host",
                               prefix_bits=128)
    for st in (full, two):
        st.train(xs[:2000])
        st.add(xs)
    assert two.prefix_words == 4 and two.prefix_t is not None
    d_f, i_f = full.search(qs, k=10)
    d_t, i_t = two.search(qs, k=10)
    overlap = np.mean([
        len(set(i_f[r].tolist()) & set(i_t[r].tolist())) / 10
        for r in range(len(qs))])
    assert overlap >= 0.9, overlap
    assert i_t[0, 0] == i_f[0, 0]  # self-hit survives the prefix


def test_pq_twostage_snapshot_roundtrip_codes_only():
    """Codes-only snapshots must carry the PQ prefix (it cannot be
    rebuilt from codes)."""
    import numpy as np

    from weaviate_tpu.engine.quantized import QuantizedVectorStore

    rng = np.random.default_rng(3)
    xs = rng.standard_normal((500, 160)).astype(np.float32)
    st = QuantizedVectorStore(dim=160, quantization="pq", rescore="none",
                              prefix_bits=128)
    st.train(xs)
    st.add(xs)
    snap = st.snapshot()
    assert snap.get("prefix_t") is not None
    st2 = QuantizedVectorStore.restore(snap)
    assert st2.prefix_t is not None
    d1, i1 = st.search(xs[:4], k=5)
    d2, i2 = st2.search(xs[:4], k=5)
    assert np.array_equal(i1, i2)


def test_pq_twostage_train_after_add_rebuilds_prefix():
    """train() after add() must re-derive the sign prefix (the re-encode
    path scatters codes AND prefix; a zeroed prefix silently floors
    stage-1 recall)."""
    import numpy as np

    from weaviate_tpu.engine.quantized import QuantizedVectorStore

    rng = np.random.default_rng(5)
    xs = rng.standard_normal((2000, 256)).astype(np.float32)
    st = QuantizedVectorStore(dim=256, quantization="pq", rescore="host",
                              prefix_bits=128)
    st.add(xs)          # untrained: codes+prefix deferred
    st.train(xs[:1500])
    pt = np.asarray(st.prefix_t)
    assert pt[:, :2000].any(), "prefix still zeroed after train()"
    d, i = st.search(xs[:6], k=5)
    assert (i[:, 0] == np.arange(6)).all()


def test_pq_twostage_chunked_stage2_matches_unchunked():
    """The R-chunked one-hot stage 2 (HBM-transient bound) must produce
    identical results to the unchunked path."""
    import jax.numpy as jnp
    import numpy as np

    from weaviate_tpu.ops import bq as bq_ops
    from weaviate_tpu.ops import pq as pq_ops

    rng = np.random.default_rng(8)
    n, d, m = 4096, 160, 40
    xs = rng.standard_normal((n, d)).astype(np.float32)
    book = pq_ops.pq_fit(xs, m=m, k=16, iters=4)
    codes = jnp.asarray(pq_ops.pq_encode(book, xs))
    prefix_t = jnp.transpose(bq_ops.bq_encode(jnp.asarray(xs[:, :128])))
    q = jnp.asarray(xs[:6] + 0.01 * rng.standard_normal((6, d)).astype(
        np.float32))
    qp = bq_ops.bq_encode(q[:, :128])
    d1, i1 = pq_ops.pq_topk_twostage(q, qp, codes, book.centroids,
                                     prefix_t, k=20, refine=8,
                                     use_pallas=False)
    # tiny budget forces many R-chunks
    d2, i2 = pq_ops.pq_topk_twostage(q, qp, codes, book.centroids,
                                     prefix_t, k=20, refine=8,
                                     use_pallas=False,
                                     chunk_budget_bytes=16384)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    assert np.allclose(np.asarray(d1), np.asarray(d2), rtol=1e-5,
                       atol=1e-5)


def test_prefix_bits_reachable_from_schema_api(tmp_path):
    """The two-stage prefix must be configurable through the public
    vectorIndexConfig wire (snake_case passthrough), not only the engine
    constructor."""
    import numpy as np

    from weaviate_tpu.api.rest import _index_config_from_json
    from weaviate_tpu.db.database import Database
    from weaviate_tpu.schema.config import (
        CollectionConfig,
        Property,
        VectorIndexConfig,
    )

    cfg = _index_config_from_json("flat", {"bq": {"enabled": True},
                                           "prefix_bits": 128})
    assert cfg.quantization == "bq" and cfg.prefix_bits == 128

    db = Database(str(tmp_path))
    from weaviate_tpu.schema.config import VectorConfig

    col = db.create_collection(CollectionConfig(
        name="Pfx",
        vectors=[VectorConfig(index=VectorIndexConfig(
            index_type="flat", quantization="bq", prefix_bits=128))],
        properties=[Property(name="s", data_type="int")]))
    rng = np.random.default_rng(0)
    vecs = rng.standard_normal((500, 256)).astype(np.float32)
    col.batch_put([{"properties": {"s": i}, "vector": vecs[i]}
                   for i in range(500)])
    shard = next(iter(col.shards.values()))
    store = shard.vector_indexes[""].store
    assert store.prefix_words == 4 and store.prefix_t is not None
    r = col.near_vector(vecs[9], k=3)
    assert r[0].object.properties["s"] == 9
    db.close()
